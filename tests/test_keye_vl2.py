"""The KeyeVL2 family through ``models/hybrid_lm.HybridMoELM`` (``model_type:
KeyeVL2``: grouped attention over the keys a learned indexer selects, with the
indexer's alignment loss; a softmax router without a shared expert; an untied
head) against the plain reference ``benchmark/reference/keye_vl2.py``, at a
small size on the CPU: widths of a few tens, 2 layers, 4 index heads of 8
over one shared key, ``topk`` 8 at T = 37 (more than twice it, so most rows
drop most of their keys), query blocks of 24 in two runs, 16 experts top-4
of which 8 are held.

Float32 comparisons run under ``default_matmul_precision("highest")``; what
is left is summation order (a threshold and a mask against ``lax.top_k``,
blockwise against full attention, grouped against expert-by-expert), so the
bounds are a few float32 roundings: 2e-5 relative, 5e-4 on gradients.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as ref
from sparknet_tpu.models.hybrid_lm import (
    DSA_SCOPES, HybridMoELM, describe, routing_gauges)
from sparknet_tpu.ops import moe
from sparknet_tpu.ops import sparse_attention as sa

SMALL = {
    "model_type": "KeyeVL2", "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 24,
                  "q_chunk_size": 24, "topk": 8},
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    # this system's own key
    "experts_held": [4, 8],
}
# the published widths at the benchmark's cut
# (benchmark/configs/keye-vl-2.0-30b-a3b.json)
PUBLISHED = {
    **SMALL, "vocab_size": 18992, "hidden_size": 2048, "num_hidden_layers": 4,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "num_experts": 128, "num_experts_per_tok": 8, "moe_intermediate_size": 768,
    "experts_held": [0, 16],
}
T = 37  # odd, more than 2 x topk, and not a multiple of the query block


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def seeded(model, seed=0):
    """Seeded weights; the vectors are moved off their initial values and
    the matrices widened to std 0.1, so that a test cannot pass by ignoring
    a term."""
    params, _ = model.init(seed)
    key = jax.random.key(seed + 100)
    for gi, (group, blobs) in enumerate(sorted(params.items())):
        for bi, blob in enumerate(blobs):
            if blob.ndim == 1:
                k = jax.random.fold_in(jax.random.fold_in(key, gi), bi)
                blobs[bi] = blob + 0.1 * jax.random.normal(k, blob.shape)
            else:
                blobs[bi] = blob * 5.0
    return params


def batch(seed, b=2, t=T, vocab=SMALL["vocab_size"]):
    tokens = jax.random.randint(jax.random.key(seed), (b, t + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def is_indexer(group, index):
    return group.endswith("_mixer") and index >= 6


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(SMALL)


@pytest.fixture(scope="module")
def params(model):
    return seeded(model)


# -- what is built ---------------------------------------------------------
def test_layers_groups_and_the_indexers_blobs(model):
    c = model.config
    assert c["mixers"] == ("dsa_attention",) * 2 and c["ffns"] == ("moe",) * 2
    assert all(model.is_attention_layer(i) for i in range(2))
    assert model.routed_layers == (0, 1) and model.biased_routers == ()
    groups = [g for g, _ in model._group_blobs]
    assert groups[0] == "embed" and groups[-2:] == ["norm_f", "head"]  # untied
    assert not any(g.endswith(("_shared", "_mlp")) for g in groups)
    sizes = dict(model._group_blobs)
    assert sizes["l1_mixer"] == [
        (32, 32), (32, 16), (32, 16), (8,), (8,), (32, 32),  # plain attention
        (32, 32), (32, 8), (8,), (8,), (32, 4)]  # the indexer, in its group
    assert (c["index_heads"], c["index_dim"], c["index_topk"],
            c["index_block"]) == (4, 8, 8, 24)
    assert c["router_scores"] == "softmax" and not c["tied"]
    assert c["rotary_dim"] == c["head_dim"] and not c["zero_centred_norm"]
    assert DSA_SCOPES == ("DSAIndexer", "DSASelect", "DSAAttention",
                          "DSAIndexerLoss")


@pytest.mark.parametrize("group, count", [
    ("l0_mixer", 8_388_608 + 2_097_152 + 8_388_608 + 256 + 2_261_120),
    ("l0_router", 262_144),
    ("l0_experts", 75_497_472),  # 16 held experts of 768
    ("embed", 38_895_616), ("head", 38_895_616),  # 18,992 rows each
])
def test_parameter_count_at_the_published_widths(group, count):
    sizes = dict(HybridMoELM(PUBLISHED)._group_blobs)
    assert sum(int(np.prod(s)) for s in sizes[group]) == count


def test_parameter_count_is_a_walk_of_the_shapes():
    published = HybridMoELM(PUBLISHED)
    assert published.num_params() == 465_391_104  # ISSUE 33's arithmetic
    shapes, stats = jax.eval_shape(published.init)
    assert stats == {}
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == published.num_params()
    sizes = dict(published._group_blobs)
    indexer = sum(int(np.prod(s)) for s in sizes["l0_mixer"][6:])
    assert indexer == 2_097_152 + 131_072 + 128 + 32_768 == 2_261_120
    layer = sum(int(np.prod(s)) for g in (
        "l0_n1", "l0_mixer", "l0_n2", "l0_router", "l0_experts")
        for s in sizes[g])
    assert layer == 96_899_456 and 4 * layer == 387_597_824
    assert published.num_params() - 4 * layer == 77_793_280


@pytest.mark.parametrize("change, message", [
    ({"sa_config": {**SMALL["sa_config"], "indexer_num_kv_heads": 2}}, "ONE key"),
    ({"sa_config": {"topk": 8}}, "sa_config: configuration lacks"),
    ({"mlp_only_layers": [0]}, "every layer routes"),
    ({"decoder_sparse_step": 2}, "every layer routes"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"model_type": "KeyeVL3"}, "model_type"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
])
def test_a_configuration_it_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        HybridMoELM({**SMALL, **change})


def test_a_missing_key_is_named():
    config = {k: v for k, v in SMALL.items() if k != "sa_config"}
    with pytest.raises(ValueError, match="sa_config"):
        HybridMoELM(config)


def test_initialisation(model):
    params, stats = model.init(3)
    assert stats == {}
    mixer = params["l0_mixer"]
    assert float(jnp.min(mixer[3])) == 1.0  # head norms at one
    assert np.all(np.asarray(mixer[8]) == 1.0)  # the LayerNorm's weight
    assert not np.asarray(mixer[9]).any()  # ... and its bias
    assert 0.015 < float(jnp.std(mixer[6])) < 0.025
    lr, decay = model.param_multipliers()
    assert decay["l0_mixer"] == [1.0, 1.0, 1.0, 0.0, 0.0, 1.0,
                                 1.0, 1.0, 0.0, 0.0, 1.0]
    assert all(x == 1.0 for xs in lr.values() for x in xs)


# -- against the reference -------------------------------------------------
def test_logits_both_losses_and_every_gradient_match_the_reference(
        model, params):
    data = batch(1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"])
        want = jax.jit(lambda p, t: ref.logits(p, t, SMALL))(
            params, data["tokens"])
        assert got.shape == (2, T, SMALL["vocab_size"])
        assert rel(got, want) < 2e-5
        (loss, (aux, stats)), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, {}, data)
        want_lm, want_index = jax.jit(lambda p, b: ref.losses(
            p, b["tokens"], b["targets"], SMALL))(params, data)
        want_grads = jax.jit(jax.grad(lambda p, b: ref.loss(
            p, b["tokens"], b["targets"], SMALL)))(params, data)
    assert stats == {}
    assert abs(float(aux["lm_loss"]) - float(want_lm)) < 2e-5 * float(want_lm)
    assert abs(float(aux["indexer_loss"]) - float(want_index)) < (
        2e-5 * float(want_index))
    assert float(loss) == pytest.approx(
        float(aux["lm_loss"]) + float(aux["indexer_loss"]), rel=1e-6)
    assert aux["indexer_loss_by_layer"].shape == (2,)
    assert float(jnp.min(aux["indexer_loss_by_layer"])) > 1e-3  # in play
    errors = {
        f"{group}[{i}]": rel(g, w)
        for group in grads
        for i, (g, w) in enumerate(zip(grads[group], want_grads[group]))
    }
    assert set(errors) == {
        f"{g}[{i}]" for g, blobs in params.items() for i in range(len(blobs))}
    worst = max(errors, key=errors.get)
    assert errors[worst] < 5e-4, (worst, errors[worst])
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for blobs in grads.values() for g in blobs)


def test_the_two_losses_reach_disjoint_parameters(model, params):
    """``L_LM`` reaches every parameter but the indexer's, exactly; ``L_I``
    reaches those alone, exactly."""
    data = batch(2)
    part = lambda name: jax.grad(  # noqa: E731
        lambda p: model.loss_fn(p, {}, data)[1][0][name])
    from_lm, from_index = jax.jit(
        lambda p: (part("lm_loss")(p), part("indexer_loss")(p)))(params)
    for group, blobs in params.items():
        for i in range(len(blobs)):
            lm = float(jnp.max(jnp.abs(from_lm[group][i])))
            index = float(jnp.max(jnp.abs(from_index[group][i])))
            if is_indexer(group, i):
                assert lm == 0.0 and index > 0.0, (group, i)
            else:
                assert lm > 0.0 and index == 0.0, (group, i)


def test_attention_over_fewer_keys_is_not_dense_attention(model, params):
    """At T > 2 x topk the selection drops most pairs: the model with
    ``topk`` past T (nothing dropped) reads otherwise, and agrees with the
    reference given that ``topk``."""
    data = batch(3)
    dense = {**SMALL, "sa_config": {**SMALL["sa_config"], "topk": 64}}
    with jax.default_matmul_precision("highest"):
        sparse = jax.jit(model.forward_logits)(params, data["tokens"])
        full = jax.jit(HybridMoELM(dense).forward_logits)(params, data["tokens"])
        want = jax.jit(lambda p, t: ref.logits(p, t, dense))(
            params, data["tokens"])
    assert rel(full, want) < 2e-5
    assert rel(sparse, full) > 1e-2


MID = {
    **SMALL, "vocab_size": 1024, "hidden_size": 128, "num_hidden_layers": 3,
    "head_dim": 32, "num_experts": 32, "moe_intermediate_size": 32,
    "experts_held": [0, 8],
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 128,
                  "q_chunk_size": 128, "topk": 128},
}


def test_where_the_seeded_weights_start():
    """``init_std``: the embedding normal(0, 1), the matrices that write to
    the residual stream 0.02 / sqrt(2 x layers), every other matrix 0.02;
    the grouped rows are ``ops/moe.ROWS_SLACK``'s for every family."""
    model = HybridMoELM(MID)
    params, _ = model.init(0)
    std = lambda x: float(jnp.std(x))  # noqa: E731
    assert std(params["embed"][0]) == pytest.approx(1.0, rel=0.02)
    out = 0.02 * 6 ** -0.5
    for i in range(3):
        mixer, experts = params[f"l{i}_mixer"], params[f"l{i}_experts"]
        assert std(mixer[5]) == pytest.approx(out, rel=0.03)
        assert std(experts[2]) == pytest.approx(out, rel=0.03)
        for blob in (*mixer[:3], mixer[6], mixer[7], mixer[10], *experts[:2],
                     params[f"l{i}_router"][0]):
            assert std(blob) == pytest.approx(0.02, rel=0.05)
    assert std(params["head"][0]) == pytest.approx(0.02, rel=0.02)
    assert not hasattr(model, "expert_rows_slack")
    assert moe.fast_rows_for(16384, 8, 128, 16) == moe.ROWS_SLACK * 16384


@pytest.mark.parametrize("seed", [0, 3])
def test_seeded_weights_route_by_token(seed):
    """On Zipf tokens every layer sends the held experts about the expected
    ``top_k * held / experts`` = 1 assignment a token, inside the cell's
    band of half to twice it.  With every matrix at 0.02 the router's input
    is the flat attention's output, the same for every token, and a layer's
    share is a draw: one falls outside the band."""
    from benchmark import lm_checks

    model = HybridMoELM(MID)
    params, _ = model.init(seed)
    tokens = lm_checks.zipf_tokens(jax.random.key(seed), (1, 512), 1024, 1.0)
    plain = {g: [b * (0.02 / jnp.std(b)) if b.ndim > 1 else b for b in blobs]
             for g, blobs in params.items()}
    load = lambda p: routing_gauges(  # noqa: E731
        jax.jit(model.routing_counts)(p, tokens), 512)[
            "held_assignments_per_token"]
    assert all(0.5 <= x <= 2.0 for x in load(params)), load(params)
    assert not all(0.5 <= x <= 2.0 for x in load(plain)), load(plain)


def test_bf16_compute_is_near_float32_and_not_float32(model):
    data = batch(2)
    params, _ = model.init(0)
    low = HybridMoELM({**SMALL, "compute_dtype": "bfloat16"})
    exact = jax.jit(model.forward_logits)(params, data["tokens"])
    got = jax.jit(low.forward_logits)(params, data["tokens"])
    assert got.dtype == jnp.float32
    # (a selection of 8 of up to 37 keys moves wholesale where bfloat16
    # changes the order of two scores: more than rounding alone)
    assert 1e-4 < rel(got, exact) < 1e-1


# -- the selection -----------------------------------------------------------
def indexer_inputs(seed, b=2, t=T, j=4, di=8):
    key = jax.random.key(seed)
    qi = jax.random.normal(jax.random.fold_in(key, 0), (b, t, j, di))
    ki = jax.random.normal(jax.random.fold_in(key, 1), (b, t, di))
    w = jax.random.normal(jax.random.fold_in(key, 2), (b, t, j))
    return qi, w, ki


def reference_mask(scores, topk):
    """``lax.top_k`` a row of the full ``(B, T, T)`` score matrix."""
    return np.asarray(ref.selection(scores, 0, topk))


@pytest.mark.parametrize("t, topk, block_q, segments", [
    (37, 8, 16, 2), (37, 8, 16, 8), (40, 8, 8, 3), (33, 40, 16, 2),
    (64, 1, 16, 4), (5, 2, 16, 8),
])
def test_selection_is_top_k_of_every_row(t, topk, block_q, segments):
    qi, w, ki = indexer_inputs(t, t=t)

    @jax.jit
    def selected(qi, w, ki):
        by_run = sa.index_scores_by_run(
            qi, w, ki, block_q=block_q, segments=segments)
        return sa.index_scores(qi, w, ki), sa.select(
            by_run, t, topk, block_q=block_q, segments=segments)

    scores, bits = selected(qi, w, ki)
    with jax.default_matmul_precision("highest"):
        assert rel(scores, ref.index_scores(qi, w, ki)) < 1e-6
    assert bits.shape == (2, t, sa.words_of(t)) and bits.dtype == jnp.uint32
    keep = np.asarray(sa.unpack_mask(bits, t))
    want = reference_mask(scores, topk)
    assert np.array_equal(keep, want)
    rows = np.arange(t)
    assert np.array_equal(keep.sum(-1), np.broadcast_to(
        np.minimum(rows + 1, topk), (2, t)))
    assert not np.triu(keep, 1).any()  # causal


@pytest.mark.parametrize("levels", [1, 2, 5])
def test_planted_ties_go_to_the_lower_key(levels):
    """Scores of a few levels (zeros of both signs among them): most rows'
    k-th largest value is shared, and the tie is cut by position, as
    ``lax.top_k`` cuts it."""
    t, topk = 48, 6
    raw = jax.random.randint(jax.random.key(levels), (2, t, t), 0, levels)
    scores = (raw - levels // 2).astype(jnp.float32)
    scores = scores * jnp.where(  # a zero of either sign is one value
        jax.random.bernoulli(jax.random.key(7), 0.5, scores.shape), 1.0, -1.0
    ) if levels == 1 else scores
    canon = jnp.where(scores == 0.0, 0.0, scores)
    keep = np.asarray(jax.jit(
        lambda x: sa.select_block(x, 0, t, topk))(canon))
    want = reference_mask(canon, topk)
    assert np.array_equal(keep, want)
    assert np.array_equal(keep.sum(-1)[0], np.minimum(np.arange(t) + 1, topk))
    if levels == 1:  # all equal: the first topk causal keys
        assert keep[0, 20, :topk].all() and not keep[0, 20, topk:].any()


def test_relu_zeros_tie_at_zero():
    """One index head with a positive weight: half the scores are exactly
    zero, a row with fewer than topk positive scores fills up with zeros by
    position."""
    qi, w, ki = indexer_inputs(5, t=40, j=1)
    w = jnp.abs(w)
    scores = sa.index_scores(qi, w, ki)
    assert float(jnp.mean(scores == 0.0)) > 0.3
    topk = 30
    keep = np.asarray(jax.jit(
        lambda x: sa.select_block(x, 0, 40, topk))(scores))
    assert np.array_equal(keep, reference_mask(scores, topk))


def test_mask_bits_round_trip():
    keep = jax.random.bernoulli(jax.random.key(0), 0.3, (2, 5, 77))
    words = sa.words_of(77)
    assert words == 3
    bits = sa.pack_mask(keep, words)
    assert np.array_equal(sa.unpack_mask(bits, 77), keep)
    # a run's keys are a prefix: pack 40 of them, read 40 back
    assert np.array_equal(
        sa.unpack_mask(sa.pack_mask(keep[..., :40], words), 40), keep[..., :40])
    causal = np.asarray(sa.unpack_mask(sa.causal_mask_bits(2, 9), 9))
    assert np.array_equal(causal[1], np.tril(np.ones((9, 9), bool)))


def test_no_gradient_passes_through_the_selection():
    qi, w, ki = indexer_inputs(9)

    def picked(qi):
        by_run = sa.index_scores_by_run(qi, w, ki, block_q=16, segments=2)
        return jnp.sum(sa.unpack_mask(
            sa.select(by_run, T, 8, block_q=16, segments=2), T).astype(float))

    assert float(picked(qi)) == 2 * sum(min(t + 1, 8) for t in range(T))
    assert not np.asarray(jax.jit(jax.grad(picked))(qi)).any()


# -- attention over a selection, and the alignment loss ------------------------
def attention_inputs(seed, b=2, t=T, hq=4, hkv=2, d=8):
    key = jax.random.key(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, t, hq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, hkv, d))
    return q, k, v


@pytest.mark.parametrize("block_q, segments", [(16, 2), (64, 1)])
def test_masked_attention_and_alignment_match_the_reference(block_q, segments):
    q, k, v = attention_inputs(0)
    qi, w, ki = indexer_inputs(1)
    kw = dict(block_q=block_q, segments=segments)
    with jax.default_matmul_precision("highest"):
        scores = ref.index_scores(qi, w, ki)
        keep = ref.selection(scores, 0, 8)
        bits = sa.pack_mask(keep, sa.words_of(T))
        want, a = ref.attend(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), keep)
        want_kl = jnp.sum(ref.alignment_rows(a, scores, keep))
        qs = sa.scaled_queries(q)
        got, lse = jax.jit(lambda *a: sa.masked_attention(*a, **kw))(
            qs, k, v, bits)
        assert got.shape == (2, T, 4, 8) and lse.shape == (2, T, 4)
        assert rel(got, want) < 2e-5
        # the gradient reaches the indexer's three and nothing else
        kl, grads = jax.jit(jax.value_and_grad(
            lambda *a: sa.alignment_loss(*a, bits, **kw), argnums=range(6)))(
                qi, w, ki, qs, k, lse)
        assert abs(float(kl) - float(want_kl)) < 2e-5 * float(want_kl)
        want_grads = jax.grad(lambda qi, w, ki: jnp.sum(ref.alignment_rows(
            a, ref.index_scores(qi, w, ki), keep)), argnums=(0, 1, 2))(
                qi, w, ki)
    for g, wg in zip(grads[:3], want_grads):
        assert rel(g, wg) < 5e-4
    assert all(not np.asarray(g).any() for g in grads[3:])
    # the selected keys hold all of the masked attention's probability, and
    # a share of the dense attention's
    mass = float(jax.jit(lambda *a: sa.selection_mass(*a, **kw))(qs, k, bits))
    dense = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((T, T), bool)), jnp.einsum(
            "bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) * 8 ** -0.5, -jnp.inf), -1)
    want_mass = float(jnp.mean(jnp.sum(
        jnp.where(keep[:, None], dense, 0.0), -1)))
    assert mass == pytest.approx(want_mass, rel=1e-4) and 0.2 < mass < 1.0
    whole = sa.causal_mask_bits(2, T)
    assert float(sa.selection_mass(qs, k, whole, **kw)) == pytest.approx(1.0)


def test_masked_attention_with_every_causal_key_is_causal_attention():
    from sparknet_tpu.ops.attention import causal_gqa_attention

    q, k, v = attention_inputs(3)
    got, _ = jax.jit(lambda *a: sa.masked_attention(
        *a, block_q=8, segments=8))(
            sa.scaled_queries(q), k, v, sa.causal_mask_bits(2, T))
    assert rel(got, causal_gqa_attention(q, k, v)) < 2e-5


def path_events(fn, *args, name="sparse_attention_path"):
    """The instant ``name``'s arguments at each trace of ``fn`` (traced
    anew: the instant is written while tracing)."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    tracer = obs.install_tracer(Tracer())
    try:
        jax.eval_shape(lambda *a: fn(*a), *args)
    finally:
        obs.uninstall_tracer()
    return [e["args"] for e in tracer.events() if e["name"] == name]


def test_the_attention_path_is_named_at_each_trace():
    q, k, v = attention_inputs(4)
    (event,) = path_events(lambda *a: sa.masked_attention(
        sa.scaled_queries(a[0], jnp.bfloat16), *a[1:],
        sa.causal_mask_bits(2, T)), q, k, v)
    assert event["path"] == "xla" and event["t"] == T
    assert event["why"] == "no Pallas lowering on cpu"
    assert event["dtype"] == "bfloat16" and event["words"] == sa.words_of(T)
    # one run of one block of T queries against T keys
    assert (event["block_q"], event["block_k"]) == (T, T)
    assert (event["blocks_computed"], event["blocks_total"]) == (1, 1)


# -- keep-masks as bits, for the alignment loss and the flash kernels -----------
KT = 64  # two words a row; key blocks of 16 and 32 are 8 and 16 bits of each


def kernel_masks(kind, b=2, t=KT):
    """Keep-masks as bits.  ``select``: the indexer's own; ``late``: most
    rows keep a few keys just before their own position (nothing in their
    first key blocks, so the running maximum stays at the mask's value past
    the first blocks met) and every third row keeps only itself;
    ``causal``: every causal key; ``early``: the rows of the second half
    keep only the first four keys (a run's later keys hold nothing of them),
    the others every causal key."""
    if kind == "causal":
        return sa.causal_mask_bits(b, t)
    if kind == "select":
        qi, w, ki = indexer_inputs(11, b=b, t=t)
        return sa.select(sa.index_scores_by_run(
            qi, w, ki, block_q=16, segments=2), t, 8, block_q=16, segments=2)
    row, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    if kind == "early":
        keep = (key <= row) & ((row < t // 2) | (key < 4))
    else:
        near = (key <= row) & (key >= row - 4) & (key % 2 == row % 2)
        keep = jnp.where((row % 3 == 0), key == row, near)
    return jnp.broadcast_to(sa.pack_mask(keep, sa.words_of(t)),
                            (b, t, sa.words_of(t)))


# -- the alignment loss hands back its gradient with its value ------------------
def alignment_inputs(t, dtype, bits, j=4, di=8, **kw):
    """``alignment_loss``'s six arrays before the mask, in ``dtype``; ``w``
    at the size the model's scale factors leave it."""
    cd = jnp.dtype(dtype)
    q, k, v = attention_inputs(7, t=t)
    qi, w, ki = indexer_inputs(8, t=t, j=j, di=di)
    q, k = sa.scaled_queries(q, cd), k.astype(cd)
    _, lse = sa.masked_attention(q, k, v, bits, **kw)
    return qi.astype(cd), w * 32 ** -0.5, ki.astype(cd), q, k, lse


@pytest.mark.parametrize("kind", ["select", "causal", "early"])
@pytest.mark.parametrize("t", [37, 32])  # rows of padding in the last block, none
@pytest.mark.parametrize("block_q, segments", [(16, 2), (8, 3)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_alignment_gradient_from_the_one_pass_matches_autodiff(
        dtype, block_q, segments, t, kind):
    """``alignment_loss``'s closed-form gradient to ``qi``, ``w``, ``ki``
    under a cotangent that is not 1, against ``jax.grad`` of the plain
    function; the value under differentiation is the plain function's; no
    gradient reaches ``q``, ``k`` or ``lse``.  Float32 differs by summation
    order; in bfloat16 ``g`` is rounded where it meets the products (what the
    MXU does to a float32 cotangent; the CPU's autodiff keeps it float32) and
    two of the three gradients are bfloat16 themselves: eps is 3.9e-3."""
    kw = dict(block_q=block_q, segments=segments)
    bits = kernel_masks(kind, t=t)
    args = alignment_inputs(t, dtype, bits, **kw)
    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(jax.value_and_grad(
            lambda *a: 0.3 * fn(*a, bits, **kw), argnums=range(6)))(*args)
            for fn in (sa.alignment_loss, sa.alignment_value))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    assert float(got[0]) > 0.0
    for g, wg in zip(got[1][:3], want[1][:3]):
        assert g.dtype == wg.dtype and g.shape == wg.shape
        assert np.asarray(wg, np.float32).any()
        assert rel(g, wg) < (5e-6 if dtype == "float32" else 1e-2)
    assert all(not np.asarray(g, np.float32).any() for g in got[1][3:])


def test_alignment_loss_keeps_its_three_gradients_and_nothing_else():
    """Not differentiated, ``alignment_loss`` is the plain function (its
    own jaxpr, called); differentiated, what its forward keeps for the
    backward are the gradients to ``qi``, ``w`` and ``ki`` (no leaf of
    ``q``'s, ``k``'s, the log-sum-exp's or the mask's shape), and its
    backward scales them: no product."""
    kw = dict(block_q=16, segments=2)
    bits = kernel_masks("select", t=T)
    # (3 index heads of 6: no two of the seven arrays share a shape)
    args = alignment_inputs(T, "float32", bits, j=3, di=6, **kw)
    plain = jax.make_jaxpr(lambda *a: sa.alignment_value(*a, **kw))(*args, bits)
    ours = jax.make_jaxpr(lambda *a: sa.alignment_loss(*a, **kw))(*args, bits)
    *constants, call = ours.jaxpr.eqns
    assert {e.primitive.name for e in constants} == {"stop_gradient"}
    assert call.primitive.name == "custom_vjp_call"
    assert str(call.params["call_jaxpr"]) == str(plain)
    value, vjp = jax.vjp(
        lambda *a: sa.alignment_loss(*a, bits, **kw), *args)
    kept = sorted(x.shape for x in jax.tree_util.tree_leaves(vjp)
                  if hasattr(x, "shape") and x.ndim)
    assert kept == sorted(x.shape for x in args[:3])
    assert not {x.shape for x in (*args[3:], bits)} & set(kept)
    backward = str(jax.make_jaxpr(vjp)(jnp.float32(0.3)))
    assert "dot_general" not in backward and "mul" in backward
    assert float(value) == pytest.approx(
        float(sa.alignment_value(*args, bits, **kw)), rel=1e-6)


def test_the_alignment_path_is_named_at_each_trace():
    """``alignment_loss_path``: ``value`` where nothing differentiates it,
    ``with_gradient`` (and the dtype ``dS`` meets the products in) where
    ``jax.grad`` does, the blocks ``by_run`` computes of the square."""
    kw = dict(block_q=8, segments=2)
    bits = kernel_masks("causal", t=T)
    args = alignment_inputs(T, "bfloat16", bits, **kw)
    loss = lambda *a: sa.alignment_loss(*a, bits, **kw)  # noqa: E731
    (value,) = path_events(loss, *args, name="alignment_loss_path")
    (grad,) = path_events(jax.grad(loss, argnums=(0, 1, 2)), *args,
                          name="alignment_loss_path")
    assert (value["path"], value["ds_dtype"]) == ("value", "")
    assert (grad["path"], grad["ds_dtype"]) == ("with_gradient", "bfloat16")
    for event in (value, grad):
        # five blocks of 8 rows in runs of 3 and 2: 3 x 3 + 2 x 5 of 25
        assert (event["t"], event["block_q"], event["segments"]) == (T, 8, 2)
        assert (event["blocks_computed"], event["blocks_total"]) == (19, 25)
    # the cell's: 32 blocks of 512 in 8 runs
    assert sa._blocks_met(16384, 512, 8) == (576, 1024)


# -- the flash kernels under a keep-mask (interpreter mode) --------------------
@pytest.mark.parametrize("kind", ["select", "late", "causal"])
@pytest.mark.parametrize("block_q, block_k, d", [(16, 16, 8), (32, 64, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [1, 8])
def test_flash_kernels_under_a_keep_mask_match_the_xla_pass(
        group, dtype, block_q, block_k, d, kind):
    """``(o, lse)`` and the gradients w.r.t. ``q``, ``k``, ``v`` under a
    cotangent on BOTH outputs, against ``_blockwise_gqa`` given the same
    bits; a head of 8 goes heads-first (a sequence's K/V heads share its
    mask), a head of 128 is read in place."""
    from sparknet_tpu.ops import pallas_attention
    from sparknet_tpu.ops.attention import _blockwise_gqa

    hkv = 2 if d == 8 else 1
    cd = jnp.dtype(dtype)
    q, k, v = attention_inputs(5, t=KT, hq=hkv * group, hkv=hkv, d=d)
    q, k, v = sa.scaled_queries(q, cd), k.astype(cd), v.astype(cd)
    bits = kernel_masks(kind)
    key = jax.random.key(6)
    do = jax.random.normal(jax.random.fold_in(key, 0), q.shape)
    dlse = jax.random.normal(jax.random.fold_in(key, 1), q.shape[:3])

    def kernels(q, k, v):
        o, lse = pallas_attention.masked_flash_attention(
            q, k, v, bits, block_q=block_q, block_k=block_k, interpret=True,
            scale=1.0, out_dtype=jnp.float32)
        return o, jnp.transpose(lse, (0, 2, 1))

    def xla(q, k, v):
        return _blockwise_gqa(q, k, v, 16, 2, keep=bits)

    def both(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * do) + jnp.sum(lse * dlse), (o, lse)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))

    with jax.default_matmul_precision("highest"):
        got_grads, got = both(kernels)(q, k, v)
        want_grads, want = both(xla)(q, k, v)
        if kind == "causal":  # the bits say what the positions say
            dense = pallas_attention.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=True, scale=1.0, out_dtype=jnp.float32)
            assert rel(got[0], dense) < 1e-6
    assert got[0].dtype == got[1].dtype == jnp.float32
    bound = 2e-5 if cd == jnp.float32 else 2e-2
    assert rel(got[0], want[0]) < bound and rel(got[1], want[1]) < bound
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == cd and rel(g, w) < bound


@pytest.mark.parametrize("change", [
    dict(block_k=24),  # whole words, but 64 keys are not whole blocks of 24
    dict(block_q=48),
    dict(block_k=17),  # not whole rows of two words
])
def test_a_keep_mask_wants_whole_blocks_of_whole_words(change):
    from sparknet_tpu.ops import pallas_attention

    q, k, v = attention_inputs(5, t=KT)
    kw = {**dict(block_q=16, block_k=16, interpret=True), **change}
    with pytest.raises(ValueError, match="whole blocks of whole words"):
        pallas_attention.masked_flash_attention(
            q, k, v, kernel_masks("causal"), **kw)


@pytest.mark.parametrize("t, hq, hkv, d, dtype, path, why", [
    (4096, 32, 4, 128, "bfloat16", "pallas", ""),
    (16384, 32, 4, 128, "bfloat16", "pallas", ""),
    (4096, 32, 4, 128, "float32", "xla", "six MXU passes"),
    (40, 32, 4, 128, "bfloat16", "xla", "T % 4096 == 0"),
    (4090, 32, 4, 128, "bfloat16", "xla", "T % 4096 == 0"),
    (4096, 32, 4, 128, "float16", "xla", "bfloat16 or float32"),
    (4096, 6, 2, 40, "bfloat16", "xla", "heads of whole lanes"),
])
def test_the_kernels_are_taken_where_the_shapes_allow(
        monkeypatch, t, hq, hkv, d, dtype, path, why):
    """The dispatch, by what the code observes (shapes alone are traced:
    nothing runs): the kernels where Pallas lowers, the heads are accepted
    and a row of the mask is whole lanes of words; the XLA pass elsewhere,
    with the reason named."""
    from sparknet_tpu.ops import attention, pallas_attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    cd = jnp.dtype(dtype)
    shape = lambda *s: jax.ShapeDtypeStruct(s, cd)  # noqa: E731
    bits = jax.ShapeDtypeStruct((1, t, sa.words_of(t)), jnp.uint32)
    (event,) = path_events(
        sa.masked_attention, shape(1, t, hq, d), shape(1, t, hkv, d),
        shape(1, t, hkv, d), bits)
    assert event["path"] == path and why in event["why"]
    assert bool(event["why"]) == (path == "xla")
    assert event["backward"] == ("fused" if path == "pallas" else "xla")
    if path == "pallas":  # the cell's blocks: 512 x 1,024
        assert (event["block_q"], event["block_k"]) == (512, 1024)
        assert event["words"] == t // 32 and 1024 % event["words"] == 0
        assert (event["blocks_computed"], event["blocks_total"]
                ) == pallas_attention.blocks_met(t, t, 512, 1024)


@pytest.mark.parametrize("t, blocks", [(16384, (272, 512)), (4096, (20, 32))])
def test_blocks_the_kernels_meet_at_the_cells_lengths(monkeypatch, t, blocks):
    """17/32 of the score matrix at T = 16,384 in blocks of 512 x 1,024,
    where the XLA pass's eight runs meet 9/16."""
    from sparknet_tpu.ops import attention

    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, t, h, 128), jnp.bfloat16)
    bits = jax.ShapeDtypeStruct((1, t, sa.words_of(t)), jnp.uint32)
    args = (shape(32), shape(4), shape(4), bits)
    (xla,) = path_events(sa.masked_attention, *args)
    assert (xla["blocks_computed"], xla["blocks_total"]) == (
        9 * (t // 512) ** 2 // 16, (t // 512) ** 2)
    monkeypatch.setattr(attention, "lowerable", lambda: True)
    (event,) = path_events(sa.masked_attention, *args)
    assert (event["blocks_computed"], event["blocks_total"]) == blocks


def test_masked_attention_through_the_kernels_keeps_its_interface(monkeypatch):
    """``masked_attention`` on the kernels' path (interpreter mode) at the
    smallest length that takes it: the output and the log-sum-exp in the XLA
    pass's shapes and values, named for ``MIXER_KEEPS``, and a recomputation
    under that policy runs the forward kernel once."""
    from sparknet_tpu.models.hybrid_lm import MIXER_KEEPS
    from sparknet_tpu.ops import attention

    t = 4096
    q, k, v = attention_inputs(8, b=1, t=t, hq=2, hkv=1, d=128)
    q = sa.scaled_queries(q, jnp.bfloat16)
    row, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    bits = sa.pack_mask(((key <= row) & ((row - key) % 37 == 0))[None],
                        sa.words_of(t))
    want = jax.jit(sa.masked_attention)(q, k, v, bits)
    monkeypatch.setattr(attention, "lowerable", lambda: True)
    (event,) = path_events(sa.masked_attention, q, k, v, bits)
    assert event["path"] == "pallas"
    got = jax.jit(sa.masked_attention)(q, k, v, bits)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype and rel(g, w) < 1e-2

    def loss(q, k, v):
        o, lse = jax.checkpoint(
            lambda *a: sa.masked_attention(*a, bits), policy=MIXER_KEEPS)(
                q, k, v)
        return jnp.sum(o) + jnp.sum(lse)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert text.count("name=flash_attention_forward") == 1
    assert text.count("name=flash_attention_backward") == 1
    assert text.count("name=flash_attention_dq") == 0


# -- one chip's share --------------------------------------------------------
def test_all_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the outputs of the shares [0, n), [n, 2n), ...
    of one routed layer sum to the uncut reference's output of the whole
    layer.  There is no shared expert: nothing is counted once."""
    experts, n = SMALL["num_experts"], 2  # eight shares of two experts
    whole = HybridMoELM({**SMALL, "experts_held": [0, experts]})
    full = seeded(whole, seed=5)
    x = jax.random.normal(jax.random.key(11), (2 * T, SMALL["hidden_size"]))
    router, blobs = full["l1_router"], full["l1_experts"]
    ones = jnp.ones(x.shape[-1])
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm(x, ones, SMALL["rms_norm_eps"])
        want = ref.moe(normed, router, blobs, SMALL, held=(0, experts))
        weights, ids = ref.route(normed, router[0], SMALL)
        total = jnp.zeros_like(want)
        for lo in range(0, experts, n):
            share = HybridMoELM({**SMALL, "experts_held": [lo, n]})
            part = [b[lo:lo + n] for b in blobs]
            routed = share._route(x, ones, *router)
            got = share._held_experts(normed, *routed, part)
            total = total + got
            # every share routes over all the experts
            assert moe.load(routed[1], experts).sum() == 2 * T * 4
            assert rel(got, ref.routed_experts(
                normed, weights, ids, part, (lo, n))) < 2e-5
    assert experts // n == 8
    assert rel(total, want) < 2e-5


def test_readings_of_the_selection(model, params):
    data = batch(3)
    readings = jax.jit(model.selection_readings)(params, data["tokens"])
    _, (aux, _) = jax.jit(model.loss_fn)(params, {}, data)
    np.testing.assert_allclose(
        readings["indexer_loss"], aux["indexer_loss_by_layer"], rtol=1e-5)
    mass = np.asarray(readings["selection_mass"])
    assert mass.shape == (2,) and np.all((0.2 < mass) & (mass < 1.0))
    # a model without such a layer reads nothing
    from tests.test_lfm2_moe import SMALL as LFM2

    other = HybridMoELM(LFM2)
    empty = other.selection_readings(other.init(0)[0], data["tokens"])
    assert empty["indexer_loss"].shape == empty["selection_mass"].shape == (0,)


# -- through the solver, the trainer and the app -----------------------------
def test_one_adam_step_moves_every_leaf_and_the_indexer_by_its_own_loss():
    """One ADAM step on each of two workers, then the average: every
    parameter moves and has both moments, the indexer's among them."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh

    lm, solver = lm_app.build_hybrid_lm_solver(SMALL)
    assert solver.method == "ADAM"
    trainer = ParameterAveragingTrainer(
        solver, make_mesh({"dp": 2}, devices=jax.devices()[:2]))
    batches = [batch(10 * w) for w in range(2)]
    stacked = {k: np.stack([np.asarray(b[k])[None] for b in batches])
               for k in ("tokens", "targets")}
    first = trainer.init_state(seed=4)
    initial = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], first.params)
    state, losses = trainer.round(first, stacked, round_index=0)
    assert losses.shape == (2, 1) and np.all(np.isfinite(losses))
    assert state.stats == {}
    for group, blobs in state.params.items():
        assert len(blobs) == len(dict(lm._group_blobs)[group])
        for i, blob in enumerate(blobs):
            assert not np.array_equal(np.asarray(blob)[0], initial[group][i]), (
                group, i)
            for moment in state.history:
                h = np.asarray(moment[group][i])
                assert h.shape == blob.shape and h.any(), (group, i)
    # the loss the solver reports is the sum of the two
    with jax.default_matmul_precision("highest"):
        want = [float(ref.loss(initial, b["tokens"], b["targets"], SMALL))
                for b in batches]
    np.testing.assert_allclose(np.asarray(losses)[:, 0], want, rtol=1e-4)


def test_checkpoint_round_trip_carries_the_indexer(tmp_path):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import caffemodel, checkpoint

    lm, solver = lm_app.build_hybrid_lm_solver(SMALL)
    data = batch(6)
    state, _ = solver.step(solver.init_state(seed=1), {
        k: np.asarray(v)[None] for k, v in data.items()})
    blobs = caffemodel.net_blobs(lm, state.params, state.stats)
    assert [b.shape for b in blobs["l1_mixer"]][6:] == [
        (32, 32), (32, 8), (8,), (8,), (32, 4)]
    prefix = str(tmp_path / "keye_ck")
    checkpoint.snapshot(solver, state, prefix, fmt="BINARYPROTO")
    restored, _ = checkpoint.restore_newest_valid(solver, prefix)
    got, want = jax.device_get(restored), jax.device_get(state)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_round_compiled_ahead_is_not_compiled_again():
    """``ParameterAveragingTrainer.compile_round`` on a thread of its own,
    for batches like the round's: ``round`` then compiles nothing, and the
    state it was compiled for is still whole (nothing ran, nothing was
    donated)."""
    import threading

    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    _, solver = lm_app.build_hybrid_lm_solver(SMALL)
    trainer = ParameterAveragingTrainer(
        solver, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    state = trainer.init_state(seed=4)
    stacked = lambda seed: {  # noqa: E731
        k: jnp.asarray(np.asarray(v)[None, None])
        for k, v in batch(seed).items()}
    like, real = stacked(1), stacked(2)
    thread = threading.Thread(target=trainer.compile_round, args=(state, like))
    thread.start()
    thread.join()
    before = len(compiles)
    assert before > 0
    assert np.isfinite(np.asarray(state.params["embed"][0])).all()
    state, losses = trainer.round(state, real, round_index=0)
    assert np.all(np.isfinite(losses)) and len(compiles) == before


def test_the_selection_probe_is_jitted_once_a_model(model, params):
    """``lm_app.selection_probe``: one jitted forward pass a model, the
    tokens an argument, so that a second batch compiles nothing; its
    readings carry the pass's ``routing_counts``."""
    from sparknet_tpu.apps import lm_app

    assert lm_app.selection_probe(model) is lm_app.selection_probe(model)
    stacked = jax.tree_util.tree_map(lambda x: x[None], params)
    gauges = [lm_app.set_selection_gauges(
        model, stacked, batch(seed)["tokens"]) for seed in (1, 2)]
    assert lm_app.selection_probe(model)._cache_size() == 1
    counts = jax.jit(model.routing_counts)(params, batch(2)["tokens"])
    assert gauges[1]["held_counts"] == np.asarray(counts).tolist()
    assert gauges[0]["indexer_loss"] != gauges[1]["indexer_loss"]
    assert set(gauges[0]) == {"indexer_loss", "selection_mass", "held_counts"}


def test_lm_app_trains_keye_from_a_configuration_file(tmp_path):
    """``lm_app --model_config``: the byte corpus through ``Solver(net=...)``
    with ADAM and ``ParameterAveragingTrainer.round`` on two workers; the
    indexer's gauges are set by layer."""
    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app

    config = {**SMALL, "vocab_size": 256, "compute_dtype": "bfloat16"}
    path = tmp_path / "tiny-keye.json"
    path.write_text(json.dumps(config))
    rc = lm_app.main([
        "--model_config", str(path), "--workers", "2", "--rounds", "3",
        "--tau", "2", "--batch", "2", "--seq_len", "24", "--log_every", "1",
        "--obs", "--obs_port", "0",
    ])
    assert rc == 0
    tm = obs.training_metrics()
    assert tm is not None and tm.lm_tokens.value == 3 * 2 * 2 * 2 * 24
    for i in range(2):
        assert tm.lm_indexer_loss.labels(str(i)).value > 0.0
        assert 0.2 < tm.lm_selection_mass.labels(str(i)).value <= 1.0
        assert 0.5 < tm.lm_held_assignments.labels(str(i)).value < 3.5
    assert tm.kernel_path.labels("sparse_attention").value == 0.0
    assert tm.kernel_path.labels("alignment_loss").value == 1.0


def test_the_benchmarks_configuration_builds_the_published_model():
    """``benchmark/configs/keye-vl-2.0-30b-a3b.json`` as the app reads it."""
    import os

    from sparknet_tpu.models.hybrid_lm import load_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_config(os.path.join(
        root, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json"))
    lm = HybridMoELM(config)
    c = lm.config
    assert c["mixers"] == ("dsa_attention",) * 4 and c["ffns"] == ("moe",) * 4
    assert (c["head_dim"], c["hidden_size"], c["index_topk"],
            c["index_block"]) == (128, 2048, 2048, 512)
    assert lm.experts_held == (0, 16) and not c["tied"]
    assert lm.num_params() == config["held_here"]["parameters"] == 465_391_104
    assert config["held_here"]["bytes_at_16_a_parameter"] == 16 * 465_391_104
    assert describe(config)["rope_theta"] == 10000000
