"""The ``deepseek_v3`` family as kanana-2-30b-a3b publishes it, through
``models/hybrid_lm.HybridMoELM`` (multi-head latent attention without a query
rank, one rotary key for all heads, a leading dense layer, a sigmoid router
with a selection bias and a scaling factor beside shared experts without an
output gate, an untied head) against the plain reference
``benchmark/reference/kanana2.py``, at a small size on the CPU with every
published ratio kept: scores 3:2 values (12 against 8), ONE rope head, 1
dense + 2 routed layers, top-3 < 4 held < 8 experts, 2 shared experts.

Float32 comparisons run under ``default_matmul_precision("highest")``; what
is left is summation order (grouped against expert-by-expert, blockwise
against a full row, the rope key joined against kept apart), so the bounds
are a few float32 roundings: 2e-5 relative, 5e-4 on gradients.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kanana2 as ref
from sparknet_tpu.models.hybrid_lm import (
    HybridMoELM, describe, load_config, rotary, rotary_pairs)
from sparknet_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the catalog row's keys (model-configs guide), at the published values
PUBLISHED_KEYS = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256,
}
SMALL = {
    **PUBLISHED_KEYS, "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
    "v_head_dim": 8, "intermediate_size": 48, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 12,
    # this system's own keys
    "experts_held": [2, 4], "expert_bias_update_rate": 0.01,
}
# the published widths at the benchmark's cut (configs/kanana-2-30b-a3b.json)
CUT = {**PUBLISHED_KEYS, "num_hidden_layers": 5, "vocab_size": 16032,
       "experts_held": [0, 16]}
T = 37  # odd, and not a multiple of anything


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def seeded(model, seed=0):
    """Seeded weights as the LFM2 tests make them: norm weights off their
    initial values, matrices widened (the residual-writing ones, which start
    narrower here, to the same 0.1), the embedding narrowed to it, so that
    a test cannot pass by ignoring a term."""
    params, _ = model.init(seed)
    key = jax.random.key(seed + 100)
    for gi, (group, blobs) in enumerate(sorted(params.items())):
        for bi, blob in enumerate(blobs):
            k = jax.random.fold_in(jax.random.fold_in(key, gi), bi)
            if blob.ndim == 1:
                blobs[bi] = blob + 0.1 * jax.random.normal(k, blob.shape)
            else:
                blobs[bi] = 0.1 * jax.random.normal(k, blob.shape)
    return params


def seeded_stats(model, seed=0, std=0.1):
    _, stats = jax.eval_shape(model.init)
    key = jax.random.key(seed + 200)
    return {g: [std * jax.random.normal(jax.random.fold_in(key, i), b.shape)
                for i, b in enumerate(blobs)]
            for g, blobs in sorted(stats.items())}


def batch(seed, b=2, t=T, vocab=SMALL["vocab_size"]):
    ids = jax.random.randint(jax.random.key(seed), (b, t + 1), 0, vocab)
    return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(SMALL)


@pytest.fixture(scope="module")
def params(model):
    return seeded(model)


@pytest.fixture(scope="module")
def stats(model):
    return seeded_stats(model)


# -- what is built ---------------------------------------------------------
def test_layer_pattern_untied_head_and_groups(model):
    c = model.config
    assert c["mixers"] == ("mla_attention",) * 3
    assert c["ffns"] == ("dense", "moe", "moe")
    assert all(model.is_attention_layer(i) for i in range(3))
    assert model.routed_layers == (1, 2)
    groups = [g for g, _ in model._group_blobs]
    assert groups[0] == "embed" and groups[-2:] == ["norm_f", "head"]
    sizes = dict(model._group_blobs)
    # Wq (E, H (8 + 4)), Wkva (E, 16 + 4), the latent's norm, Wkvb (16, H (8
    # + 8)), Wo (H 8, E); no norm on the heads
    assert sizes["l0_mixer"] == [(32, 48), (32, 20), (16,), (16, 64), (32, 32)]
    assert sizes["l0_mlp"] == [(32, 48), (32, 48), (48, 32)]
    assert sizes["l1_router"] == [(32, 8)]  # the selection bias is no blob
    assert sizes["l1_experts"] == [(4, 32, 12), (4, 32, 12), (4, 12, 32)]
    # the two shared experts are ONE gated MLP, and there is no output gate
    assert sizes["l1_shared"] == [(32, 24), (32, 24), (24, 32)]
    assert c["shared_expert_gate"] is False
    assert "l0_router" not in sizes and "l1_mlp" not in sizes
    assert [(r.collection, r.index) for r in model._blob_refs["l1_router"]] == [
        ("params", 0), ("stats", 0), ("stats", 1)]
    assert model.biased_routers == ("l1_router", "l2_router")
    assert (c["router_scores"], c["expert_bias"], c["routed_scaling_factor"],
            c["topk_eps"]) == ("sigmoid", True, 2.448, 1e-20)
    assert c["init_std"] == {"embed": 1.0, "out": 0.02 * 6 ** -0.5}


@pytest.mark.parametrize("group, count", [
    ("l0_mixer", 26_345_984),  # Wq 12,582,912 + Wkva 1,179,648 + 512 +
    # Wkvb 4,194,304 + Wo 8,388,608
    ("l0_mlp", 37_748_736),  # the leading dense layer, 6,144 wide
    ("l1_router", 262_144),  # 128 wide; its selection bias is no parameter
    ("l1_experts", 75_497_472),  # 16 held experts of 768
    ("l1_shared", 9_437_184),  # one gated MLP of 2 x 768, no gate blob
    ("embed", 32_833_536), ("head", 32_833_536),  # 16,032 rows each
])
def test_parameter_count_at_the_published_widths(group, count):
    sizes = dict(HybridMoELM(CUT)._group_blobs)
    assert sum(int(np.prod(s)) for s in sizes[group]) == count


def test_parameter_count_is_a_walk_of_the_shapes():
    cut = HybridMoELM(CUT)
    e = 2048
    mixer, routed = 26_345_984, 262_144 + 75_497_472 + 9_437_184
    assert cut.num_params() == 575_955_456 == (
        5 * (mixer + 2 * e) + 37_748_736 + 4 * routed + 2 * 32_833_536 + e)
    shapes, stats = jax.eval_shape(cut.init)
    walked = sum(int(np.prod(leaf.shape))
                 for leaf in jax.tree_util.tree_leaves(shapes))
    assert walked == cut.num_params()
    assert {g: [b.shape for b in blobs] for g, blobs in stats.items()} == {
        f"l{i}_router": [(128,), (128,)] for i in (1, 2, 3, 4)}
    # uncut: a routed layer 640M, the model the "30B" of its name
    whole_routed = 128 * 3 * e * 768 + 262_144 + 9_437_184 + mixer + 2 * e
    assert round(whole_routed / 1e6) == 640
    whole = (47 * whole_routed + mixer + 2 * e + 37_748_736
             + 2 * 128_256 * e + e)
    assert round(whole / 1e9, 1) == 30.7


@pytest.mark.parametrize("change, key", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"attention_bias": True}, "attention_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"qk_head_dim": 16}, "qk_head_dim"),
    ({"v_head_dim": 4}, "v_head_dim"),
])
def test_every_key_it_cannot_build_is_refused_by_name(change, key):
    with pytest.raises(ValueError, match=key):
        HybridMoELM({**SMALL, **change})


def test_a_missing_key_is_named():
    config = {k: v for k, v in SMALL.items() if k != "kv_lora_rank"}
    with pytest.raises(ValueError, match="kv_lora_rank"):
        HybridMoELM(config)


def test_keys_the_file_may_leave_out_take_the_published_values():
    only = ("q_lora_rank", "rope_scaling", "n_group", "topk_group",
            "moe_layer_freq", "attention_bias", "norm_topk_prob",
            "scoring_func", "topk_method", "rope_interleave", "qk_head_dim",
            "tie_word_embeddings", "expert_bias_update_rate")
    c = describe({k: v for k, v in SMALL.items() if k not in only})
    full = describe(SMALL)
    assert c["expert_bias_update_rate"] == 0.0 and not c["tied"]
    assert {k: v for k, v in c.items() if k != "expert_bias_update_rate"} == {
        k: v for k, v in full.items() if k != "expert_bias_update_rate"}


def test_initialisation(model):
    params, stats = model.init(3)
    assert float(jnp.min(params["l0_n1"][0])) == 1.0
    assert float(jnp.min(params["l0_mixer"][2])) == 1.0  # the latent's norm
    std = lambda a: float(jnp.std(a))  # noqa: E731
    assert 0.9 < std(params["embed"][0]) < 1.1
    assert 0.015 < std(params["l0_mixer"][0]) < 0.025  # Wq
    assert 0.006 < std(params["l0_mixer"][4]) < 0.010  # Wo: 0.02 / sqrt(6)
    assert 0.006 < std(params["l1_shared"][2]) < 0.010
    assert all(float(jnp.max(jnp.abs(b))) == 0.0
               for blobs in stats.values() for b in blobs)


# -- rotary over adjacent pairs ----------------------------------------------
def test_rotary_turns_adjacent_pairs_where_they_lie():
    """Against a pair rotation written by hand in float64; rotate-half, the
    other families', is another function of the same input."""
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 8))
    theta = 1e4
    got = np.asarray(rotary_pairs(x, theta), np.float64)
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for t in range(9):
        for i in range(4):
            a = t * theta ** (-2 * i / 8)
            even, odd = xs[:, t, :, 2 * i], xs[:, t, :, 2 * i + 1]
            want[:, t, :, 2 * i] = even * np.cos(a) - odd * np.sin(a)
            want[:, t, :, 2 * i + 1] = odd * np.cos(a) + even * np.sin(a)
    assert rel(got, want) < 1e-6
    assert rel(ref.rotate_pairs(x, theta), want) < 1e-6
    assert rel(ref.rotate_pairs(x[:, :, 0], theta), want[:, :, 0]) < 1e-6
    assert rel(rotary(x, theta, 8), want) > 0.3  # rotate-half is not this
    # the first token does not turn, and a score depends on the distance only
    assert np.array_equal(got[:, 0], xs[:, 0])
    q, k = got[0, 5, 0], got[0, 3, 0]
    shifted = np.asarray(rotary_pairs(
        jnp.concatenate([jnp.zeros_like(x[:, :2]), x], 1), theta), np.float64)
    assert abs(q @ k - shifted[0, 7, 0] @ shifted[0, 5, 0]) < 1e-5


# -- against the reference -------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference(
        model, params, stats):
    """With selection biases that change most selections, and without
    ``stats`` (every bias zero), in program and reference alike."""
    data = batch(1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"], stats)
        want = jax.jit(lambda p, t, s: ref.logits(p, t, SMALL, stats=s))(
            params, data["tokens"], stats)
        assert got.shape == (2, T, SMALL["vocab_size"])
        assert rel(got, want) < 2e-5
        unbiased = jax.jit(model.forward_logits)(params, data["tokens"])
        assert rel(unbiased, want) > 1e-2  # the biases are in play
        assert rel(unbiased, jax.jit(lambda p, t: ref.logits(p, t, SMALL))(
            params, data["tokens"])) < 2e-5
        (loss, (_, after)), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, stats, data)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, b["tokens"], b["targets"], SMALL,
                                  stats=stats)))(params, data)
        want_after = jax.jit(lambda p, t, s: ref.balanced_stats(
            p, t, SMALL, s))(params, data["tokens"], stats)
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
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
    assert set(after) == set(want_after) == set(stats)
    for group in stats:
        bias, load = (np.asarray(a) for a in after[group])
        want_bias, want_load = (np.asarray(a) for a in want_after[group])
        assert np.array_equal(load, want_load)
        assert load.sum() == 2 * T * 3 and load.min() >= 0
        np.testing.assert_allclose(bias, want_bias, rtol=0, atol=1e-7)


# each of the benchmark cell's planted faults (``kanana_checks.PLANTS``), in
# the reference: the program, which has none of them, must disagree
@pytest.mark.parametrize("plant", [
    "scale_128", "rotary_on_nope", "rotate_half_keys", "rope_key_per_head",
    "no_latent_norm", "no_routed_scaling", "gated_shared_expert"])
def test_a_planted_fault_in_the_reference_reads_far_from_the_program(
        model, params, plant):
    from benchmark import kanana_checks

    data = batch(2)
    faulty = kanana_checks.planted_reference({plant})
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"])
        want = jax.jit(lambda p, t: faulty.logits(p, t, SMALL))(
            params, data["tokens"])
        clean = jax.jit(lambda p, t: ref.logits(p, t, SMALL))(
            params, data["tokens"])
    assert rel(got, clean) < 2e-5 and rel(got, want) > 2e-3, plant
    assert kanana_checks.planted_reference(set()) is ref  # none: the module


def test_bf16_compute_is_near_float32_and_not_float32(model):
    data = batch(2)
    params, _ = model.init(0)
    low = HybridMoELM({**SMALL, "compute_dtype": "bfloat16"})
    exact = jax.jit(model.forward_logits)(params, data["tokens"])
    got = jax.jit(low.forward_logits)(params, data["tokens"])
    assert got.dtype == jnp.float32
    assert 1e-4 < rel(got, exact) < 3e-2


# -- the router ----------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_the_reference_scaled_and_renormalised(seed):
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 0), (512, 32))
    w = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32, 8))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (8,))
    with jax.default_matmul_precision("highest"):
        weights, ids = moe.route(x, w, 3, scores="sigmoid", bias=bias,
                                 scale=2.448, eps=1e-20)
        want_w, want_ids, scores = ref.route(x, w, SMALL, bias)
    assert np.array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), np.argsort(ids, -1), -1),
        np.take_along_axis(np.asarray(want_w), np.argsort(want_ids, -1), -1),
        rtol=1e-6)
    # the weights of a token sum to the scaling factor: 1e-20 is nothing
    np.testing.assert_allclose(weights.sum(-1), 2.448, rtol=1e-6)
    _, unbiased = jax.lax.top_k(scores, 3)
    assert np.any(np.sort(unbiased, -1) != np.sort(ids, -1), axis=-1).mean() > 0.2


# -- one chip's share --------------------------------------------------------
def test_all_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test: the held experts' terms of the shares [0, n),
    [n, 2n), ... of one routed layer, and the shared expert, which every
    chip computes alike, counted ONCE, sum to the uncut reference's
    feed-forward of the whole layer."""
    config = {**SMALL, "n_routed_experts": 16, "num_experts_per_tok": 3}
    experts, n = 16, 2  # eight shares of two experts
    whole = HybridMoELM({**config, "experts_held": [0, experts]})
    full = seeded(whole, seed=5)
    x = jax.random.normal(jax.random.key(11), (2 * T, config["hidden_size"]))
    router, blobs = full["l1_router"], full["l1_experts"]
    shared = full["l1_shared"]
    bias = seeded_stats(whole, seed=5)["l1_router"][0]
    ones = jnp.ones(x.shape[-1])
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm(x, ones, config["rms_norm_eps"])
        routed, _ = ref.moe(
            normed, router, blobs, config, held=(0, experts), bias=bias)
        want = routed + ref.shared_expert(normed, shared)
        total = whole._shared_expert(normed, shared)  # once
        assert rel(total, ref.shared_expert(normed, shared)) < 2e-5
        assert rel(total, want) > 0.1  # and it is not the layer
        for lo in range(0, experts, n):
            share = HybridMoELM({**config, "experts_held": [lo, n]})
            part = [b[lo:lo + n] for b in blobs]
            picked = share._route(x, ones, *router, bias)
            got = share._held_experts(normed, *picked, part)
            total = total + got
            assert moe.load(picked[1], experts).sum() == 2 * T * 3
            weights, ids, _ = ref.route(normed, router[0], config, bias)
            assert rel(got, ref.routed_experts(
                normed, weights, ids, part, (lo, n))) < 2e-5
    assert experts // n == 8
    assert rel(total, want) < 2e-5


# -- through the solver, the trainer and the app -----------------------------
def test_one_adam_step_and_a_round_move_every_leaf_and_each_bias():
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
    zeros = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], first.stats)
    state, losses = trainer.round(first, stacked, round_index=0)
    assert losses.shape == (2, 1) and np.all(np.isfinite(losses))
    for group, blobs in state.params.items():
        assert len(blobs) == len(dict(lm._group_blobs)[group])
        for i, blob in enumerate(blobs):
            assert not np.array_equal(np.asarray(blob)[0], initial[group][i])
            for moment in state.history:
                h = np.asarray(moment[group][i])
                assert h.shape == blob.shape and h.any(), (group, i)
    with jax.default_matmul_precision("highest"):
        want = [ref.balanced_stats(initial, b["tokens"], SMALL, zeros)
                for b in batches]
    assert set(state.stats) == set(lm.biased_routers)
    for group, (bias, load) in state.stats.items():
        for got, index in ((bias, 0), (load, 1)):
            got = np.asarray(got)
            mean = np.mean([np.asarray(w[group][index]) for w in want], 0)
            assert got.shape == (2, 8) and np.array_equal(got[0], got[1])
            np.testing.assert_allclose(got[0], mean, atol=1e-7)
        assert np.asarray(bias).any()


def test_a_checkpoint_round_trip_gives_the_state_bit_for_bit(tmp_path):
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import caffemodel, checkpoint

    lm, solver = lm_app.build_hybrid_lm_solver(SMALL)
    data = batch(6)
    state, _ = solver.step(solver.init_state(seed=1), {
        k: np.asarray(v)[None] for k, v in data.items()})
    assert np.asarray(state.stats["l1_router"][0]).any()
    blobs = caffemodel.net_blobs(lm, state.params, state.stats)
    assert [b.shape for b in blobs["l1_router"]] == [(32, 8), (8,), (8,)]
    assert [b.shape for b in blobs["l1_mixer"]] == [
        (32, 48), (32, 20), (16,), (16, 64), (32, 32)]
    prefix = str(tmp_path / "kanana_ck")
    checkpoint.snapshot(solver, state, prefix, fmt="BINARYPROTO")
    restored, _ = checkpoint.restore_newest_valid(solver, prefix)
    got, want = jax.device_get(restored), jax.device_get(state)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lm_app_trains_it_from_a_configuration_file(tmp_path):
    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app

    config = {**SMALL, "vocab_size": 256, "compute_dtype": "bfloat16"}
    path = tmp_path / "tiny-kanana.json"
    path.write_text(json.dumps(config))
    rc = lm_app.main([
        "--model_config", str(path), "--workers", "2", "--rounds", "3",
        "--tau", "2", "--batch", "2", "--seq_len", "24", "--log_every", "1",
        "--obs", "--obs_port", "0",
    ])
    assert rc == 0
    tm = obs.training_metrics()
    assert tm is not None and tm.lm_tokens.value == 3 * 2 * 2 * 2 * 24
    per_token = [tm.lm_held_assignments.labels(str(i)).value for i in (1, 2)]
    # 4 of 8 experts held, top-3: one and a half assignments a token expected
    assert all(0.3 < x < 3.0 for x in per_token)


def test_the_mixer_opens_its_two_scopes_and_keeps_the_kernels_names(
        model, params):
    """``MLALatent:l<i>_latent`` and ``MLAAttention:l<i>_mixer`` a layer,
    forward and backward, in the lowered program's locations."""
    data = batch(5)
    text = jax.jit(jax.grad(lambda p: model.loss_fn(p, {}, data)[0])).lower(
        params).as_text(debug_info=True)
    for i in range(3):
        assert f"MLALatent:l{i}_latent" in text
        assert f"MLAAttention:l{i}_mixer" in text
    assert "transpose(jvp(MLAAttention:l0_mixer))" in text
    assert "DenseMLP:l0_mlp" in text and "MoEShared:l1_shared" in text


def test_the_benchmarks_configuration_builds_the_published_model():
    """``benchmark/configs/kanana-2-30b-a3b.json`` as the app reads it: every
    key of the catalog's row unchanged but the three it lists as reduced,
    the five layers of the cut, the counts the file states."""
    config = load_config(
        os.path.join(ROOT, "benchmark", "configs", "kanana-2-30b-a3b.json"))
    table = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in table["configs"] if c["name"] == "kanana-2-30b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert {k: config[k] for k in PUBLISHED_KEYS
            if k not in entry["reduced"]} == {
        k: v for k, v in PUBLISHED_KEYS.items() if k not in entry["reduced"]}
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (5, [0, 16], 16032)
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["vocab_size"] == 128256
    lm = HybridMoELM(config)
    c = lm.config
    assert c["mixers"] == ("mla_attention",) * 5
    assert c["ffns"] == ("dense", "moe", "moe", "moe", "moe")
    assert lm.experts_held == (0, 16) and not c["tied"]
    assert lm.num_params() == config["held_here"]["parameters"] == 575_955_456
    assert config["held_here"]["bytes_at_12_a_parameter"] == 12 * 575_955_456
    assert c["expert_bias_update_rate"] == config["expert_bias_update_rate"] > 0
    # the source is the catalog row's, which names every key compared above
    assert entry["source"].endswith(
        "kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
