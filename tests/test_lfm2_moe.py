"""The LFM2 family through ``models/hybrid_lm.HybridMoELM`` (``model_type:
lfm2_moe``: gated short convolutions beside plain grouped attention, leading
dense layers, a sigmoid router with a selection bias and no shared expert, a
tied head) against the plain reference ``benchmark/reference/lfm2_moe.py``,
at a small size on the CPU: widths of a few tens, 6 layers (two dense, one
attention layer in each half), 16 experts top-4 of which 8 are held.

Float32 comparisons run under ``default_matmul_precision("highest")``; what
is left is summation order (grouped against expert-by-expert, blockwise
against full attention, one padded pass against a tap at a time), so the
bounds are a few float32 roundings: 2e-5 relative, 5e-4 on gradients.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from sparknet_tpu.models.hybrid_lm import HybridMoELM, describe
from sparknet_tpu.ops import moe
from sparknet_tpu.ops.attention import causal_gqa_attention
from sparknet_tpu.ops.short_conv import causal_depthwise_conv, gated_short_conv

TYPES = ["conv", "conv", "full_attention", "conv", "conv", "full_attention"]
SMALL = {
    "model_type": "lfm2_moe", "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 6, "layer_types": TYPES, "num_dense_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "norm_eps": 1e-5, "conv_L_cache": 3, "conv_bias": False,
    "intermediate_size": 48, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "use_expert_bias": True,
    # this system's own keys
    "experts_held": [4, 8], "expert_bias_update_rate": 0.01,
}
# the published widths at the benchmark's cut (benchmark/configs/lfm2-24b-a2b.json)
PUBLISHED = {
    **SMALL, "vocab_size": 8192, "hidden_size": 2048, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 1, "num_attention_heads": 32,
    "num_key_value_heads": 8, "intermediate_size": 11776, "num_experts": 64,
    "moe_intermediate_size": 1536, "experts_held": [0, 8],
}
T = 37  # odd, and not a multiple of anything


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def seeded(model, seed=0):
    """Seeded weights; the norm weights are moved off their initial values
    and the matrices widened to std 0.1, so that a test cannot pass by
    ignoring a term."""
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


def seeded_stats(model, seed=0, std=0.1):
    """``stats`` with selection biases off their initial zero: a bias of
    std 0.1 against scores near a half changes most selections."""
    _, stats = jax.eval_shape(model.init)
    key = jax.random.key(seed + 200)
    return {g: [std * jax.random.normal(jax.random.fold_in(key, i), b.shape)
                for i, b in enumerate(blobs)]
            for g, blobs in sorted(stats.items())}


def batch(seed, b=2, t=T, vocab=SMALL["vocab_size"]):
    tokens = jax.random.randint(jax.random.key(seed), (b, t + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


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
def test_layer_pattern_tied_head_and_groups(model):
    c = model.config
    assert c["mixers"] == tuple(
        "attention" if t == "full_attention" else "short_conv" for t in TYPES)
    assert c["ffns"] == ("dense", "dense", "moe", "moe", "moe", "moe")
    assert [model.is_attention_layer(i) for i in range(6)] == [
        t == "full_attention" for t in TYPES]
    assert model.routed_layers == (2, 3, 4, 5)
    groups = [g for g, _ in model._group_blobs]
    assert "head" not in groups and groups[0] == "embed"  # tied
    assert groups[-1] == "norm_f"
    assert not any(g.endswith("_shared") for g in groups)
    assert {g for g in groups if g.endswith("_mlp")} == {"l0_mlp", "l1_mlp"}
    assert {g for g in groups if g.endswith("_router")} == {
        f"l{i}_router" for i in (2, 3, 4, 5)}
    sizes = dict(model._group_blobs)
    assert sizes["l2_router"] == [(32, 16)]  # the selection bias is no blob
    assert sizes["l0_mixer"] == [(32, 96), (32, 3), (32, 32)]
    assert sizes["l2_mixer"] == [(32, 32), (32, 16), (32, 16), (8,), (8,),
                                 (32, 32)]
    assert set(model._blob_refs) == set(groups)
    # ... of the parameters: it is checkpointed with its router, from stats
    assert [(r.collection, r.index) for r in model._blob_refs["l2_router"]] == [
        ("params", 0), ("stats", 0), ("stats", 1)]
    assert model.biased_routers == tuple(f"l{i}_router" for i in (2, 3, 4, 5))


@pytest.mark.parametrize("group, count", [
    ("l0_mixer", 16_783_360),  # the gated short convolution
    ("l1_mixer", 10_485_888),  # grouped attention, heads of 64
    ("l0_mlp", 72_351_744),  # the leading dense layer
    ("l1_router", 131_072),  # 64 wide; its selection bias is no parameter
    ("l1_experts", 75_497_472),  # 8 held experts of 1,536
    ("embed", 16_777_216),  # 8,192 rows, the head with it
])
def test_parameter_count_at_the_published_widths(group, count):
    published = HybridMoELM(PUBLISHED)
    sizes = dict(published._group_blobs)
    assert sum(int(np.prod(s)) for s in sizes[group]) == count


def test_parameter_count_is_a_walk_of_the_shapes():
    published = HybridMoELM(PUBLISHED)
    # ISSUE 31's 469.3M, less the four biases of 64 it counted
    assert published.num_params() == 469_285_248 - 4 * 64
    shapes, stats = jax.eval_shape(published.init)
    walked = sum(int(np.prod(leaf.shape))
                 for leaf in jax.tree_util.tree_leaves(shapes))
    assert walked == published.num_params()
    assert {g: [b.shape for b in blobs] for g, blobs in stats.items()} == {
        f"l{i}_router": [(64,), (64,)] for i in (1, 2, 3, 4)}
    # uncut, one routed layer is the issue's 621M and the model 23.8B
    e, f, v = 2048, 1536, 65536
    conv, attention = 16_783_360, 10_485_888
    routed = 64 * 3 * e * f + e * 64 + 2 * e
    assert round((routed + conv) / 1e6) == 621
    whole = (30 * conv + 10 * attention + 2 * (72_351_744 + 2 * e)
             + 38 * routed + v * e + e)
    assert round(whole / 1e9, 1) == 23.8


@pytest.mark.parametrize("change, message", [
    ({"layer_types": TYPES[:5]}, "layer_types must list 6"),
    ({"layer_types": TYPES[:5] + ["sliding_attention"]}, "layer_types"),
    ({"conv_bias": True}, "conv_bias"),
    ({"model_type": "lfm3"}, "model_type"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
])
def test_a_configuration_it_cannot_build_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        HybridMoELM({**SMALL, **change})


def test_a_missing_key_is_named():
    config = {k: v for k, v in SMALL.items() if k != "conv_L_cache"}
    with pytest.raises(ValueError, match="conv_L_cache"):
        HybridMoELM(config)


def test_family_defaults_are_the_public_implementations():
    c = describe({k: v for k, v in SMALL.items() if k not in (
        "use_expert_bias", "routed_scaling_factor", "norm_topk_prob")})
    assert c["head_dim"] == c["rotary_dim"] == 8  # hidden / heads, whole head
    assert c["tied"] and c["expert_bias"] and not c["zero_centred_norm"]
    assert c["routed_scaling_factor"] == 1.0 and c["topk_eps"] == 1e-6
    assert c["router_scores"] == "sigmoid"
    assert c["shared_expert_intermediate_size"] == 0
    assert describe({**SMALL, "head_dim": 16})["head_dim"] == 16
    assert not describe({**SMALL, "tie_word_embeddings": False})["tied"]
    untied = HybridMoELM({**SMALL, "tie_word_embeddings": False})
    assert [g for g, _ in untied._group_blobs][-1] == "head"


def test_initialisation(model):
    params, stats = model.init(3)
    # plain norms start at one, matrices at 0.02; the selection biases and
    # the loads, where the balancing rule starts from: zero
    assert float(jnp.min(params["l0_n1"][0])) == 1.0
    assert float(jnp.min(params["l2_mixer"][3])) == 1.0
    assert 0.015 < float(jnp.std(params["l0_mlp"][0])) < 0.025
    assert set(stats) == set(model.biased_routers)
    assert all(b.shape == (16,) and not np.asarray(b).any()
               for blobs in stats.values() for b in blobs)
    lr, decay = model.param_multipliers()
    assert lr["l2_router"] == [1.0] and decay["l2_router"] == [1.0]
    assert all(x == 1.0 for xs in lr.values() for x in xs)
    # a file without a bias carries no stats; one without a rate a fixed bias
    assert HybridMoELM({**SMALL, "use_expert_bias": False}).init(0)[1] == {}
    fixed = {k: v for k, v in SMALL.items() if k != "expert_bias_update_rate"}
    assert describe(fixed)["expert_bias_update_rate"] == 0.0


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
    # the training step leaves every expert's load and moves each bias one
    # step of the balancing rule, as the reference does
    assert set(after) == set(want_after) == set(stats)
    for group in stats:
        bias, load = (np.asarray(a) for a in after[group])
        want_bias, want_load = (np.asarray(a) for a in want_after[group])
        assert np.array_equal(load, want_load)
        assert load.sum() == 2 * T * 4 and load.min() >= 0
        np.testing.assert_allclose(bias, want_bias, rtol=0, atol=1e-7)
        moved = bias - np.asarray(stats[group][0])
        np.testing.assert_allclose(
            moved, 0.01 * np.sign(load.mean() - load), atol=1e-7)
    # outside training the stats stay as they were
    _, (_, kept) = model.loss_fn(params, stats, data, train=False)
    assert all(a is b for g in stats for a, b in zip(kept[g], stats[g]))


def test_tied_head_takes_the_gradient_of_both_its_uses(model, params):
    """The embedding's gradient is the gather's plus the head's: with the
    head cut off (a loss on the hidden state) it reads otherwise."""
    data = batch(4)
    full = jax.grad(lambda p: model.loss_fn(p, {}, data)[0])(params)
    gather_only = jax.grad(
        lambda p: jnp.sum(model._hidden(p, data["tokens"])[0] ** 2))(params)
    rows = np.unique(np.asarray(data["tokens"]))
    unseen = np.setdiff1d(np.arange(SMALL["vocab_size"]), rows)
    # a row no token gathers still gets the head's gradient
    assert np.abs(np.asarray(full["embed"][0])[unseen]).max() > 0
    assert np.abs(np.asarray(gather_only["embed"][0])[unseen]).max() == 0


def test_bf16_compute_is_near_float32_and_not_float32(model):
    """A rounding or two of bfloat16 through six layers; below 1e-4 the
    bf16 path would be computing in float32, above 3e-2 it lost more than
    rounding.  Weights as ``init`` makes them (see the Qwen3-Next test)."""
    data = batch(2)
    params, _ = model.init(0)
    low = HybridMoELM({**SMALL, "compute_dtype": "bfloat16"})
    exact = jax.jit(model.forward_logits)(params, data["tokens"])
    got = jax.jit(low.forward_logits)(params, data["tokens"])
    assert got.dtype == jnp.float32
    assert 1e-4 < rel(got, exact) < 3e-2


# -- the short convolution -------------------------------------------------
@pytest.mark.parametrize("width", [3, 4])
@pytest.mark.parametrize("t", [1, 2, 3, 5, 64, 129])
def test_causal_convolution_matches_the_tap_loop(t, width):
    x = jax.random.normal(jax.random.key(t), (2, t, 12))
    w = jax.random.normal(jax.random.key(width), (12, width))
    got = causal_depthwise_conv(x, w)
    assert got.shape == x.shape and got.dtype == jnp.float32
    assert rel(got, ref.causal_conv_taps(x, w)) < 1e-6
    # by hand: the last tap is on the current token, zeros before the first
    xs, ws = np.asarray(x, np.float64), np.asarray(w, np.float64)
    want = np.zeros_like(xs)
    for now in range(t):
        for j in range(width):
            back = now - (width - 1) + j
            if back >= 0:
                want[:, now] += ws[:, j] * xs[:, back]
    assert rel(got, want) < 1e-6


@pytest.mark.parametrize("t", [1, 2, 7, 40])
def test_gated_short_convolution_matches_the_reference(t):
    bcu = jax.random.normal(jax.random.key(t), (2, t, 36))
    w = jax.random.normal(jax.random.key(9), (12, 3))
    want = ref.gated_conv_core(bcu, w)
    assert rel(gated_short_conv(bcu, w), want) < 1e-6
    low = gated_short_conv(bcu.astype(jnp.bfloat16), w)
    assert low.dtype == jnp.float32 and 1e-4 < rel(low, want) < 2e-2
    # the planted faults of the benchmark's check: a swapped gate, a dropped tap
    swapped = jnp.concatenate([bcu[..., 12:24], bcu[..., :12], bcu[..., 24:]], -1)
    assert rel(gated_short_conv(swapped, w), want) > 0.3 or t == 1
    assert rel(gated_short_conv(bcu, w.at[:, -1].set(0.0)), want) > 0.3


def test_short_convolution_is_causal():
    bcu = jax.random.normal(jax.random.key(0), (1, 9, 36))
    w = jax.random.normal(jax.random.key(1), (12, 3))
    base = gated_short_conv(bcu, w)
    later = gated_short_conv(bcu.at[:, 5].add(1.0), w)
    assert float(jnp.max(jnp.abs((later - base)[:, :5]))) == 0.0
    assert float(jnp.max(jnp.abs((later - base)[:, 5:8]))) > 0.0
    assert float(jnp.max(jnp.abs((later - base)[:, 8:]))) == 0.0  # width 3


# -- the router --------------------------------------------------------------
def router_inputs(rows=512, seed=0, bias_std=0.1):
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 0), (rows, 32))
    w = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    bias = bias_std * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    return x, w, bias


def dense(weights, ids, experts=16):
    rows = weights.shape[0]
    return jnp.zeros((rows, experts)).at[
        jnp.arange(rows)[:, None], ids].set(weights)


def lfm2_route(x, w, bias):
    return moe.route(x, w, 4, scores="sigmoid", bias=bias, eps=1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_the_reference_with_the_bias_in_play(seed):
    x, w, bias = router_inputs(seed=seed)
    with jax.default_matmul_precision("highest"):
        weights, ids = lfm2_route(x, w, bias)
        want_w, want_ids, scores = ref.route(x, w, SMALL, bias)
    assert np.array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    assert rel(dense(weights, ids), dense(want_w, want_ids)) < 1e-6
    # the bias is large enough to change the selection of most rows ...
    _, unbiased = jax.lax.top_k(scores, 4)
    moved = np.any(np.sort(unbiased, -1) != np.sort(ids, -1), axis=-1)
    assert moved.mean() > 0.3
    # ... and the weights are the UNbiased scores of the chosen, renormalised
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert np.all(np.asarray(weights.sum(-1)) < 1.0)  # the 1e-6


def biased_weights(x, w, bias):
    """The planted fault: weights gathered from the BIASED scores."""
    s = jax.nn.sigmoid(jnp.dot(x, w, precision="highest")) + bias
    weights, ids = jax.lax.top_k(s, 4)
    return weights / (weights.sum(-1, keepdims=True) + 1e-6), ids


def rounded_router(x, w, bias):
    r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return lfm2_route(r(x), r(w), bias)


@pytest.mark.parametrize("fault", [biased_weights, rounded_router])
def test_planted_router_faults_fail_the_comparison(fault):
    """Both faults read thousands of times the agreement above, far over
    the benchmark's bound (``check.router_rel_tol``, 1e-4)."""
    x, w, bias = router_inputs()
    with jax.default_matmul_precision("highest"):
        want_w, want_ids, _ = ref.route(x, w, SMALL, bias)
        got = dense(*fault(x, w, bias))
    assert rel(got, dense(want_w, want_ids)) > 5e-3


@pytest.mark.parametrize("scores", ["softmax", "sigmoid"])
@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_route_by_its_values(scores, biased, scale):
    x, w, bias = router_inputs(rows=64)
    weights, ids = moe.route(x, w, 4, scores=scores,
                             bias=bias if biased else None, scale=scale)
    logits = np.asarray(jnp.dot(x, w, precision="highest"), np.float64)
    s = (np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
         if scores == "softmax" else 1 / (1 + np.exp(-logits)))
    chosen = np.argsort(-(s + (np.asarray(bias) if biased else 0)), -1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(ids, -1))
    picked = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, scale * picked / picked.sum(-1, keepdims=True), rtol=2e-5)


def test_route_refuses_other_scores():
    x, w, _ = router_inputs(rows=4)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe.route(x, w, 4, scores="tanh")


def test_no_gradient_reaches_the_selection_bias():
    x, w, bias = router_inputs(rows=32)
    grad = jax.grad(lambda b: jnp.sum(lfm2_route(x, w, b)[0] ** 2))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0
    grad_w = jax.grad(lambda m: jnp.sum(lfm2_route(x, m, bias)[0] ** 2))(w)
    assert float(jnp.max(jnp.abs(grad_w))) > 0.0


# -- one chip's share --------------------------------------------------------
def test_all_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the outputs of the shares [0, n), [n, 2n), ...
    of one routed layer sum to the uncut reference's output of the whole
    layer.  There is no shared expert: nothing is counted once."""
    experts, n = SMALL["num_experts"], 2  # eight shares of two experts
    whole = HybridMoELM({**SMALL, "experts_held": [0, experts]})
    full = seeded(whole, seed=5)
    x = jax.random.normal(jax.random.key(11), (2 * T, SMALL["hidden_size"]))
    router, blobs = full["l2_router"], full["l2_experts"]
    bias = seeded_stats(whole, seed=5)["l2_router"][0]
    ones = jnp.ones(x.shape[-1])
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm(x, ones, SMALL["norm_eps"])
        want, _ = ref.moe(
            normed, router, blobs, SMALL, held=(0, experts), bias=bias)
        total = jnp.zeros_like(want)
        for lo in range(0, experts, n):
            share = HybridMoELM({**SMALL, "experts_held": [lo, n]})
            part = [b[lo:lo + n] for b in blobs]
            routed = share._route(x, ones, *router, bias)
            got = share._held_experts(normed, *routed, part)
            total = total + got
            # every share routes over all the experts, and counts them all
            assert moe.load(routed[1], experts).sum() == 2 * T * 4
            # the program's share is the reference's share
            weights, ids, _ = ref.route(normed, router[0], SMALL, bias)
            assert rel(got, ref.routed_experts(
                normed, weights, ids, part, (lo, n))) < 2e-5
    assert experts // n == 8
    assert rel(total, want) < 2e-5


def test_routing_counts_cover_the_layers_that_route(model, params, stats):
    from sparknet_tpu.models.hybrid_lm import routing_gauges

    data = batch(3)
    counts = np.asarray(jax.jit(model.routing_counts)(
        params, data["tokens"], stats))
    unbiased = np.asarray(jax.jit(model.routing_counts)(params, data["tokens"]))
    assert not np.array_equal(counts, unbiased)
    assert counts.shape == (4, 8)  # layers 2..5, the 8 held experts
    assert counts.sum(axis=1).max() <= data["tokens"].size * 4
    gauges = routing_gauges(counts, tokens=data["tokens"].size)
    # 8 of 16 experts held, top-4: two assignments a token expected
    assert 1.0 < np.mean(gauges["held_assignments_per_token"]) < 3.0
    assert all(s >= 1.0 for s in gauges["held_load_skew"])


# -- attention at heads of half a lane ---------------------------------------
@pytest.mark.parametrize("hq, hkv, d, path", [
    (32, 8, 64, "pallas"),  # LFM2: four heads of 64 side by side, 256 lanes
    (8, 8, 64, "xla"),  # one head of 64 a group: half a lane
    (4, 2, 16, "xla"),
    (16, 2, 256, "pallas"),  # Qwen3-Next, as before
])
def test_attention_path_at_half_lane_heads(monkeypatch, hq, hkv, d, path):
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer
    from sparknet_tpu.ops import attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 1024, h, d), jnp.float32)
    tracer = obs.install_tracer(Tracer())
    try:  # traced, not run
        jax.eval_shape(
            lambda q, k, v: causal_gqa_attention(
                q, k, v, compute_dtype=jnp.bfloat16),
            shape(hq), shape(hkv), shape(hkv))
    finally:
        obs.uninstall_tracer()
    (event,) = [e for e in tracer.events() if e["name"] == "attention_path"]
    assert event["args"]["path"] == path
    assert event["args"]["backward"] == (
        "fused" if path == "pallas" else "xla")
    assert (event["args"]["hq"], event["args"]["d"]) == (hq, d)


def test_heads_first_kernels_match_the_xla_path(monkeypatch):
    """Heads of 8 in groups of 16 (one vreg row of lanes, as 4 x 64 fill
    two): ``causal_gqa_attention`` through the kernels in interpreter mode
    against itself through the XLA path, output and gradients, in bfloat16."""
    from sparknet_tpu.ops import attention

    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, 40, 32, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 40, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 40, 2, 8))
    weights = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    run = lambda: jax.vjp(  # noqa: E731
        lambda *a: causal_gqa_attention(*a, compute_dtype=jnp.bfloat16),
        q, k, v)
    want, want_vjp = run()
    monkeypatch.setattr(attention, "lowerable", lambda: True)
    got, vjp = run()
    assert rel(got, want) < 6e-3
    for g_, w_ in zip(vjp(weights), want_vjp(weights)):
        assert rel(g_, w_) < 1e-2


# -- through the solver, the trainer and the app -----------------------------
def test_the_bias_moves_by_its_rule_and_holds_no_adam_state():
    """One ADAM step on each of two workers, then the average: every
    parameter moves and has both moments; a selection bias is no leaf of the
    parameters or of ADAM's state, and reads the mean over the workers of
    one balancing step on each worker's own load, as the reference gives
    them from the initial weights."""
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
    assert jax.tree_util.tree_structure(state.history[0]) == (
        jax.tree_util.tree_structure(state.params))
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
            assert got.shape == (2, 16) and np.array_equal(got[0], got[1])
            np.testing.assert_allclose(got[0], mean, atol=1e-7)
        assert np.asarray(bias).any()


def test_the_bias_is_checkpointed_with_its_router(tmp_path):
    """A snapshot carries the selection biases and loads (blobs of the
    router's group, from ``stats``); a restore gives the state bit for
    bit."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.io import caffemodel, checkpoint

    lm, solver = lm_app.build_hybrid_lm_solver(SMALL)
    data = batch(6)
    state, _ = solver.step(solver.init_state(seed=1), {
        k: np.asarray(v)[None] for k, v in data.items()})
    assert np.asarray(state.stats["l2_router"][0]).any()
    blobs = caffemodel.net_blobs(lm, state.params, state.stats)
    assert [b.shape for b in blobs["l2_router"]] == [(32, 16), (16,), (16,)]
    prefix = str(tmp_path / "lfm2_ck")
    checkpoint.snapshot(solver, state, prefix, fmt="BINARYPROTO")
    restored, _ = checkpoint.restore_newest_valid(solver, prefix)
    got, want = jax.device_get(restored), jax.device_get(state)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rate, evens", [(0.02, True), (0.0, False)])
def test_the_balancing_rule_evens_a_skewed_load(rate, evens):
    """A router that prefers four experts by far: the rule walks the biases
    until every expert's load is near the mean; at rate 0 nothing moves."""
    x, w, _ = router_inputs(rows=512, seed=3)
    x, w = x + 1.0, w.at[:, :4].add(0.05)  # 1.6 more in their logits
    bias = jnp.zeros((16,))

    @jax.jit
    def step(bias):
        _, ids = lfm2_route(x, w, bias)
        load = moe.load(ids, 16)
        return moe.balance(bias, load, rate), load

    _, first = step(bias)
    assert np.array_equal(first, ref.expert_load(lfm2_route(x, w, bias)[1], 16))
    assert first.sum() == 512 * 4 and first.max() / first.mean() > 2.5
    for _ in range(60):
        bias, load = step(bias)
    skew = float(load.max() / load.mean())
    assert (skew < 1.3) == evens and (skew > 2.5) != evens
    np.testing.assert_allclose(
        ref.balance_step(bias, load, rate), moe.balance(bias, load, rate))


def test_lm_app_trains_lfm2_from_a_configuration_file(tmp_path):
    """``lm_app --model_config``: the byte corpus through ``Solver(net=...)``
    with ADAM and ``ParameterAveragingTrainer.round`` on two workers; the
    routing gauges carry the index of the layer that routes."""
    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app

    config = {**SMALL, "vocab_size": 256, "compute_dtype": "bfloat16"}
    path = tmp_path / "tiny-lfm2.json"
    path.write_text(json.dumps(config))
    rc = lm_app.main([
        "--model_config", str(path), "--workers", "2", "--rounds", "3",
        "--tau", "2", "--batch", "2", "--seq_len", "24", "--log_every", "1",
        "--obs", "--obs_port", "0",
    ])
    assert rc == 0
    tm = obs.training_metrics()
    assert tm is not None and tm.lm_tokens.value == 3 * 2 * 2 * 2 * 24
    per_token = [tm.lm_held_assignments.labels(str(i)).value
                 for i in (2, 3, 4, 5)]
    skew = [tm.lm_held_load_skew.labels(str(i)).value for i in (2, 3, 4, 5)]
    # 8 of 16 experts held, top-4: two assignments a token expected
    assert all(0.5 < x < 3.5 for x in per_token) and all(x >= 1 for x in skew)


def test_the_benchmarks_configuration_builds_the_published_model():
    """``benchmark/configs/lfm2-24b-a2b.json`` as the app reads it: the five
    layers of the cut, heads of 64, the counts the file states."""
    import os

    from sparknet_tpu.models.hybrid_lm import load_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_config(
        os.path.join(root, "benchmark", "configs", "lfm2-24b-a2b.json"))
    lm = HybridMoELM(config)
    c = lm.config
    assert c["mixers"] == ("short_conv", "attention", "short_conv",
                           "short_conv", "short_conv")
    assert c["ffns"] == ("dense", "moe", "moe", "moe", "moe")
    assert (c["head_dim"], c["hidden_size"], c["conv_L_cache"]) == (64, 2048, 3)
    assert lm.experts_held == (0, 8) and c["tied"]
    assert lm.num_params() == config["held_here"]["parameters"] == 469_284_992
    assert config["held_here"]["bytes_at_16_a_parameter"] == 16 * 469_284_992
    assert c["expert_bias_update_rate"] == config["expert_bias_update_rate"] > 0
