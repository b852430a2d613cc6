"""The ``laguna`` family (Laguna-XS.2) through ``models/hybrid_lm.HybridMoELM``
(gated grouped attention, full causal on some layers and over a window of
keys on the others, each kind with its own query heads and rotary, YaRN on
the full layers; a leading dense layer; a sigmoid router with a selection
bias and a scaling factor beside an ungated shared expert; an untied head)
against the plain reference ``benchmark/reference/laguna.py``, at a small
size on the CPU with a window shorter than the sequence and two head counts;
and the windowed attention (``ops/attention.causal_gqa_attention(..,
window=)``: the XLA path, and the flash kernels in interpreter mode) against
a masked softmax.

Float32 comparisons run under ``default_matmul_precision("highest")``; what
is left is summation order, so the bounds are a few float32 roundings: 2e-5
relative, 5e-4 on gradients.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from sparknet_tpu.models.hybrid_lm import (
    HybridMoELM, describe, load_config, yarn_inv_freq)
from sparknet_tpu.ops import attention, moe, pallas_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "laguna-xs.2.json")
FULL, SLIDING = "full_attention", "sliding_attention"
# the catalog row's keys (model-configs guide) at the published values, but
# the four lists of 40, whose pattern the cut keeps (test at the end)
PUBLISHED_KEYS = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5,
}
# one full layer, two sliding, one full; heads 4 and 6 over 2 K/V heads; a
# window of 7 in a sequence of 37; YaRN's ramp over the two pairs of a
# rotary part 4 wide; 1 dense + 3 routed layers, top-3 < 4 held < 8 experts
SMALL = {
    **PUBLISHED_KEYS, "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 4, "head_dim": 8, "num_key_value_heads": 2,
    "layer_types": [FULL, SLIDING, SLIDING, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 4], "sliding_window": 7,
    "intermediate_size": 48, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 12, "shared_expert_intermediate_size": 12,
    # this system's own keys
    "experts_held": [2, 4], "expert_bias_update_rate": 0.01,
}
T = 37  # odd, and over five windows


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def seeded(model, seed=0):
    """Norm weights off their initial values, every matrix at 0.1 (the
    attention's scores far from flat), so that no term can be ignored."""
    params, _ = model.init(seed)
    key = jax.random.key(seed + 100)
    for gi, (group, blobs) in enumerate(sorted(params.items())):
        for bi, blob in enumerate(blobs):
            k = jax.random.fold_in(jax.random.fold_in(key, gi), bi)
            if blob.ndim == 1:
                blobs[bi] = blob + 0.1 * jax.random.normal(k, blob.shape)
            else:
                blobs[bi] = 0.3 * jax.random.normal(k, blob.shape)
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
def test_heads_rotary_and_window_are_a_layers_own(model):
    c = model.config
    assert c["mixers"] == ("gated_attention", "window_attention",
                           "window_attention", "gated_attention")
    assert c["ffns"] == ("dense", "moe", "moe", "moe")
    assert c["heads"] == (4, 6, 6, 4) and c["window"] == (None, 7, 7, None)
    full, sliding = c["rope"][0], c["rope"][1]
    assert (full["theta"], full["dim"]) == (500000.0, 4)
    assert full["yarn"]["factor"] == 64.0
    assert sliding == {"theta": 10000.0, "dim": 8, "yarn": None}
    assert c["attention_out_dtype"] is None  # the compute dtype
    assert not c["tied"] and not c["shared_expert_gate"]
    assert c["router_scores"] == "sigmoid" and c["expert_bias"]
    assert c["routed_scaling_factor"] == 2.5 and c["topk_eps"] == 1e-20
    assert [model.is_attention_layer(i) for i in range(4)] == [True] * 4
    shapes = dict(model._group_blobs)
    assert shapes["l0_mixer"][0] == (32, 2 * 4 * 8)  # [q | gate] a head
    assert shapes["l1_mixer"][0] == (32, 2 * 6 * 8)
    assert shapes["l1_mixer"][5] == (6 * 8, 32)
    assert shapes["l1_shared"] == [(32, 12), (32, 12), (12, 32)]  # no gate


@pytest.mark.parametrize("family", ["qwen3_next", "lfm2_moe", "KeyeVL2",
                                    "deepseek_v3"])
def test_the_other_families_fill_the_per_layer_values_from_their_scalars(
        family):
    name = {"qwen3_next": "qwen3-next-80b-a3b", "lfm2_moe": "lfm2-24b-a2b",
            "KeyeVL2": "keye-vl-2.0-30b-a3b",
            "deepseek_v3": "kanana-2-30b-a3b"}[family]
    c = describe(load_config(os.path.join(
        ROOT, "benchmark", "configs", name + ".json")))
    depth = c["num_hidden_layers"]
    assert c["heads"] == (c["num_attention_heads"],) * depth
    assert c["rope"] == ({"theta": c["rope_theta"], "dim": c["rotary_dim"],
                          "yarn": None},) * depth
    assert c["window"] == (None,) * depth
    assert c["attention_out_dtype"] == jnp.float32


@pytest.mark.parametrize("change, key", [
    ({"gating": False}, "gating"),
    ({"gating": "per-head"}, "gating"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"num_attention_heads_per_layer": [4, 5, 6, 4]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [4, 6, 6]},
     "num_attention_heads_per_layer"),
    ({"layer_types": [FULL, "linear_attention", SLIDING, FULL]},
     "layer_types"),
    ({"mlp_layer_types": ["dense", "moe", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"rope_parameters": {**PUBLISHED_KEYS["rope_parameters"], FULL: {
        **PUBLISHED_KEYS["rope_parameters"][FULL], "rope_type": "linear"}}},
     "rope_type"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
])
def test_every_key_it_cannot_build_is_refused_by_name(change, key):
    with pytest.raises(ValueError, match=key):
        HybridMoELM({**SMALL, **change})


def test_a_missing_key_is_named():
    config = {k: v for k, v in SMALL.items() if k != "sliding_window"}
    with pytest.raises(ValueError, match="sliding_window"):
        describe(config)
    yarn = {k: v for k, v in PUBLISHED_KEYS["rope_parameters"][FULL].items()
            if k != "attention_factor"}
    with pytest.raises(ValueError, match="attention_factor"):
        describe({**SMALL, "rope_parameters": {
            **PUBLISHED_KEYS["rope_parameters"], FULL: yarn}})


# -- positions -------------------------------------------------------------------
def test_yarn_frequencies_at_the_published_values():
    """At the full layers' rotary part of 64: ``low = 5``, ``high = 16``; the
    first five pairs keep ``theta^(-2i/64)``, from the sixteenth on they are
    divided by the factor, and in between the ramp mixes the two."""
    theta, dim, factor = 500000.0, 64, 64.0
    turns = lambda r: dim * math.log(4096 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    assert (math.floor(turns(64)), math.ceil(turns(1))) == (5, 16)
    got = yarn_inv_freq(theta, dim, factor, 4096, 64, 1)
    f_e = theta ** (-np.arange(32) * 2.0 / dim)
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    want = f_e / factor * ramp + f_e * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:6], f_e[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], f_e[16:] / factor, rtol=1e-12)
    assert np.all(got[6:16] < f_e[6:16]) and np.all(got[6:16] > f_e[6:16] / 64)
    np.testing.assert_allclose(
        ref.yarn_frequencies(theta, dim, factor, 4096, 64, 1), want,
        rtol=1e-12)


def test_yarn_scales_the_turned_part_by_the_attention_factor(model):
    """The full layers' rotary turns the first half of a head by YaRN's
    frequencies with cos and sin times ``attention_factor``; the second half
    passes through; a score depends on the distance alone."""
    from sparknet_tpu.models.hybrid_lm import rotary

    rope = model.config["rope"][0]
    x = jax.random.normal(jax.random.key(0), (1, 9, 2, 8))
    got = np.asarray(rotary(x, rope["theta"], rope["dim"], rope["yarn"]))
    np.testing.assert_array_equal(got[..., 4:], np.asarray(x)[..., 4:])
    np.testing.assert_allclose(
        np.linalg.norm(got[..., :4], axis=-1),
        1.4158883083359672 * np.linalg.norm(np.asarray(x)[..., :4], axis=-1),
        rtol=1e-5)
    theta, dim, yarn = ref.rope_of(SMALL, FULL)
    assert rel(ref.rotary(x, theta, dim, yarn), got) < 1e-6
    assert rel(ref.rotary(x, theta, dim, None), got) > 0.1


# -- against the reference -------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference(
        model, params, stats):
    """With selection biases in play and without them, in program and
    reference alike."""
    data = batch(1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"], stats)
        want = jax.jit(lambda p, t, s: ref.logits(p, t, SMALL, stats=s))(
            params, data["tokens"], stats)
        assert got.shape == (2, T, SMALL["vocab_size"])
        assert rel(got, want) < 2e-5
        unbiased = jax.jit(model.forward_logits)(params, data["tokens"])
        assert rel(unbiased, want) > 1e-2  # the biases are in play
        (loss, (_, after)), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, stats, data)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, b["tokens"], b["targets"], SMALL,
                                  stats=stats)))(params, data)
        want_after = jax.jit(lambda p, t, s: ref.balanced_stats(
            p, t, SMALL, s))(params, data["tokens"], stats)
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    errors = {f"{group}[{i}]": rel(g, w)
              for group in grads
              for i, (g, w) in enumerate(zip(grads[group], want_grads[group]))}
    assert set(errors) == {
        f"{g}[{i}]" for g, blobs in params.items() for i in range(len(blobs))}
    worst = max(errors, key=errors.get)
    assert errors[worst] < 5e-4, (worst, errors[worst])
    for group in stats:
        bias, load = (np.asarray(a) for a in after[group])
        want_bias, want_load = (np.asarray(a) for a in want_after[group])
        assert np.array_equal(load, want_load)
        np.testing.assert_allclose(bias, want_bias, rtol=0, atol=1e-7)


# each of the benchmark cell's planted faults (``laguna_checks.PLANTS``), in
# the reference: the program, which has none of them, must disagree
@pytest.mark.parametrize("plant", [
    "window_511", "window_513", "full_causal_sliding", "plain_rotary_full",
    "whole_head_rotary_full", "per_head_scalar_gate", "full_heads_sliding",
    "no_routed_scaling", "bfloat16_reference"])
def test_a_planted_fault_in_the_reference_reads_far_from_the_program(
        model, params, plant):
    from benchmark import laguna_checks

    data = batch(2)
    faulty = laguna_checks.planted_reference({plant})
    config = SMALL
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"])
        want = jax.jit(lambda p, t: faulty.logits(p, t, config))(
            params, data["tokens"])
        clean = jax.jit(lambda p, t: ref.logits(p, t, config))(
            params, data["tokens"])
    assert rel(got, clean) < 2e-5 and rel(got, want) > 1e-3, plant
    assert laguna_checks.planted_reference(set()) is ref  # none: the module
    window = {"window_511": 6, "window_513": 8, "full_causal_sliding": None}
    if plant in window:  # one key at the window's edge, or no window
        assert faulty.window_of(config, SLIDING) == window[plant]
        assert faulty.window_of(config, FULL) is None


def test_bf16_compute_is_near_float32_and_not_float32(model, params):
    data = batch(2)
    low = HybridMoELM({**SMALL, "compute_dtype": "bfloat16"})
    exact = jax.jit(model.forward_logits)(params, data["tokens"])
    got = jax.jit(low.forward_logits)(params, data["tokens"])
    assert got.dtype == jnp.float32
    assert 1e-4 < rel(got, exact) < 5e-2


# -- the windowed attention --------------------------------------------------
def masked_softmax(q, k, v, window):
    """Full rows, the window as a mask: the oracle of both paths."""
    with jax.default_matmul_precision("highest"):
        return ref.attention_core(q, k, v, window, query_block=16)


def gqa_inputs(t, hq, hkv, d, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (2, t, hq, d))
    k, v = (jax.random.normal(key, (2, t, hkv, d)) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], (2, t, hq, d))


@pytest.mark.parametrize("kernels", [False, True])
@pytest.mark.parametrize("window, fault, far", [
    (9, None, False), (9, 8, True), (9, 10, True), (9, "causal", True),
    (16, None, False), (1, None, False)])
def test_windowed_attention_against_a_masked_softmax(
        monkeypatch, kernels, window, fault, far):
    """The XLA path and the kernels (interpreter mode; blocks of 8 queries
    of a 2-head group against 8 keys, so the band's edge crosses blocks)
    against the masked reference: output and the three gradients.  The
    reference planted one key short, one key long, or fully causal reads
    far from the program."""
    monkeypatch.setattr(attention, "lowerable", lambda: kernels)
    monkeypatch.setattr(attention, "KERNEL_BLOCK_K", 8)
    monkeypatch.setattr(attention, "KERNEL_Q_BYTES", 8 * 2 * 128 * 4)
    t, hq, hkv, d = 40, 4, 2, 128
    q, k, v, ct = gqa_inputs(t, hq, hkv, d)
    want_window = {None: window, "causal": None}.get(fault, fault)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda *a: attention.causal_gqa_attention(
            *a, window=window), q, k, v)
        want, want_vjp = jax.vjp(
            lambda *a: masked_softmax(*a, want_window), q, k, v)
    # a window of one key: the softmax is 1 whatever q and k, and their
    # gradients are 0 exactly in the reference, a few roundings in a kernel
    errors = [rel(a, b) if np.any(b) else float(jnp.max(jnp.abs(a)))
              for a, b in zip((got, *vjp(ct)), (want, *want_vjp(ct)))]
    if far:
        assert min(errors[:1]) > 1e-3, errors
    else:
        assert max(errors) < 2e-5, errors


def test_the_windowed_path_names_itself_and_counts_the_band(monkeypatch):
    """The instant carries the window and the band's blocks: at 2 x 8,192
    tokens, 64 / 8 heads of 128 in bfloat16, blocks of 512, a query block
    meets its own key block and the one before it, 31 of 256."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    calls = []
    real = pallas_attention.flash_attention
    monkeypatch.setattr(pallas_attention, "flash_attention",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8192, h, 128), jnp.float32)
    tracer = obs.install_tracer(Tracer())
    try:
        o = jax.eval_shape(lambda *a: attention.causal_gqa_attention(
            *a, compute_dtype=jnp.bfloat16, window=512, out_dtype=None),
            shape(64), shape(8), shape(8))
    finally:
        obs.uninstall_tracer()
    assert o.dtype == jnp.bfloat16  # handed over in the compute dtype
    (event,) = [e for e in tracer.events() if e["name"] == "attention_path"]
    args = event["args"]
    assert args["path"] == "pallas" and args["window"] == 512
    assert args["backward"] == "fused"
    assert (args["block_q"], args["block_k"]) == (
        attention.kernel_block_q(8, 128, jnp.bfloat16,
                                 attention.KERNEL_BLOCK_K),
        attention.KERNEL_BLOCK_K)
    assert (args["blocks_computed"], args["blocks_total"]) == (
        pallas_attention.blocks_met(8192, 8192, args["block_q"],
                                    args["block_k"], 512))
    assert calls[0]["window"] == 512


@pytest.mark.parametrize("t, bq, bk, window, met, band", [
    (8192, 512, 512, 512, (31, 256), (2, 2)),
    (8192, 512, 512, None, (136, 256), None),
    (8192, 256, 256, 512, (93, 1024), (3, 3)),
    (8192, 128, 128, 512, (310, 4096), (5, 5)),
    (8192, 256, 512, 512, (62, 512), (2, 4)),
    (40, 8, 8, 9, (9, 25), (2, 2)),
    (40, 8, 8, 1, (5, 25), (1, 1)),
    (40, 8, 8, 40, (15, 25), (5, 5)),
])
def test_blocks_met_counts_the_band(t, bq, bk, window, met, band):
    assert pallas_attention.blocks_met(t, t, bq, bk, window) == met
    if band is not None:
        assert pallas_attention.band(t, bq, bk, window) == band
    # the window of the whole sequence is causal attention
    assert pallas_attention.blocks_met(t, t, bq, bk, t) == (
        pallas_attention.blocks_met(t, t, bq, bk))


def grids(fn, *args):
    """The ``pallas_call`` grids of a traced program, in order."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("window, want", [
    # forward, the one-pass backward: (B, Hkv, query blocks, key blocks)
    (None, [(2, 2, 5, 5), (2, 2, 5, 5)]),
    (9, [(2, 2, 5, 2), (2, 2, 5, 2)]),
])
def test_without_a_window_the_kernels_grids_are_the_causal_ones(window, want):
    """``window=None`` leaves the accepted kernels' grids (every key block
    a step, the causal skip inside) and their block count as they were; a
    window shortens the inner axis to the band."""
    q, k, v, ct = gqa_inputs(40, 4, 2, 128)

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(lambda *a: pallas_attention.flash_attention(
            *a, causal=True, block_q=8, block_k=8, window=window,
            interpret=True), q, k, v)
        return o, vjp(ct)
    assert grids(fwd_bwd, q, k, v) == want
    assert pallas_attention.blocks_met(40, 40, 8, 8) == (15, 25)


# -- the router and one chip's share --------------------------------------------
def test_router_matches_the_reference_scaled_and_renormalised():
    key = jax.random.key(0)
    x = jax.random.normal(jax.random.fold_in(key, 0), (512, 32))
    w = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32, 8))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (8,))
    with jax.default_matmul_precision("highest"):
        weights, ids = moe.route(x, w, 3, scores="sigmoid", bias=bias,
                                 scale=2.5, eps=1e-20)
        want_w, want_ids, _ = ref.route(x, w, SMALL, bias)
    assert np.array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(want_w.sum(-1), 2.5, rtol=1e-6)


def test_all_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test, as the configuration's deployment cuts a
    layer: sixteen ranges of the experts, each one chip's held experts, and
    the shared expert, which every chip computes alike, counted ONCE, sum to
    the uncut reference's feed-forward of the whole layer."""
    config = {**SMALL, "num_experts": 32, "num_experts_per_tok": 4}
    experts, n = 32, 2
    whole = HybridMoELM({**config, "experts_held": [0, experts]})
    full = seeded(whole, seed=5)
    x = jax.random.normal(jax.random.key(11), (2 * T, config["hidden_size"]))
    router, blobs, shared = (full[f"l1_{p}"] for p in
                             ("router", "experts", "shared"))
    bias = seeded_stats(whole, seed=5)["l1_router"][0]
    ones = jnp.ones(x.shape[-1])
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm(x, ones, config["rms_norm_eps"])
        routed, _ = ref.moe(normed, router, blobs,
                            {**config, "experts_held": [0, experts]},
                            bias=bias)
        want = routed + ref.shared_expert(normed, shared)
        total = whole._shared_expert(normed, shared)  # once
        assert rel(total, want) > 0.1  # and it is not the layer
        for lo in range(0, experts, n):
            share = HybridMoELM({**config, "experts_held": [lo, n]})
            part = [b[lo:lo + n] for b in blobs]
            picked = share._route(x, ones, *router, bias)
            total = total + share._held_experts(normed, *picked, part)
    assert experts // n == 16
    assert rel(total, want) < 2e-5


# -- scopes, the app and the benchmark's file ------------------------------------
def test_the_mixers_open_their_scopes_and_keep_the_kernels_names(
        model, params):
    """``WindowAttention:l<i>_mixer`` on the sliding layers and
    ``GatedAttention:l<i>_mixer`` on the full ones, forward and backward."""
    data = batch(5)
    text = jax.jit(jax.grad(lambda p: model.loss_fn(p, {}, data)[0])).lower(
        params).as_text(debug_info=True)
    for i, scope in enumerate(["GatedAttention", "WindowAttention",
                               "WindowAttention", "GatedAttention"]):
        assert f"{scope}:l{i}_mixer" in text
    assert "WindowAttention:l0_mixer" not in text
    assert "transpose(jvp(WindowAttention:l1_mixer))" in text
    assert "DenseMLP:l0_mlp" in text and "MoEShared:l1_shared" in text


def test_lm_app_trains_it_from_a_configuration_file(tmp_path):
    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app

    config = {**SMALL, "vocab_size": 256, "compute_dtype": "bfloat16"}
    path = tmp_path / "tiny-laguna.json"
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


@pytest.mark.parametrize("group, count", [
    ("l0_mixer", 41_943_296), ("l1_mixer", 54_526_208),
    ("l4_mixer", 41_943_296), ("l0_mlp", 50_331_648),
    ("l1_router", 524_288), ("l1_experts", 50_331_648),
    ("l1_shared", 3_145_728), ("embed", 25_690_112), ("head", 25_690_112),
])
def test_parameter_count_by_part_at_the_published_widths(group, count):
    lm = HybridMoELM(load_config(CONFIG))
    shapes = dict(lm._group_blobs)[group]
    assert sum(int(np.prod(s)) for s in shapes) == count


def test_the_benchmarks_configuration_builds_the_published_model():
    """``benchmark/configs/laguna-xs.2.json`` as the app reads it: every key
    of the catalog's row unchanged but those it lists as reduced, one whole
    period of the pattern after the leading dense layer, the count by the
    shapes."""
    config = load_config(CONFIG)
    table = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in table["configs"] if c["name"] == "laguna-xs.2")
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "experts_held", "vocab_size"]
    assert {k: config[k] for k in PUBLISHED_KEYS
            if k not in entry["reduced"]} == {
        k: v for k, v in PUBLISHED_KEYS.items() if k not in entry["reduced"]}
    assert config["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (config["num_hidden_layers"], config["experts_held"],
            config["vocab_size"]) == (5, [0, 16], 12544)
    assert config["vocab_size"] * 8 == PUBLISHED_KEYS["vocab_size"]
    lm = HybridMoELM(config)
    assert lm.num_params() == config["held_here"]["parameters"] == 565_206_272
    assert lm.config["window"] == (None, 512, 512, 512, None)
