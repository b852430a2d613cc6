"""The hybrid linear-attention / sparse-expert LM (``models/hybrid_lm.py``,
``ops/delta_rule.py``, ``ops/moe.py``, ``ops/attention.causal_gqa_attention``)
against the plain reference ``benchmark/reference/qwen3_next.py``, at a small
size on the CPU: widths of a few tens, 8 layers (two periods of three
DeltaNet layers and one attention layer), 16 experts top-4, T of two and a
half of the delta rule's chunks of 64 so that a ragged last chunk is covered
(the model takes no chunk or block size: ragged query blocks, runs of blocks
and small chunks are tested on the ops, which take them as arguments).

Float32 comparisons run under ``default_matmul_precision("highest")``; what
is left is summation order (chunked against token-by-token, grouped against
expert-by-expert), so the bounds are a few float32 roundings of sums of tens
to hundreds of terms: 2e-5 relative, 5e-4 on gradients (they pass through
eight layers, and the decay's, ``A_log`` / ``dt_bias`` / ``g``, through the
exponentials of running sums: 1.1e-4 was measured on ``dt_bias``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from sparknet_tpu.models.hybrid_lm import HybridMoELM, routing_gauges
from sparknet_tpu.ops import delta_rule, moe, pallas_delta_rule
from sparknet_tpu.ops.attention import causal_gqa_attention
from sparknet_tpu.ops.delta_rule import gated_delta_rule

SMALL = {
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 8,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "norm_topk_prob": True,
    # this system's own keys
    "experts_held": [4, 8],
}
T = 160  # two and a half chunks of 64
T_OP = 40  # on the ops: two and a half chunks of 16


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def seeded(model, seed=0, spread=True):
    """Seeded weights; the zero-initialised norm weights and the one-
    initialised vectors are moved off their defaults so that a test cannot
    pass by ignoring them."""
    params, _ = model.init(seed)
    if spread:
        key = jax.random.key(seed + 100)
        for gi, (group, blobs) in enumerate(sorted(params.items())):
            for bi, blob in enumerate(blobs):
                if blob.ndim == 1:
                    k = jax.random.fold_in(jax.random.fold_in(key, gi), bi)
                    blobs[bi] = blob + 0.1 * jax.random.normal(k, blob.shape)
                else:
                    blobs[bi] = blob * 5.0  # std 0.1: every term matters
    return params


def batch(seed, b=2, t=T, vocab=SMALL["vocab_size"]):
    tokens = jax.random.randint(jax.random.key(seed), (b, t + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


@pytest.fixture(scope="module")
def model():
    return HybridMoELM(SMALL)


@pytest.fixture(scope="module")
def params(model):
    return seeded(model)


def test_layer_pattern_and_parameter_count(model):
    assert [model.is_attention_layer(i) for i in range(8)] == [
        False, False, False, True] * 2
    published = HybridMoELM({
        **SMALL, "vocab_size": 18992, "hidden_size": 2048,
        "num_hidden_layers": 4, "num_attention_heads": 16, "head_dim": 256,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "num_experts": 512, "num_experts_per_tok": 10,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "experts_held": [0, 32],
    })
    sizes = dict(published._group_blobs)
    count = lambda g: sum(int(np.prod(s)) for s in sizes[g])  # noqa: E731
    assert count("l0_mixer") == 33_718_464  # Gated DeltaNet
    assert count("l3_mixer") == 27_263_488  # gated attention
    assert count("l0_router") == 1_048_576
    assert count("l0_shared") == 3_147_776
    assert count("l0_experts") == 100_663_296
    assert published.num_params() == 625_667_136  # ISSUE 27: 625.7M


def test_logits_loss_and_every_gradient_match_the_reference(model, params):
    data = batch(1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.forward_logits)(params, data["tokens"])
        want = jax.jit(lambda p, t: ref.logits(p, t, SMALL))(
            params, data["tokens"])
        assert rel(got, want) < 2e-5
        (loss, _), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, {}, data)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, b["tokens"], b["targets"], SMALL)))(
                params, data)
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    errors = {
        f"{group}[{i}]": rel(g, w)
        for group in grads
        for i, (g, w) in enumerate(zip(grads[group], want_grads[group]))
    }
    assert set(errors) == {
        f"{g}[{i}]" for g, blobs in params.items() for i in range(len(blobs))}
    # the decay's two vectors (A_log, dt_bias) of a DeltaNet layer have
    # gradients that all but vanish (most heads forget within a token:
    # exp(-A) with A up to 16), what is left of them is float32 noise of
    # terms that cancel: 8.3e-4 at this T, against 1e-4 and less elsewhere
    decay = {f"l{i}_mixer[{j}]" for i in range(8) for j in (3, 4)
             if not model.is_attention_layer(i)}
    print({k: float(f"{v:.2g}") for k, v in errors.items() if k in decay})
    worst = max(set(errors) - decay, key=errors.get)
    assert errors[worst] < 5e-4, (worst, errors[worst])
    assert max(errors[k] for k in decay) < 5e-3
    # nothing is trivially zero: every blob has a gradient
    assert all(float(jnp.max(jnp.abs(g))) > 0 for gs in want_grads.values()
               for g in gs)


DECAYS = ["near_one", "near_zero", "mixed"]


def rule_inputs(b, t, h, dk, dv, decay, seed=7):
    """Unit keys, scaled unit queries; a decay that forgets nothing, forgets
    within a token, or spans both."""
    keys = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, h)))
    u = jax.random.uniform(keys[4], (b, t, h))
    g = {"near_one": -1e-4 * u, "near_zero": -8.0 - 8.0 * u,
         "mixed": -jnp.exp(6.0 * u - 5.0)}[decay]
    return q, k, v, g, beta


def outputs_and_gradients(fn, inputs):
    """``fn``'s outputs (one or a tuple) and the gradients of a fixed
    weighting of them, in all five inputs."""
    def scalar(*xs):
        outs = fn(*xs)
        outs = outs if isinstance(outs, tuple) else (outs,)
        total = sum(
            jnp.sum(o.astype(jnp.float32)
                    * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
            for o in outs)
        return total, outs
    (_, outs), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1, 2, 3, 4), has_aux=True))(*inputs)
    return outs, grads


def assert_gradients_close(grads, want_grads, tol):
    scale = max(float(jnp.linalg.norm(w)) for w in want_grads)
    for name, got_g, want_g in zip("qkvgb", grads, want_grads):
        # against the largest gradient where this one all but vanishes (g's
        # at decay ~0 is 1e-5 of v's: float32 noise of terms that cancel)
        floor = max(float(jnp.linalg.norm(want_g)), 1e-3 * scale)
        assert float(jnp.linalg.norm(got_g - want_g)) < tol * floor, name


@pytest.mark.parametrize("decay", DECAYS)
def test_chunked_delta_rule_matches_the_recurrence(decay):
    b, t, h, dk, dv = 2, T_OP, 6, 8, 8
    inputs = rule_inputs(b, t, h, dk, dv, decay)
    with jax.default_matmul_precision("highest"):
        (o,), grads = outputs_and_gradients(
            lambda *a: gated_delta_rule(*a, chunk=16), inputs)
        (want,), want_grads = outputs_and_gradients(
            ref.delta_rule_recurrent, inputs)
    assert o.shape == (b, t, h, dv)
    assert rel(o, want) < 2e-5
    assert_gradients_close(grads, want_grads, 5e-4)


# the shapes the kernels take: heads of 128, chunks of 64, and a ragged tail
# (200 tokens: three chunks and 8 tokens, padded to two groups of 128)
KERNEL_SHAPE = (1, 200, 2, 128, 128)
# float32: the existing bounds; bfloat16: the configuration's
# ``delta_rule_rel_tol`` (benchmark/configs/qwen3-next-80b-a3b.json)
KERNEL_BOUNDS = {"float32": (2e-5, 5e-4), "bfloat16": (0.02, 0.02)}


@pytest.fixture
def kernels_lower(monkeypatch):
    """The selection sees a backend that lowers the kernels; the kernels
    themselves still see the CPU and run in interpreter mode."""
    monkeypatch.setattr(delta_rule, "lowerable", lambda: True)


@pytest.mark.parametrize("dtype", sorted(KERNEL_BOUNDS))
@pytest.mark.parametrize("decay", DECAYS)
def test_delta_rule_kernels_match_the_recurrence(kernels_lower, decay, dtype):
    """Forward and backward kernel (interpreter mode) inside the whole
    rule, against the token-by-token recurrence."""
    inputs = rule_inputs(*KERNEL_SHAPE, decay)
    out_tol, grad_tol = KERNEL_BOUNDS[dtype]
    with jax.default_matmul_precision("highest"):
        (o,), grads = outputs_and_gradients(
            lambda *a: gated_delta_rule(*a, compute_dtype=jnp.dtype(dtype)),
            inputs)
        (want,), want_grads = outputs_and_gradients(
            ref.delta_rule_recurrent, inputs)
    assert o.shape == want.shape
    assert rel(o, want) < out_tol
    assert_gradients_close(grads, want_grads, grad_tol)


# every decay regime in both dtypes at the benchmark's shape, then the other
# shapes ``pallas_delta_rule.accepts`` lets in: four, two and one chunk a
# group of 128 tokens, a key head wider than the value head and the reverse
@pytest.mark.parametrize("decay, dtype, chunk, dk, dv", [
    *((decay, dtype, 64, 128, 128)
      for decay in DECAYS for dtype in sorted(KERNEL_BOUNDS)),
    ("mixed", "float32", 32, 128, 128),
    ("mixed", "float32", 128, 128, 256),
    ("mixed", "bfloat16", 16, 256, 128),
])
def test_delta_rule_kernels_match_the_xla_stage(decay, dtype, chunk, dk, dv):
    """The kernels against ``_within_chunks``, the oracle and the fallback:
    the five outputs and, through them, the five gradients."""
    b, t, h, cd = 1, 256, 2, jnp.dtype(dtype)
    assert pallas_delta_rule.accepts(chunk, dk, dv)
    inputs = rule_inputs(b, t, h, dk, dv, decay)

    def blocks(x):  # (B, T, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    out_tol, grad_tol = KERNEL_BOUNDS[dtype]
    with jax.default_matmul_precision("highest"):
        got, grads = outputs_and_gradients(
            lambda *xs: tuple(
                pallas_delta_rule.within_chunks(*xs, chunk, cd)), inputs)
        want, want_grads = outputs_and_gradients(
            lambda *xs: delta_rule._within_chunks(
                *(blocks(x) for x in xs), cd)[:5], inputs)
    for name, g, w in zip(("u", "w", "qk", "q_in", "k_out"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert rel(g.astype(jnp.float32), w.astype(jnp.float32)) < out_tol, name
    assert_gradients_close(grads, want_grads, grad_tol)


@pytest.mark.parametrize("lowers, dk, path, why", [
    (False, 128, "xla", "no Pallas lowering on cpu"),
    (True, 8, "xla", "dk and dv whole lanes"),
    (True, 128, "pallas", ""),
])
def test_delta_rule_selects_by_backend_and_shape(
        monkeypatch, lowers, dk, path, why):
    """No switch: the backend and the shapes decide, and each trace says so
    in one ``obs`` instant."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    monkeypatch.setattr(delta_rule, "lowerable", lambda: lowers)
    calls = []
    real = pallas_delta_rule.within_chunks
    monkeypatch.setattr(pallas_delta_rule, "within_chunks",
                        lambda *a: calls.append(a[5:]) or real(*a))
    inputs = rule_inputs(1, 70, 2, dk, dk, "mixed")
    tracer = obs.install_tracer(Tracer())
    try:
        o = jax.jit(lambda *a: gated_delta_rule(
            *a, compute_dtype=jnp.bfloat16))(*inputs)
    finally:
        obs.uninstall_tracer()
    assert o.shape == inputs[2].shape
    assert len(calls) == (path == "pallas")
    events = [e for e in tracer.events() if e["name"] == "delta_rule_path"]
    assert len(events) == 1  # one a trace
    args = events[0]["args"]
    assert args["path"] == path and why in args["why"]
    assert (args["why"] == "") == (path == "pallas")
    assert (args["backend"], args["chunk"], args["dk"], args["dv"],
            args["dtype"]) == ("cpu", 64, dk, dk, "bfloat16")


def test_inverse_cotangent_by_its_identity_matches_autodiff():
    """``dL = -tril(T^T dT T^T, -1)`` against ``jax.vjp`` through the
    doubling's twelve products, on correlated keys (where powers of ``L``
    would cancel), two chunks of 64 as the kernel's group of 128 holds
    them."""
    chunk, d = 64, 16
    keys = jax.random.split(jax.random.key(5), 4)
    common = jax.random.normal(keys[0], (1, 1, d))
    k = common + 0.1 * jax.random.normal(keys[1], (2, chunk, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jax.random.normal(keys[2], (2, chunk, 1)))
    strict = jnp.tril(jnp.einsum("nid,njd->nij", k * beta, k), -1)
    d_inv = jax.random.normal(keys[3], (2, chunk, chunk))
    with jax.default_matmul_precision("highest"):
        inv, vjp = jax.vjp(delta_rule._unit_lower_inverse, strict)
        (want,) = vjp(d_inv)
        m = pallas_delta_rule._masks(chunk)
        group = lambda x: jax.scipy.linalg.block_diag(*x)  # noqa: E731
        f32 = jnp.dtype("float32")
        (got_inv,) = pallas_delta_rule._unit_lower_inverses(
            [group(strict)], m, chunk, f32)
        (got,) = pallas_delta_rule._inverse_cotangents(
            [got_inv], [group(d_inv)], m, f32)
    # the keys are correlated: L's powers grow, a series in them cancels
    assert float(jnp.max(jnp.sum(jnp.abs(strict), -1))) > 10
    assert rel(got_inv, group(inv)) < 1e-5
    assert rel(got, group(want)) < 1e-5


def skewed_router(hidden, experts, hot, cold, key):
    """A router that sends nearly every token to ``hot`` and none to
    ``cold`` (inputs are positive in their first coordinate)."""
    w = 0.01 * jax.random.normal(key, (hidden, experts))
    return w.at[0, hot].set(50.0).at[0, cold].set(-50.0)


@pytest.mark.parametrize("slack", [8.0, 0.05])
def test_held_experts_match_the_dense_loop_under_skew(slack):
    """One held expert gets most tokens and one gets none; with ``slack``
    0.05 the grouped path is too short and the exact path over chunks of
    tokens runs."""
    n_tok, e, f, experts, top_k = 96, 16, 8, 16, 4
    lo, n = 4, 8
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (n_tok, e)).at[:, 0].set(1.0)
    router = skewed_router(e, experts, hot=6, cold=9, key=keys[1])
    gate, up = (0.3 * jax.random.normal(k, (n, e, f)) for k in keys[2:4])
    down = 0.3 * jax.random.normal(keys[4], (n, f, e))
    config = {"num_experts_per_tok": top_k, "norm_topk_prob": True}

    def program(x, router, gate, up, down):
        weights, ids = moe.route(x, router, top_k)
        order, counts = moe.plan(ids, lo, n)
        rows = moe.fast_rows_for(n_tok, top_k, experts, n, slack=slack,
                                 multiple=8)
        return moe.held_experts(x, weights, ids, order, counts, gate, up,
                                down, lo=lo, fast_rows=rows), counts

    def plain(x, router, gate, up, down):
        weights, ids = ref.route(x, router, config)
        return ref.routed_experts(x, weights, ids, (gate, up, down), (lo, n))

    with jax.default_matmul_precision("highest"):
        out, counts = jax.jit(program)(x, router, gate, up, down)
        want = jax.jit(plain)(x, router, gate, up, down)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(program(*a)[0] ** 2),
            argnums=(0, 1, 2, 3, 4)))(x, router, gate, up, down)
        want_grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(plain(*a) ** 2),
            argnums=(0, 1, 2, 3, 4)))(x, router, gate, up, down)
    counts = np.asarray(counts)
    assert counts[6 - lo] == n_tok and counts[9 - lo] == 0
    fast = moe.fast_rows_for(n_tok, top_k, experts, n, slack=slack, multiple=8)
    assert (counts.sum() <= fast) == (slack > 1)  # which path ran
    assert rel(out, want) < 2e-5
    for got_g, want_g in zip(grads, want_grads):
        assert rel(got_g, want_g) < 5e-4


def test_all_shares_add_up_to_the_uncut_layer(model, params):
    """The guide's share test: the outputs of the shares [0, n), [n, 2n), ...
    of one MoE layer, the shared expert counted once, sum to the uncut
    reference's output of the whole layer."""
    experts, n = SMALL["num_experts"], 4
    whole = HybridMoELM({**SMALL, "experts_held": [0, experts]})
    full = seeded(whole, seed=5)
    x = jax.random.normal(jax.random.key(11), (2 * T, SMALL["hidden_size"]))
    router, blobs, shared = (full["l0_router"][0], full["l0_experts"],
                             full["l0_shared"])
    with jax.default_matmul_precision("highest"):
        normed = ref.rms_norm0(x, 0.0, SMALL["rms_norm_eps"])
        want = ref.moe(normed, router, blobs, shared, SMALL, held=(0, experts))
        total = whole._shared_expert(normed, shared)
        for lo in range(0, experts, n):
            share = HybridMoELM({**SMALL, "experts_held": [lo, n]})
            part = [b[lo:lo + n] for b in blobs]
            total = total + share._held_experts(
                normed, *share._route(x, jnp.zeros(x.shape[-1]), router), part)
            # the program's share is the reference's share
            assert rel(
                share._held_experts(
                    normed, *share._route(x, jnp.zeros(x.shape[-1]), router),
                    part),
                ref.routed_experts(normed, *ref.route(normed, router, SMALL),
                                   part, (lo, n))) < 2e-5
    assert rel(total, want) < 2e-5


def test_bf16_compute_is_near_float32_and_not_float32(model):
    """bf16 keeps 8 bits (2^-9 = 2e-3 a rounding); through eight layers the
    logits differ from float32's by a rounding or two: 2.9e-3 to 3.0e-3 on
    three seeds.  Below 1e-4 the bf16 path would be computing in float32;
    above 2e-2 it lost more than rounding.  Weights as ``init`` makes them:
    at five times that, near-ties of a top-4 of 16 flip under rounding and a
    flipped expert moves a token's logits wholesale (0.10 to 0.13)."""
    data = batch(2)
    params = seeded(model, spread=False)
    low = HybridMoELM({**SMALL, "compute_dtype": "bfloat16"})
    exact = jax.jit(model.forward_logits)(params, data["tokens"])
    got = jax.jit(low.forward_logits)(params, data["tokens"])
    assert got.dtype == jnp.float32
    assert 1e-4 < rel(got, exact) < 2e-2
    loss = lambda m: float(jax.jit(m.loss_fn)(params, {}, data)[0])  # noqa: E731
    assert 0 < abs(loss(low) - loss(model)) < 2e-2 * loss(model)


def test_routing_counts_and_gauges(model, params):
    data = batch(3)
    counts = np.asarray(jax.jit(model.routing_counts)(params, data["tokens"]))
    assert counts.shape == (8, 8)
    _, ids = ref.route(
        ref.rms_norm0(params["embed"][0][data["tokens"]], 0.0, 1e-6),
        params["l0_router"][0], SMALL)  # shape only: ids of some routing
    assert counts.sum(axis=1).max() <= ids.size
    gauges = routing_gauges(counts, tokens=data["tokens"].size)
    assert len(gauges["held_assignments_per_token"]) == 8
    # 8 of 16 experts held, top-4: two assignments a token expected
    assert 1.0 < np.mean(gauges["held_assignments_per_token"]) < 3.0
    assert all(s >= 1.0 for s in gauges["held_load_skew"])


def test_generation_is_refused(model, params):
    with pytest.raises(NotImplementedError, match="not supported"):
        model.prefill_with_kv(params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(NotImplementedError, match="not supported"):
        model.decode_step_with_kv(params, None, None, None, None)


def test_solver_hands_compute_dtype_to_a_net_that_takes_one():
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.config import parse_solver_prototxt
    from sparknet_tpu.models import build_transformer_lm
    from sparknet_tpu.solver import Solver

    lm, solver = lm_app.build_hybrid_lm_solver(
        {**SMALL, "compute_dtype": "bfloat16"})
    assert solver.net is lm and lm.compute_dtype == jnp.bfloat16
    assert solver.method == "ADAM" and solver.param.momentum2 == 0.95
    lm, _ = lm_app.build_hybrid_lm_solver(SMALL)
    assert lm.compute_dtype is None
    plain = parse_solver_prototxt('base_lr: 0.1 lr_policy: "fixed"')
    # TransformerLM has float32 written into its forward: asked for another
    # precision it says so, and without one it is built as before
    with pytest.raises(ValueError, match="set_compute_dtype"):
        Solver(plain, net=build_transformer_lm(), compute_dtype="bfloat16")
    assert Solver(plain, net=build_transformer_lm()).compute_dtype is None


def test_one_adam_round_on_two_workers_is_two_solver_runs_averaged():
    """``ParameterAveragingTrainer.round`` through ``Solver(net=...)`` with
    ADAM on two virtual CPU workers: parameters are the mean of two plain
    solver runs (each on its worker's batch and rng), history is each run's
    own (never averaged)."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh
    from sparknet_tpu.utils.rngs import default_train_key

    config = {**SMALL, "num_hidden_layers": 4, "compute_dtype": "bfloat16"}
    _, solver = lm_app.build_hybrid_lm_solver(config)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    trainer = ParameterAveragingTrainer(solver, mesh)
    tau = 2
    per_worker = [
        {k: jnp.stack([batch(10 * w + i)[k] for i in range(tau)])
         for k in ("tokens", "targets")} for w in range(2)]
    stacked = {k: np.stack([np.asarray(b[k]) for b in per_worker])
               for k in ("tokens", "targets")}
    state, losses = trainer.round(trainer.init_state(seed=4), stacked,
                                  round_index=0)
    assert losses.shape == (2, tau)
    runs = []
    for w in range(2):
        rng = jax.random.fold_in(default_train_key(0), w)
        runs.append(solver.step(solver.init_state(seed=4), per_worker[w],
                                rng=rng)[0])
    leaves = jax.tree_util.tree_leaves
    for got, a, b in zip(leaves(state.params), leaves(runs[0].params),
                         leaves(runs[1].params)):
        want = (np.asarray(a) + np.asarray(b)) / 2
        np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-5, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]))
    for got, a, b in zip(leaves(state.history), leaves(runs[0].history),
                         leaves(runs[1].history)):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(a), rtol=2e-5, atol=1e-9)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(b), rtol=2e-5, atol=1e-9)
    # Adam moved every blob, both moments
    assert all(float(jnp.max(jnp.abs(h))) > 0 for h in leaves(state.history))


def test_init_state_stacks_what_the_solver_initialises():
    """One worker takes the donated reshape on the device, two the host
    path: both give every worker the solver's own initial state."""
    from sparknet_tpu.apps import lm_app
    from sparknet_tpu.parallel import ParameterAveragingTrainer, make_mesh

    _, solver = lm_app.build_hybrid_lm_solver(
        {**SMALL, "num_hidden_layers": 4})
    single = solver.init_state(seed=9)
    for n in (1, 2):
        mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
        state = ParameterAveragingTrainer(solver, mesh).init_state(seed=9)
        for got, want in zip(jax.tree_util.tree_leaves(state),
                             jax.tree_util.tree_leaves(single)):
            assert got.shape == (n,) + want.shape
            assert len(got.sharding.device_set) == n
            for w in range(n):
                np.testing.assert_array_equal(np.asarray(got[w]),
                                              np.asarray(want))


@pytest.mark.parametrize("t,block_q,segments", [(40, 16, 4), (33, 16, 2),
                                                (64, 64, 1), (48, 8, 4)])
def test_blockwise_gqa_attention_matches_full_attention(t, block_q, segments):
    """Ragged last block, runs of blocks that meet only their own keys, one
    block: all the full-matrix attention of the reference, forward and
    gradient."""
    b, hq, hkv, d = 2, 4, 2, 16
    keys = jax.random.split(jax.random.key(t), 3)
    q = jax.random.normal(keys[0], (b, t, hq, d))
    k = jax.random.normal(keys[1], (b, t, hkv, d))
    v = jax.random.normal(keys[2], (b, t, hkv, d))

    def full(q, k, v):
        kk, vv = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)

    def blockwise(q, k, v):
        return causal_gqa_attention(q, k, v, block_q=block_q,
                                    segments=segments)

    weigh = lambda f: (lambda *a: jnp.sum(  # noqa: E731
        f(*a) * jnp.sin(jnp.arange(b * t * hq * d).reshape(b, t, hq, d))))
    with jax.default_matmul_precision("highest"):
        assert rel(jax.jit(blockwise)(q, k, v), jax.jit(full)(q, k, v)) < 2e-6
        got = jax.jit(jax.grad(weigh(blockwise), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(weigh(full), argnums=(0, 1, 2)))(q, k, v)
    for g_, w_ in zip(got, want):
        assert rel(g_, w_) < 2e-5


def gqa_inputs(t, hq, hkv, d, seed=0):
    keys = jax.random.split(jax.random.key(seed + t + hq), 4)
    shape = lambda h: (2, t, h, d)  # noqa: E731
    return tuple(jax.random.normal(key, shape(h))
                 for key, h in zip(keys, (hq, hkv, hkv, hq)))


def full_gqa_attention(q, k, v):
    """``mha_reference`` on K/V repeated for their groups."""
    from sparknet_tpu.ops.attention import mha_reference

    group = q.shape[2] // k.shape[2]
    return mha_reference(q, jnp.repeat(k, group, axis=2),
                         jnp.repeat(v, group, axis=2), causal=True)


# float32: the XLA path's bounds above; bfloat16: rounding of q, k, v, p and
# the cotangents, a twentieth of the cell's forward band at most
ATTENTION_BOUNDS = {"float32": (2e-6, 2e-5), "bfloat16": (6e-3, 1e-2)}


# the kernels in interpreter mode, several blocks a side: groups of 1 and 8,
# heads of 128 and 256, whole blocks and a ragged tail (T = 50: the last
# query block and the last key block are padded), query blocks smaller than,
# equal to and larger than the key blocks
@pytest.mark.parametrize("dtype", sorted(ATTENTION_BOUNDS))
@pytest.mark.parametrize("t, hq, hkv, d, block_q, block_k", [
    (64, 8, 1, 128, 16, 32),
    (50, 8, 1, 128, 16, 32),
    (64, 2, 2, 256, 32, 16),
    (50, 16, 2, 256, 16, 16),
    (64, 1, 1, 128, 16, 16),
])
def test_attention_kernels_match_full_attention(
        t, hq, hkv, d, block_q, block_k, dtype):
    """Output and the three gradients of the flash kernels as
    ``causal_gqa_attention`` calls them, against full attention on repeated
    K/V."""
    from sparknet_tpu.ops import pallas_attention

    cd = jnp.dtype(dtype)
    q, k, v, weights = gqa_inputs(t, hq, hkv, d)

    def kernels(q, k, v):
        q = (q * d ** -0.5).astype(cd)
        return pallas_attention.flash_attention(
            q, k.astype(cd), v.astype(cd), causal=True, block_q=block_q,
            block_k=block_k, scale=1.0, out_dtype=jnp.float32)

    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(jax.jit(kernels), q, k, v)
        want, want_vjp = jax.vjp(jax.jit(full_gqa_attention), q, k, v)
        grads, want_grads = vjp(weights), want_vjp(weights)
    out_tol, grad_tol = ATTENTION_BOUNDS[dtype]
    assert got.dtype == jnp.float32 and got.shape == q.shape
    assert rel(got, want) < out_tol
    for name, g_, w_ in zip("qkv", grads, want_grads):
        assert g_.shape == w_.shape and rel(g_, w_) < grad_tol, name
    with open(os.path.join(os.path.dirname(ref.__file__), os.pardir,
                           "configs", "qwen3-next-80b-a3b.json")) as f:
        band = json.load(f)["check"]["forward_rel_tol"]["logits"]
    assert out_tol < band and grad_tol < band


@pytest.mark.parametrize("tq, tk, block_q, block_k", [
    (64, 64, 16, 16), (64, 64, 16, 32), (64, 64, 32, 16), (50, 50, 16, 32),
    (16, 64, 16, 16), (8192, 8192, 512, 512), (8192, 8192, 256, 512)])
def test_causal_attention_meets_the_blocks_below_the_diagonal(
        tq, tk, block_q, block_k):
    """``blocks_met`` (the kernels' own predicate) against the mask itself:
    a block is met iff some key of it is visible to some query of it."""
    from sparknet_tpu.ops import pallas_attention

    nq, nk = -(-tq // block_q), -(-tk // block_k)
    query = (tk - tq) + np.arange(nq * block_q)
    visible = np.arange(nk * block_k)[None, :] <= query[:, None]
    by_block = visible.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))
    computed, total = pallas_attention.blocks_met(tq, tk, block_q, block_k)
    assert (computed, total) == (int(by_block.sum()), nq * nk)
    if tq == tk and block_q == block_k and tq % block_q == 0:
        assert computed == nq * (nq + 1) // 2


@pytest.mark.parametrize("lowers, d, dtype, path, why", [
    (False, 128, "bfloat16", "xla", "no Pallas lowering on cpu"),
    (True, 16, "bfloat16", "xla", "whole lanes"),
    (True, 128, "float16", "xla", "bfloat16 or float32"),
    (True, 128, "bfloat16", "pallas", ""),
    (True, 256, "float32", "pallas", ""),
])
def test_attention_selects_by_backend_shape_and_dtype(
        monkeypatch, lowers, d, dtype, path, why):
    """No switch: the backend, the shapes and the dtype decide, and each
    trace says so in one ``obs`` instant, with the blocks it computes: at
    ``n`` key blocks ``n (n + 1) / 2`` of ``n^2`` in the kernels, 5/8 of the
    matrix in the XLA path's four runs."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer
    from sparknet_tpu.ops import attention, pallas_attention

    monkeypatch.setattr(attention, "lowerable", lambda: lowers)
    calls = []
    real = pallas_attention.flash_attention
    monkeypatch.setattr(
        pallas_attention, "flash_attention",
        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    t, hq, hkv = 8192, 8, 2
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, t, h, d), jnp.float32)
    tracer = obs.install_tracer(Tracer())
    try:  # traced, not run: the instant is the trace's
        o = jax.eval_shape(
            lambda q, k, v: causal_gqa_attention(
                q, k, v, compute_dtype=jnp.dtype(dtype)),
            shape(hq), shape(hkv), shape(hkv))
    finally:
        obs.uninstall_tracer()
    assert (o.shape, o.dtype) == ((1, t, hq, d), jnp.float32)
    assert len(calls) == (path == "pallas")
    events = [e for e in tracer.events() if e["name"] == "attention_path"]
    assert len(events) == 1  # one a trace
    args = events[0]["args"]
    assert args["path"] == path and why in args["why"]
    assert (args["why"] == "") == (path == "pallas")
    assert (args["backend"], args["t"], args["hq"], args["hkv"], args["d"],
            args["dtype"]) == ("cpu", t, hq, hkv, d, dtype)
    n = t // args["block_k"]
    # dk/dv over 8,192 keys of 128 or 256: 8 or 16 MiB, in the budget
    assert args["backward"] == ("fused" if path == "pallas" else "xla")
    assert args["backward_why"] == ""
    if path == "pallas":
        assert args["block_k"] == 512 and calls[0]["block_k"] == 512
        assert calls[0]["block_q"] == args["block_q"]
        # a MiB of queries (4 heads' rows of d in the dtype), a block of
        # keys at most
        rows = (1 << 20) // (4 * d * jnp.dtype(dtype).itemsize)
        assert args["block_q"] == min(rows, 512)
        met = n * (n + 1) // 2 * (args["block_k"] // args["block_q"])
        assert args["blocks_computed"] == met
        assert args["blocks_computed"] / args["blocks_total"] == (n + 1) / (2 * n)
    else:
        assert args["block_q"] == args["block_k"] == 512
        assert args["blocks_computed"] / args["blocks_total"] == 5 / 8


def test_selected_kernels_match_the_xla_path(monkeypatch):
    """``causal_gqa_attention`` through the kernels (interpreter mode)
    against itself through the XLA path, the oracle: output and gradients,
    in bfloat16 as the cell computes."""
    from sparknet_tpu.ops import attention

    q, k, v, weights = gqa_inputs(40, 4, 2, 128)
    run = lambda: jax.vjp(  # noqa: E731
        lambda *a: causal_gqa_attention(*a, compute_dtype=jnp.bfloat16),
        q, k, v)
    want, want_vjp = run()
    monkeypatch.setattr(attention, "lowerable", lambda: True)
    got, vjp = run()
    assert rel(got, want) < 6e-3
    for g_, w_ in zip(vjp(weights), want_vjp(weights)):
        assert rel(g_, w_) < 1e-2


@pytest.mark.parametrize("keeps, forwards", [(False, 2), (True, 1)])
def test_mixer_recomputation_keeps_what_the_flash_kernels_name(
        monkeypatch, keeps, forwards):
    """Under the mixers' ``jax.checkpoint`` policy the kernels' ``o`` and
    ``lse`` are kept and the backward pass holds the forward kernel once,
    not twice (traced, not run); the one backward kernel once either way."""
    from sparknet_tpu.models.hybrid_lm import MIXER_KEEPS
    from sparknet_tpu.ops import attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    q, k, v, _ = gqa_inputs(64, 4, 2, 128)

    def mixer(q, k, v):  # work before and after, as a layer has
        return 3.0 * causal_gqa_attention(
            2.0 * q, k, v, compute_dtype=jnp.bfloat16)

    kept = jax.checkpoint(mixer, policy=MIXER_KEEPS if keeps else None)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kept(*a)), argnums=(0, 1, 2)))(q, k, v))
    count = lambda name: jaxpr.count(f"name={name}")  # noqa: E731
    assert count("flash_attention_forward") == forwards
    assert count("flash_attention_backward") == 1
    assert count("flash_attention_dq") == count("flash_attention_dkv") == 0


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from pallas_calls(inner)


BF16, FP32 = "bfloat16", "float32"
# name -> (grid, operands after the (2,) int32 offsets, scratch), an array
# as (shape, dtype); B = 2 sequences of 8,192 tokens in bfloat16
UNMASKED_CALLS = {
    # qwen3next-train-8k: 16 query heads on 2 K/V heads of 256, read in place
    (16, 2, 256): {
        "flash_attention_forward": (
            (2, 2, 32, 16),
            [((2, 8192, 4096), BF16)] + [((2, 8192, 512), BF16)] * 2,
            [((2048, 1), FP32)] * 2 + [((2048, 256), FP32)]),
        "flash_attention_backward": (
            (2, 2, 32, 16),
            [((2, 8192, 4096), BF16)] + [((2, 8192, 512), BF16)] * 2
            + [((2, 8192, 4096), FP32)] * 2 + [((2, 2, 8, 8192), FP32)] * 2,
            [((2048, 256), BF16)] * 2 + [((1, 2048), FP32)] * 2
            + [((256, 2048), FP32)] + [((8192, 256), FP32)] * 2),
    },
    # lfm2moe-train-8k: 32 query heads on 8 K/V heads of 64, heads-first
    (32, 8, 64): {
        "flash_attention_forward": (
            (16, 1, 16, 16),
            [((16, 8192, 256), BF16)] + [((16, 8192, 64), BF16)] * 2,
            [((2048, 1), FP32)] * 2 + [((2048, 64), FP32)]),
        "flash_attention_backward": (
            (16, 1, 16, 16),
            [((16, 8192, 256), BF16)] + [((16, 8192, 64), BF16)] * 2
            + [((16, 8192, 256), FP32)] * 2 + [((16, 1, 4, 8192), FP32)] * 2,
            [((2048, 64), BF16)] * 2 + [((1, 2048), FP32)] * 2
            + [((64, 2048), FP32)] + [((8192, 64), FP32)] * 2),
    },
}


@pytest.mark.parametrize("hq, hkv, d", sorted(UNMASKED_CALLS))
def test_without_a_keep_mask_the_kernels_calls_are_pinned(
        monkeypatch, hq, hkv, d):
    """The two ``pallas_call``s of ``causal_gqa_attention``'s forward +
    backward at the two sequence cells' head shapes: grid, operands and
    scratch (the forward's as before the kernels learned to take a
    keep-mask; the one-pass backward's: the query block's rows and scalars,
    dq transposed, and the K/V head's dk and dv over every key).
    ``qwen3next-train-8k``'s order of operations hangs on 50 MB of XLA's
    own memory estimate (PERF.md section 6): an operand added to the unmasked
    kernels has to fail here, not in a cell."""
    from sparknet_tpu.ops import attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8192, h, d), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(causal_gqa_attention(
            *a, compute_dtype=jnp.bfloat16)), argnums=(0, 1, 2)))(
                shape(hq), shape(hkv), shape(hkv))
    avals = lambda vs: [(v.aval.shape, v.aval.dtype.name) for v in vs]  # noqa: E731
    got = {}
    for eqn in pallas_calls(jaxpr.jaxpr):
        mapping = eqn.params["grid_mapping"]
        assert mapping.num_index_operands == 1
        assert avals(eqn.invars[:1]) == [((2,), "int32")]
        name = eqn.params["name"]
        assert name not in got  # each kernel once
        got[name] = (
            tuple(mapping.grid), avals(eqn.invars[1:]),
            avals(eqn.params["jaxpr"].invars[-mapping.num_scratch_operands:]))
    assert got == UNMASKED_CALLS[hq, hkv, d]


def test_lm_app_trains_the_hybrid_model_from_a_configuration_file(tmp_path):
    """``lm_app --model_config``: the byte corpus through
    ``Solver(net=...)`` with ADAM and ``ParameterAveragingTrainer.round`` on
    two workers, the routing gauges set once before the loop."""
    import json

    from sparknet_tpu import obs
    from sparknet_tpu.apps import lm_app

    config = {**SMALL, "vocab_size": 256, "num_hidden_layers": 4,
              "compute_dtype": "bfloat16"}
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(config))
    rc = lm_app.main([
        "--model_config", str(path), "--workers", "2", "--rounds", "3",
        "--tau", "2", "--batch", "2", "--seq_len", "24", "--log_every", "1",
        "--obs", "--obs_port", "0",
    ])
    assert rc == 0
    tm = obs.training_metrics()
    assert tm is not None and tm.lm_tokens.value == 3 * 2 * 2 * 2 * 24
    per_token = [tm.lm_held_assignments.labels(str(i)).value for i in range(4)]
    skew = [tm.lm_held_load_skew.labels(str(i)).value for i in range(4)]
    used = [tm.lm_grouped_rows_used.labels(str(i)).value for i in range(4)]
    # 8 of 16 experts held, top-4: two assignments a token expected, half the
    # grouped path's rows (all of them here: ROWS_SLACK x 2 x 48 tokens is
    # more than the 4 x 48 a batch can send, so it holds those)
    assert all(0.5 < x < 3.5 for x in per_token) and all(x >= 1 for x in skew)
    assert all(abs(u - x / 4) < 1e-6 for u, x in zip(used, per_token))
    with pytest.raises(SystemExit, match="--sp 1"):
        lm_app.main(["--model_config", str(path), "--sp", "2"])
