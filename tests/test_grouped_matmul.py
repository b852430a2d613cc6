"""The held experts' grouped-product kernels (``ops/pallas_grouped_matmul.py``)
in interpreter mode against ``jax.lax.ragged_dot``, and ``moe.held_experts``
on the kernels against ``held_experts`` on ``ragged_dot``, on the CPU at
small widths of whole lanes.

The kernels and ``ragged_dot`` multiply the same bfloat16 operands into
float32 sums; what differs is the order of a sum of 128-256 products, and in
the backward one rounding of each cotangent to bfloat16 (both round it: the
kernels before their products, ``ragged_dot``'s transpose after).  So the
forward is held to 1e-6, the cotangents to a bfloat16 rounding; the rows past
the groups' sum, and an empty group's gradient, to exactly zero.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.models.hybrid_lm import HybridMoELM
from sparknet_tpu.ops import moe
from sparknet_tpu.ops import pallas_grouped_matmul as pgm

BF16 = jnp.bfloat16
ROWS = 96  # three row tiles of 32 (``pgm._row_block``)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def operands(k, n, groups, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (ROWS, k)).astype(BF16),
            jax.random.normal(keys[1], (groups, k, n)).astype(BF16),
            jax.random.normal(keys[2], (ROWS, n)).astype(BF16))


def ragged(lhs, rhs, sizes):
    """``ragged_dot`` as the grouped path used it alone: the rows past the
    total in the last group, then masked."""
    total = jnp.sum(sizes)
    padded = sizes.at[-1].add(lhs.shape[0] - total)
    out = jax.lax.ragged_dot(lhs, rhs, padded,
                             preferred_element_type=jnp.float32)
    return jnp.where((jnp.arange(lhs.shape[0]) < total)[:, None], out, 0.0)


@pytest.mark.parametrize("sizes", [
    (20, 0, 33, 10),   # an empty group; total 63, not whole tiles of 32
    (1, 1, 40, 1),     # groups of one row
    (30, 2, 60, 4),    # total == rows
    (0, 0, 0, 0),      # total 0: every tile writes zeros
    (0, 0, 50, 0),     # one group, straddling two tiles
    (32, 32, 0, 32),   # boundaries on the tiles' edges
], ids=["empty-group", "one-row", "full", "none", "one-group", "aligned"])
@pytest.mark.parametrize("width_block", [1024, 128],
                         ids=["whole-widths", "width-tiles"])
def test_kernels_match_ragged_dot(monkeypatch, sizes, width_block):
    """Forward, ``dlhs`` and ``drhs`` against ``ragged_dot`` and its
    transpose; rows past the total come out zero and so do their
    gradients.  ``width_block`` 128 walks K and N in two tiles each."""
    monkeypatch.setattr(pgm, "BLOCK_OUT", width_block)
    monkeypatch.setattr(pgm, "BLOCK_KN", width_block)
    sizes = jnp.array(sizes, jnp.int32)
    total = int(sizes.sum())
    lhs, rhs, dout = operands(256, 256, 4)

    out, vjp = jax.vjp(lambda a, b: pgm.grouped_matmul(
        a, b, sizes, interpret=True), lhs, rhs)
    dlhs, drhs = vjp(dout.astype(jnp.float32))
    want, want_vjp = jax.vjp(lambda a, b: ragged(a, b, sizes), lhs, rhs)
    want_dlhs, want_drhs = want_vjp(dout.astype(jnp.float32))

    assert out.dtype == jnp.float32
    assert dlhs.dtype == drhs.dtype == BF16
    assert rel(out, want) < 1e-6
    assert rel(dlhs, want_dlhs) < 4e-3 and rel(drhs, want_drhs) < 4e-3
    assert not np.any(np.asarray(out[total:]))
    assert not np.any(np.asarray(dlhs[total:], np.float32))
    for g in np.flatnonzero(np.asarray(sizes) == 0):
        assert not np.any(np.asarray(drhs[g], np.float32))


def test_accepts_only_bfloat16_of_whole_lanes():
    assert pgm.accepts(16384, 2048, 768, BF16)
    assert not pgm.accepts(16384, 2048, 768, jnp.float32)
    assert not pgm.accepts(16384, 2048, 96, BF16)
    assert not pgm.accepts(100, 2048, 768, BF16)


def skewed(seed, n_tok, e, f, experts, n, hot, cold):
    """Tokens whose first coordinate sends most of them to ``hot`` and none
    to ``cold``, and the held experts' weights."""
    keys = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(keys[0], (n_tok, e)).at[:, 0].set(1.0)
    router = 0.01 * jax.random.normal(keys[1], (e, experts))
    router = router.at[0, hot].set(50.0).at[0, cold].set(-50.0)
    gate, up = (0.3 * jax.random.normal(k, (n, e, f)) for k in keys[2:4])
    down = 0.3 * jax.random.normal(keys[4], (n, f, e))
    return x, router, gate, up, down


@pytest.mark.parametrize("slack", [2.0, 0.05], ids=["fast", "chunked"])
def test_held_experts_on_the_kernels_match_ragged_dot(monkeypatch, slack):
    """``held_experts`` in bfloat16 with the kernels forced on (interpreter
    mode) against the same call on ``ragged_dot``: in the fast branch, and
    with the counts forced over the rows, where the chunked branch runs
    ``ragged_dot`` on both sides."""
    n_tok, e, f, experts, top_k, lo, n = 128, 128, 128, 16, 4, 4, 8
    args = skewed(1, n_tok, e, f, experts, n, hot=6, cold=9)

    def program(x, router, gate, up, down):
        weights, ids = moe.route(x, router, top_k)
        order, counts = moe.plan(ids, lo, n)
        rows = moe.fast_rows_for(n_tok, top_k, experts, n, slack=slack,
                                 multiple=16)
        return moe.held_experts(x, weights, ids, order, counts, gate, up,
                                down, lo=lo, fast_rows=rows,
                                compute_dtype=BF16), counts

    def run():
        out, counts = jax.jit(program)(*args)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(program(*a)[0] ** 2),
                                 argnums=(0, 2, 3, 4)))(*args)
        return out, counts, grads

    want, counts, want_grads = run()
    monkeypatch.setattr(moe, "lowerable", lambda: True)
    got, _, grads = run()
    rows = moe.fast_rows_for(n_tok, top_k, experts, n, slack=slack,
                             multiple=16)
    # which branch ran; the fast one with rows past the total to skip
    assert (int(np.sum(counts)) < rows) == (slack > 1)
    assert rel(got, want) < 4e-3
    for g, w in zip(grads, want_grads):
        assert rel(g, w) < 1e-2


def test_a_model_traces_each_kernel_once_per_shape(monkeypatch):
    """The loss and gradients of a 4-layer ``HybridMoELM`` (bfloat16, every
    layer routed, the kernels forced on in interpreter mode) and then its
    forward trace each kernel body ONCE per distinct shape: three kernels at
    two shapes (gate and up share one), whatever the depth, the ``lax.cond``
    branches and the recomputation under ``jax.checkpoint``."""
    config = {
        "vocab_size": 128, "hidden_size": 128, "num_hidden_layers": 4,
        "full_attention_interval": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 64,
        "partial_rotary_factor": 0.25,
        "rope_theta": 10000000, "rms_norm_eps": 1e-6,
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "num_experts": 16,
        "num_experts_per_tok": 4, "moe_intermediate_size": 256,
        "shared_expert_intermediate_size": 128, "norm_topk_prob": True,
        "experts_held": [4, 4], "compute_dtype": "bfloat16",
    }
    monkeypatch.setattr(moe, "lowerable", lambda: True)
    for kernel in (pgm._forward, pgm._dlhs, pgm._drhs):
        kernel.clear_cache()
    before = pgm.TRACES.copy()
    lm = HybridMoELM(config)
    params, stats = lm.init(0)
    tokens = jnp.zeros((2, 128), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    jax.jit(jax.value_and_grad(lambda p: lm.loss_fn(p, stats, batch)[0])
            ).lower(params)
    jax.jit(lambda p: lm.forward_logits(p, tokens, stats)).lower(params)
    traced = pgm.TRACES - before
    assert sorted((name, lhs) for name, lhs, _ in traced) == [
        ("grouped_matmul", (512, 128)), ("grouped_matmul", (512, 256)),
        ("grouped_matmul_dlhs", (512, 128)),
        ("grouped_matmul_dlhs", (512, 256)),
        ("grouped_matmul_drhs", (512, 128)),
        ("grouped_matmul_drhs", (512, 256))]
    assert set(traced.values()) == {1}


@pytest.mark.parametrize("lowers, dtype, rows, path", [
    (False, "bfloat16", 256, "ragged_dot"),  # the CPU
    (True, "float32", 256, "ragged_dot"),    # the float32 checks
    (True, "bfloat16", 200, "ragged_dot"),   # rows not whole tiles
    (True, "bfloat16", 256, "pallas"),
])
def test_the_grouped_path_is_named_at_each_trace(monkeypatch, lowers, dtype,
                                                 rows, path):
    """``grouped_matmul_path``: one instant a trace of ``held_experts``, its
    ``path`` the one taken (``pallas`` calls the kernels), its ``why`` empty
    exactly where the kernels run."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    monkeypatch.setattr(moe, "lowerable", lambda: lowers)
    calls = []
    real = pgm.grouped_matmul
    monkeypatch.setattr(pgm, "grouped_matmul",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    n_tok, e, f, experts, top_k, n = 128, 128, 256, 16, 4, 8
    cd = jnp.dtype(dtype)
    def shape(*s, dt=jnp.float32):
        return jax.ShapeDtypeStruct(s, dt)

    tracer = obs.install_tracer(Tracer())
    try:  # traced, not run: the instant is the trace's
        jax.eval_shape(
            lambda x, w, ids, order, counts, gate, up, down: moe.held_experts(
                x, w, ids, order, counts, gate, up, down, lo=0,
                fast_rows=rows, compute_dtype=cd),
            shape(n_tok, e, dt=cd), shape(n_tok, top_k),
            shape(n_tok, top_k, dt=jnp.int32),
            shape(n_tok * top_k, dt=jnp.int32), shape(n, dt=jnp.int32),
            shape(n, e, f), shape(n, e, f), shape(n, f, e))
    finally:
        obs.uninstall_tracer()
    events = [ev for ev in tracer.events()
              if ev["name"] == "grouped_matmul_path"]
    assert len(events) == 1
    args = events[0]["args"]
    assert (args["path"], args["rows"], args["dtype"]) == (path, rows, dtype)
    assert (args["why"] == "") == (path == "pallas") == bool(calls)
