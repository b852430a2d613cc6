"""The flash kernels' second score term (``pallas_attention.mla_flash_
attention``: latent attention, scores 192 wide against values 128 wide, one
rotary key for every head) in interpreter mode against the XLA oracle
(``ops/attention._blockwise_gqa`` on the joined heads) and against full
attention written here; what ``accepts`` takes over the four families'
shapes; and the path ``causal_mla_attention`` names at each trace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops import attention, pallas_attention
from sparknet_tpu.ops.attention import causal_mla_attention

# (output, gradients): relative L2 error against float32 full attention
BOUNDS = {"float32": (2e-5, 2e-5), "bfloat16": (2e-2, 3e-2)}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def mla_inputs(t, h, d, r, b=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = {0: (b, t, h, d), 1: (b, t, h, r), 2: (b, t, h, d),
             3: (b, t, 1, r), 4: (b, t, h, d), 5: (b, t, h, d)}
    xs = [jax.random.normal(k, shape[i], jnp.float32)
          for i, k in enumerate(keys)]
    return xs[:5], xs[5]


def full_mla_attention(q_nope, q_rope, k_nope, k_rope, v):
    """The whole masked score matrix, the one rope key repeated a head."""
    d_qk = q_nope.shape[-1] + q_rope.shape[-1]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope[:, :, 0])) * d_qk ** -0.5
    t = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


# several blocks a side, whole and ragged (T = 50), query blocks smaller
# than, equal to and larger than the key blocks; the published ratio 3:2 of
# score to value width at (128 + 64, 128), and a rope part of a whole row of
# lanes
@pytest.mark.parametrize("dtype", sorted(BOUNDS))
@pytest.mark.parametrize("t, h, d, r, block_q, block_k", [
    (64, 2, 128, 64, 16, 32),
    (50, 2, 128, 64, 16, 32),
    (64, 3, 128, 64, 32, 16),
    (64, 1, 128, 128, 16, 16),
])
def test_mla_kernels_match_full_attention(t, h, d, r, block_q, block_k, dtype):
    """Output and the five gradients of the widened kernels as
    ``causal_mla_attention`` calls them; ``dk_rope`` sums over the heads."""
    cd = jnp.dtype(dtype)
    xs, weights = mla_inputs(t, h, d, r)

    def kernels(q_nope, q_rope, k_nope, k_rope, v):
        scale = (d + r) ** -0.5
        return pallas_attention.mla_flash_attention(
            (q_nope * scale).astype(cd), (q_rope * scale).astype(cd),
            k_nope.astype(cd), k_rope[:, :, 0].astype(cd), v.astype(cd),
            block_q=block_q, block_k=block_k, scale=1.0,
            out_dtype=jnp.float32)

    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(jax.jit(kernels), *xs)
        want, want_vjp = jax.vjp(jax.jit(full_mla_attention), *xs)
        grads, want_grads = vjp(weights), want_vjp(weights)
    out_tol, grad_tol = BOUNDS[dtype]
    assert got.dtype == jnp.float32 and got.shape == xs[4].shape  # d_v wide
    assert rel(got, want) < out_tol
    for name, g_, w_ in zip(
            ("q_nope", "q_rope", "k_nope", "k_rope", "v"), grads, want_grads):
        assert g_.shape == w_.shape and rel(g_, w_) < grad_tol, name


@pytest.mark.parametrize("dtype", sorted(BOUNDS))
def test_selected_mla_kernels_match_the_xla_oracle(monkeypatch, dtype):
    """``causal_mla_attention`` through the kernels (interpreter mode)
    against itself through ``_blockwise_gqa``, output and gradients."""
    cd = jnp.dtype(dtype)
    xs, weights = mla_inputs(40, 2, 128, 64, seed=1)
    run = lambda: jax.vjp(  # noqa: E731
        lambda *a: causal_mla_attention(*a, compute_dtype=cd), *xs)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = run()
        monkeypatch.setattr(attention, "lowerable", lambda: True)
        got, vjp = run()
        assert got.dtype == want.dtype == cd  # both paths, as computed
        weights = weights.astype(cd)
        grads, want_grads = vjp(weights), want_vjp(weights)
    out_tol, grad_tol = BOUNDS[dtype]
    assert rel(got, want) < out_tol
    for g_, w_ in zip(grads, want_grads):
        assert g_.shape == w_.shape and rel(g_, w_) < grad_tol


# the four sequence families' attention shapes (query heads, K/V heads, head
# width, rope width, dtype) as their cells run them, and shapes turned away
@pytest.mark.parametrize("hq, hkv, d, rope, dtype, taken", [
    (16, 2, 256, 0, "bfloat16", True),    # qwen3-next: read in place
    (32, 8, 64, 0, "bfloat16", True),     # lfm2: heads-first, 256 lanes
    (32, 4, 128, 0, "bfloat16", True),    # keye-vl-2.0
    (32, 32, 128, 64, "bfloat16", True),  # kanana-2: 192 against 128
    (32, 32, 128, 64, "float32", True),   # and its checks' float32
    (32, 32, 128, 64, "float16", False),
    (32, 8, 128, 64, "bfloat16", False),  # a rope term wants a key a head
    (32, 32, 64, 64, "bfloat16", False),  # and the other part whole lanes
    (32, 32, 128, 192, "bfloat16", False),
    (32, 32, 128, 4, "bfloat16", False),
    (8, 2, 16, 0, "bfloat16", False),
])
def test_accepts_over_the_families_shapes(hq, hkv, d, rope, dtype, taken):
    assert pallas_attention.accepts(hq, hkv, d, jnp.dtype(dtype), rope) is taken
    if not rope:
        assert pallas_attention.accepts(hq, hkv, d, jnp.dtype(dtype)) is taken


@pytest.mark.parametrize("lowers, d, path, why", [
    (False, 128, "xla", "no Pallas lowering on cpu"),
    (True, 64, "xla", "whole lanes"),
    (True, 128, "pallas", ""),
])
def test_mla_attention_names_its_path_and_both_widths(
        monkeypatch, lowers, d, path, why):
    """One ``attention_path`` instant a trace: the path, why, the score's
    width and the value's; the kernels get the one rope key unrepeated and
    the values unpadded."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    monkeypatch.setattr(attention, "lowerable", lambda: lowers)
    calls = []
    real = pallas_attention.mla_flash_attention
    monkeypatch.setattr(
        pallas_attention, "mla_flash_attention",
        lambda *a, **kw: calls.append(a) or real(*a, **kw))
    t, h, r = 8192, 32, 64
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    tracer = obs.install_tracer(Tracer())
    try:  # traced, not run
        o = jax.eval_shape(
            lambda *a: causal_mla_attention(*a, compute_dtype=jnp.bfloat16),
            shape(2, t, h, d), shape(2, t, h, r), shape(2, t, h, d),
            shape(2, t, 1, r), shape(2, t, h, d))
    finally:
        obs.uninstall_tracer()
    assert (o.shape, o.dtype) == ((2, t, h, d), jnp.bfloat16)  # as computed
    (event,) = [e for e in tracer.events() if e["name"] == "attention_path"]
    args = event["args"]
    assert args["path"] == path and why in args["why"]
    assert (args["d_qk"], args["d_v"], args["hq"], args["hkv"]) == (
        d + r, d, h, h)
    assert len(calls) == (path == "pallas")
    assert args["backward"] == ("fused" if path == "pallas" else "xla")
    if path == "pallas":
        # keys x width as the selected-key attention's: 1,024 at D = 128
        assert args["block_q"] == args["block_k"] == 1024
        assert args["blocks_computed"] / args["blocks_total"] == 9 / 16
        q_nope, q_rope, k_nope, k_rope, v = calls[0]
        assert k_rope.shape == (2, t, r)  # one key, no head axis
        assert v.shape == (2, t, h, d) and q_rope.shape == (2, t, h, r)
    else:
        assert args["blocks_computed"] / args["blocks_total"] == 5 / 8


def test_grouped_attention_says_one_width_twice(monkeypatch):
    """``causal_gqa_attention``'s instant carries the two widths too, equal."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer

    shape = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 256, h, 128), jnp.float32)
    tracer = obs.install_tracer(Tracer())
    try:
        jax.eval_shape(attention.causal_gqa_attention,
                       shape(8), shape(2), shape(2))
    finally:
        obs.uninstall_tracer()
    (event,) = [e for e in tracer.events() if e["name"] == "attention_path"]
    assert (event["args"]["d"], event["args"]["d_qk"], event["args"]["d_v"]
            ) == (128, 128, 128)
