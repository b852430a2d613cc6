"""Long-context stack tests: attention layer, blockwise form, ring
attention on the CPU mesh, and the pallas kernel (interpret mode) — all
pinned to the same reference function."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu import config
from sparknet_tpu.net import JaxNet
from sparknet_tpu.ops.attention import blockwise_attention, mha_reference
from sparknet_tpu.ops.pallas_attention import flash_attention
from sparknet_tpu.parallel import make_mesh
from sparknet_tpu.parallel.ring_attention import ring_self_attention

B, T, H, D = 2, 32, 4, 16
F32 = jnp.float32


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    for bs in (8, 11, 32, 64):  # including non-dividing and over-long blocks
        out = blockwise_attention(q, k, v, block_size=bs, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(1)
    fn = ring_self_attention(mesh, "sp", causal=causal)
    out = fn(q, k, v)  # T=32 sharded 8 ways -> 4 per device
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_matches_reference(causal):
    q, k, v = _qkv(2)
    out = flash_attention(q, k, v, causal=causal, block_q=8)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_attention_layer_in_net():
    net_text = """
layer { name: "d" type: "HostData" top: "x"
  java_data_param { shape { dim: 2 dim: 16 dim: 64 } } }
layer { name: "attn" type: "Attention" bottom: "x" top: "y"
  attention_param { num_heads: 4 causal: true block_size: 8 } }
layer { name: "red" type: "Reduction" bottom: "y" top: "loss"
  loss_weight: 1.0 reduction_param { operation: MEAN axis: 0 } }
"""
    net = JaxNet(config.parse_net_prototxt(net_text), phase="TRAIN")
    params, stats = net.init(0)
    assert [tuple(b.shape) for b in params["attn"]] == [
        (64, 192),
        (192,),
        (64, 64),
        (64,),
    ]
    x = np.random.RandomState(0).randn(2, 16, 64).astype(np.float32)
    out = net.apply(params, stats, {"x": x}, rng=jax.random.PRNGKey(0))
    assert out.blobs["y"].shape == (2, 16, 64)
    grads = jax.grad(lambda p: net.loss_fn(p, stats, {"x": x})[0])(params)
    total = sum(float(jnp.sum(jnp.abs(g))) for gs in grads.values() for g in gs)
    assert np.isfinite(total) and total > 0


def test_attention_layer_causality():
    # causal: changing future tokens must not affect earlier outputs
    net_text = """
layer { name: "d" type: "HostData" top: "x"
  java_data_param { shape { dim: 1 dim: 8 dim: 16 } } }
layer { name: "attn" type: "Attention" bottom: "x" top: "y"
  attention_param { num_heads: 2 causal: true } }
"""
    net = JaxNet(config.parse_net_prototxt(net_text), phase="TEST")
    params, stats = net.init(0)
    rng = np.random.RandomState(0)
    x1 = rng.randn(1, 8, 16).astype(np.float32)
    x2 = x1.copy()
    x2[:, 5:] += 100.0  # perturb the future
    y1 = np.asarray(net.forward(params, stats, {"x": x1})["y"])
    y2 = np.asarray(net.forward(params, stats, {"x": x2})["y"])
    np.testing.assert_allclose(y1[:, :5], y2[:, :5], atol=1e-5)
    assert not np.allclose(y1[:, 5:], y2[:, 5:])


def test_ring_attention_long_sequence_grad():
    # gradient flows through the ring (trainability of the sp path)
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(3)

    fn = ring_self_attention(mesh, "sp", causal=True)

    def loss(q):
        return jnp.sum(jnp.square(fn(q, k, v)))

    g = jax.grad(loss)(q)
    ref_g = jax.grad(
        lambda q: jnp.sum(jnp.square(mha_reference(q, k, v, causal=True)))
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), atol=5e-4)


def test_ring_attention_rejects_ragged_sequence():
    # T that doesn't divide over the ring dies up front with the fix
    # spelled out, not deep in the shard_map partitioner
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn = ring_self_attention(mesh, "sp", causal=True)
    rng = np.random.RandomState(0)
    bad = tuple(
        jnp.asarray(rng.randn(2, 30, 4, 16).astype(np.float32))
        for _ in range(3)
    )
    with pytest.raises(ValueError, match="does not divide"):
        fn(*bad)
    # and a non-(B,T,H,D) rank is named too
    q3 = jnp.zeros((2, 32, 4), jnp.float32)
    with pytest.raises(ValueError, match=r"\(B, T, H, D\)"):
        fn(q3, q3, q3)


def test_ring_attention_kv_grads_match_reference():
    # the transposed-ppermute path: gradients w.r.t. K and V flow BACK
    # around the ring (the existing grad test covers q only) — the
    # sp-trained LM depends on all three being exact
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(5)
    fn = ring_self_attention(mesh, "sp", causal=True)
    for wrt in (1, 2):  # k, v
        g = jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a))), argnums=wrt
        )(q, k, v)
        ref = jax.grad(
            lambda *a: jnp.sum(
                jnp.square(mha_reference(*a, causal=True))
            ),
            argnums=wrt,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref), atol=5e-4
        )


# ---------------------------------------------------------------------
# flash backward (custom_vjp): grads pinned against jax.grad of the
# dense reference — the training-step default rides this kernel pair


def _flash_loss(q, k, v, causal, block_q=8):
    out = flash_attention(q, k, v, causal=causal, block_q=block_q)
    return jnp.sum(jnp.square(out.astype(jnp.float32)))


def _dense_loss(q, k, v, causal):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    return jnp.sum(jnp.square(mha_reference(qf, kf, vf, causal=causal)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _qkv(7)
    for wrt in (0, 1, 2):  # dq, dk, dv
        g = jax.grad(_flash_loss, argnums=wrt)(q, k, v, causal)
        ref = jax.grad(_dense_loss, argnums=wrt)(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref), atol=5e-5
        )


@pytest.mark.parametrize("tq,heads", [(5, 4), (13, 3), (29, 2)])
def test_flash_ragged_query_fwd_and_grad(tq, heads):
    """T_q not divisible by block_q auto-pads (mask-correct) instead of
    raising — forward AND backward, odd head counts included."""
    rng = np.random.RandomState(20 + tq)
    q = jnp.asarray(rng.randn(2, tq, heads, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(2, tq, heads, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(2, tq, heads, 16).astype(np.float32))
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, block_q=8)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )
        g = jax.grad(_flash_loss)(q, k, v, causal)
        ref_g = jax.grad(_dense_loss)(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref_g), atol=5e-5
        )


def test_flash_causal_convention_end_aligned():
    """T_q < T_k uses the END-aligned causal convention — row i of the
    query block sits at absolute position (tk - tq) + i, exactly
    ``mha_reference``'s ``tril(k=tk-tq)`` — forward and grads."""
    rng = np.random.RandomState(11)
    tq, tk = 8, 32
    q = jnp.asarray(rng.randn(2, tq, 4, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(2, tk, 4, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(2, tk, 4, 16).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=8)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for wrt in (0, 1, 2):
        g = jax.grad(_flash_loss, argnums=wrt)(q, k, v, True)
        ref_g = jax.grad(_dense_loss, argnums=wrt)(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref_g), atol=5e-5
        )


def test_flash_bf16_within_pinned_tolerance():
    """bf16 inputs: fp32-accumulated kernel stays within the pinned
    band of the fp32 dense reference, forward (4e-2) and grads (6e-2),
    and the output keeps the input dtype."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(12))
    out = flash_attention(q, k, v, causal=True, block_q=8)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=4e-2
    )
    g = jax.grad(_flash_loss)(q, k, v, True)
    assert g.dtype == jnp.bfloat16
    ref_g = jax.grad(_dense_loss)(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(g, np.float32), np.asarray(ref_g), atol=6e-2
    )


def test_flash_rejects_empty_query():
    q = jnp.zeros((2, 0, 4, 16), jnp.float32)
    k, v = (jnp.zeros((2, 8, 4, 16), jnp.float32) for _ in range(2))
    with pytest.raises(ValueError, match="T_q=0"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense_ring(causal):
    """The per-shard flash path inside ring attention (use_flash=True,
    interpret on CPU) matches the einsum ring AND the dense reference —
    forward and q/k/v grads (the sp training path's contract)."""
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(9)
    fn = ring_self_attention(mesh, "sp", causal=causal, use_flash=True)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for wrt in (0, 1, 2):
        g = jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a))), argnums=wrt
        )(q, k, v)
        ref_g = jax.grad(
            lambda *a: jnp.sum(
                jnp.square(mha_reference(*a, causal=causal))
            ),
            argnums=wrt,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref_g), atol=5e-4
        )


def test_flash_jitted_step_zero_post_warmup_recompiles():
    """Sanitizer: the kernel inside a jitted value_and_grad step
    compiles ONCE — repeated same-shape steps with fresh data hit the
    cache (recompiles after warmup == 0)."""

    @jax.jit
    def step(q, k, v):
        return jax.value_and_grad(
            lambda q: _flash_loss(q, k, v, True)
        )(q)

    step(*_qkv(14))  # warmup compile
    warm = step._cache_size()
    assert warm == 1
    for seed in (15, 16, 17):
        loss, g = step(*_qkv(seed))
        assert np.isfinite(float(loss))
        assert np.all(np.isfinite(np.asarray(g)))
    assert step._cache_size() - warm == 0


# ---------------------------------------------------------------------
# the one-pass backward (``flash_attention_backward``, one kernel for dq,
# dk, dv and the rope term's parts) against jax.grad of the dense
# reference given the same keys, and against the dq + dk/dv passes it
# replaces; every case with cotangents on BOTH outputs (the dlse term)

def _masked_reference(q, k, v, keep, scale, rope=None):
    """``mha_reference``'s arithmetic in float32 over the keys ``keep``
    ``(B, Tq, Tk)`` holds, K/V heads repeated for their groups and the rope
    term joined to the heads: ``(o (B, Tq, H, D), lse (B, H, Tq))``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    if rope is not None:
        q = jnp.concatenate([q, rope[0]], -1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            rope[1][:, :, None], (*k.shape[:3], rope[1].shape[-1]))], -1)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    s = jnp.where(keep[:, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v,
                   precision="highest")
    return o, lse


# name -> (query heads, K/V heads, head width, T, query offset, window,
# keep-mask, rope width, causal); blocks of 16 x 16
ONE_PASS_CASES = {
    "grouped heads in place": (4, 2, 128, 32, 0, None, False, 0, True),
    "grouped heads first, ragged T": (4, 2, 16, 24, 0, None, False, 0, True),
    "not causal, ragged T": (2, 2, 16, 24, 0, None, False, 0, False),
    "a window band": (4, 2, 128, 48, 0, 20, False, 0, True),
    "a keep-mask": (4, 2, 128, 32, 0, None, True, 0, True),
    "a rope term": (2, 2, 128, 32, 0, None, False, 64, True),
    "a ring step's offsets": (2, 1, 128, 32, 24, None, False, 0, True),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(ONE_PASS_CASES))
def test_the_one_pass_backward_matches_the_reference_and_the_two_passes(
        monkeypatch, case, dtype):
    from sparknet_tpu.ops import pallas_attention as pa
    from sparknet_tpu.ops.attention import pack_mask, words_of

    hq, hkv, d, t, q_off, window, masked, rope, causal = ONE_PASS_CASES[case]
    cd = jnp.dtype(dtype)
    key = jax.random.key(len(case))
    draw = lambda i, *s: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), s).astype(cd)
    q, k, v = draw(0, 1, t, hq, d), draw(1, 1, t, hkv, d), draw(2, 1, t, hkv, d)
    parts = (draw(3, 1, t, hq, rope), draw(4, 1, t, rope)) if rope else None
    scale = (d + rope) ** -0.5
    query, keys = q_off + np.arange(t)[:, None], np.arange(t)[None, :]
    keep = (keys <= query) if causal else np.ones((t, t), bool)
    if window:
        keep &= keys > query - window
    bits = None
    if masked:  # a scattered selection that keeps the diagonal
        keep &= (np.random.RandomState(3).rand(t, t) < 0.4) | (keys == query)
        bits = pack_mask(jnp.asarray(keep)[None], words_of(t))
    offs = jnp.asarray([q_off, 0], jnp.int32)

    def kernels(q, k, v, *parts):
        o, lse = pa._attend(q, k, v, offs, causal, 16, 16, True, scale, F32,
                            keep=bits, rope=parts or None, window=window)
        return o, lse

    def reference(q, k, v, *parts):
        return _masked_reference(q, k, v, jnp.asarray(keep)[None], scale,
                                 parts or None)

    xs = (q, k, v) + (parts or ())
    do = jax.random.normal(jax.random.fold_in(key, 5), (1, t, hq, d))
    dlse = jax.random.normal(jax.random.fold_in(key, 6), (1, hq, t))

    def grads(fn):  # and the kernels' names
        def both(*xs):
            out, vjp = jax.vjp(fn, *xs)
            return out, vjp((do, dlse))
        with jax.default_matmul_precision("highest"):
            traced = jax.jit(both).trace(*xs)
            return traced.lower().compile()(*xs), str(traced.jaxpr)

    ((o, lse), fused), names = grads(kernels)
    assert "name=flash_attention_backward" in names
    assert "flash_attention_dkv" not in names
    (want_out, want), _ = grads(reference)
    monkeypatch.setattr(pa, "BACKWARD_ACCUMULATOR_BYTES", 0)
    (_, two_pass), names = grads(kernels)
    assert "name=flash_attention_dq" in names
    assert "name=flash_attention_backward" not in names

    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm((a.astype(F32) - b.astype(F32)).ravel())
        / jnp.linalg.norm(b.astype(F32).ravel()))
    exact, close = (1e-6, 2e-5) if cd == jnp.float32 else (2e-3, 2e-2)
    assert rel(o, want_out[0]) < close and rel(lse, want_out[1]) < close
    for g, g2, w in zip(fused, two_pass, want):
        assert g.dtype == w.dtype == cd
        assert rel(g, g2) < exact and rel(g, w) < close


@pytest.mark.parametrize("t, backward", [(16384, "fused"), (32768, "two_pass")])
def test_over_the_vmem_budget_the_backward_takes_two_passes(
        monkeypatch, t, backward):
    """A K/V head's float32 dk and dv over every key, 2 x 128 x 4 bytes a
    key, are 16 MiB at 16,384 keys, the budget: past it the backward is the
    dq and dk/dv passes, and the ``attention_path`` instant says which and
    why (traced, not run)."""
    from sparknet_tpu import obs
    from sparknet_tpu.obs.trace import Tracer
    from sparknet_tpu.ops import attention

    monkeypatch.setattr(attention, "lowerable", lambda: True)
    shape = lambda h: jax.ShapeDtypeStruct((1, t, h, 128), F32)  # noqa: E731
    tracer = obs.install_tracer(Tracer())
    try:
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(attention.causal_gqa_attention(
                *a, compute_dtype=jnp.bfloat16)), argnums=(0, 1, 2)))(
                    shape(8), shape(2), shape(2)))
    finally:
        obs.uninstall_tracer()
    (event,) = [e for e in tracer.events() if e["name"] == "attention_path"]
    args = event["args"]
    assert (args["path"], args["backward"]) == ("pallas", backward)
    assert ("32 MiB" in args["backward_why"]) == (backward == "two_pass")
    assert ("name=flash_attention_backward" in jaxpr) == (backward == "fused")
    assert ("name=flash_attention_dkv" in jaxpr) == (backward == "two_pass")
