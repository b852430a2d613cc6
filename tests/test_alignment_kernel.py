"""The alignment loss's kernel (``ops/pallas_alignment.py``) in interpreter
mode against the XLA form it replaces on the TPU (``ops/sparse_attention.
_alignment_with_gradient``, the CPU's path) and against ``jax.grad`` of the
plain ``alignment_value``: the loss and its gradient to ``qI``, ``w`` and
``kI``, at tiny sizes, with the keep-masks of ``tests/test_keye_vl2.py``.
Float32 differs by summation order (and the ``sum_s p`` identity the kernel
takes for the XLA form's sum); in bfloat16 ``g`` is rounded where it meets the
products, and two of the three gradients are bfloat16 themselves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops import attention, pallas_alignment, pallas_attention
from sparknet_tpu.ops import sparse_attention as sa
from tests.test_keye_vl2 import (
    attention_inputs, indexer_inputs, kernel_masks, path_events, rel)

# the XLA form's bounds (tests/test_keye_vl2.py): the loss, the gradients
LOSS_REL = 1e-6
GRAD_REL = {"float32": 5e-6, "bfloat16": 1e-2}
_MADE = {}


def inputs(kind, t, dtype):
    """The mask and ``alignment_loss``'s six arrays as ``tests/test_keye_vl2.
    alignment_inputs`` makes them (``lse`` of the masked attention, here from
    the dense scores: the same numbers to rounding), made once a module."""
    if (kind, t) not in _MADE:
        _MADE[kind, t] = jax.jit(kernel_masks, static_argnums=(0, 1, 2))(
            kind, 2, t)
    bits = _MADE[kind, t]
    if (kind, t, dtype) not in _MADE:
        _MADE[kind, t, dtype] = jax.jit(_inputs, static_argnums=(0, 1))(
            t, dtype, bits)
    return bits, _MADE[kind, t, dtype]


def _inputs(t, dtype, bits):
    cd = jnp.dtype(dtype)
    q, k, _ = attention_inputs(7, t=t)
    qi, w, ki = indexer_inputs(8, t=t)
    q, k = sa.scaled_queries(q, cd), k.astype(cd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   jnp.repeat(k, 2, 2).astype(jnp.float32),
                   precision="highest")
    keep = sa.unpack_mask(bits, t)[:, None]
    lse = jnp.log(jnp.sum(jnp.where(keep, jnp.exp(s), 0.0), -1))
    return (qi.astype(cd), w * 32 ** -0.5, ki.astype(cd), q, k,
            jnp.swapaxes(lse, 1, 2))


@pytest.mark.parametrize("kind, t, block_k, segments, dtype", [
    # the indexer's selection at T = 37: three query blocks of 16, the last
    # with 11 rows of padding, keys padded to the mask's 64; the XLA form in
    # runs of 2 and 1 blocks
    ("select", 37, 16, 2, "float32"),
    # the first half's rows keep every causal key, the second half's only
    # keys 0..3, which lie in the first of two key blocks of 32; the XLA form
    # in three runs
    ("early", 64, 32, 3, "bfloat16"),
])
def test_the_kernel_matches_the_xla_form_and_autodiff(
        kind, t, block_k, segments, dtype):
    kw = dict(block_q=16, segments=segments)
    bits, args = inputs(kind, t, dtype)
    def three(*a):
        return (pallas_alignment.alignment_gradient(
                    *a, bits, block_q=16, block_k=block_k, interpret=True),
                sa._alignment_with_gradient(*a, bits, 16, segments),
                jax.value_and_grad(lambda *a: sa.alignment_value(
                    *a, bits, **kw), argnums=(0, 1, 2))(*a))

    with jax.default_matmul_precision("highest"):
        got, *wants = jax.jit(three)(*args)
    for want in wants:
        assert float(got[0]) == pytest.approx(float(want[0]), rel=LOSS_REL)
        for g, wg in zip(got[1], want[1]):
            assert g.dtype == wg.dtype and g.shape == wg.shape
            assert np.asarray(wg, np.float32).any()
            assert rel(g, wg) < GRAD_REL[dtype]


def test_each_real_rows_probabilities_sum_to_one():
    """The identity the kernel takes ``sum_s p`` by: the attention's
    head-mean probabilities over a row's kept keys, from the masked pass's
    own log-sum-exp, sum to 1 to float32 rounding on every row that keeps a
    key; every real row does (the selection at T = 37)."""
    t = 37
    bits, (_, _, _, q, k, _) = inputs("select", t, "float32")
    v = attention_inputs(7, t=t)[2].astype(q.dtype)

    @jax.jit
    def sums(q, k, v):
        _, lse = sa.masked_attention(q, k, v, bits, block_q=16, segments=2)
        keep = sa.unpack_mask(bits, t)
        qb, kh = sa._heads_first(q, k, t)
        lb = lse.reshape(2, t, 2, 2).transpose(0, 2, 3, 1)
        p = sa._head_mean_probabilities(qb[0], kh, lb, keep)
        return jnp.any(keep, -1), jnp.sum(p, -1)

    kept, total = sums(q, k, v)
    assert np.asarray(kept).all()
    np.testing.assert_allclose(np.asarray(total), 1.0, atol=1e-6)


@pytest.mark.parametrize("t, dtype, path, why", [
    (4096, "bfloat16", "pallas", ""),
    (16384, "bfloat16", "pallas", ""),
    (4096, "float32", "with_gradient", "bfloat16"),
    (40, "bfloat16", "with_gradient", "T % 4096 == 0"),
    # what a query block holds over 65,536 keys passes the kernel's VMEM
    (65536, "bfloat16", "with_gradient", "within its VMEM"),
])
def test_the_alignment_path_names_the_kernel_and_its_blocks(
        monkeypatch, t, dtype, path, why):
    """Under differentiation the ``alignment_loss_path`` instant says
    ``pallas`` with the kernel's blocks where Pallas lowers and ``accepts``
    takes the shapes, and ``with_gradient`` with the reason where it does
    not (shapes alone are traced: nothing runs)."""
    monkeypatch.setattr(attention, "lowerable", lambda: True)
    cd = jnp.dtype(dtype)
    shape = lambda *s, dt=cd: (  # noqa: E731
        jax.ShapeDtypeStruct((1, t, *s), dt))
    args = (shape(16, 64), shape(16, dt=jnp.float32), shape(64),
            shape(32, 128), shape(4, 128), shape(32, dt=jnp.float32),
            shape(sa.words_of(t), dt=jnp.uint32))
    loss = lambda *a: sa.alignment_loss(*a)  # noqa: E731
    (event,) = path_events(jax.grad(loss, argnums=(0, 1, 2)), *args,
                           name="alignment_loss_path")
    assert event["path"] == path and why in event["why"]
    assert bool(event["why"]) == (path != "pallas")
    assert event["ds_dtype"] == cd.name
    if path == "pallas":  # the flash kernels' walk in blocks of 512 x 512
        assert (event["block_q"], event["block_k"]) == (512, 512)
        assert (event["blocks_computed"], event["blocks_total"]) == (
            pallas_attention.blocks_met(t, t, 512, 512))
    if t == 16384:  # 528 of 1,024 where the XLA form's eight runs meet 576
        assert event["blocks_computed"] == 528
