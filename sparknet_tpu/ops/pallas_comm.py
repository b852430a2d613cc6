"""Fused averaging-epilogue kernels in Pallas — the comm plane's
per-round hot path as single-pass programs.

``parallel/comm.py`` runs three epilogue programs per averaging round:
delta-encode (momentum-advanced params minus anchor, plus the
error-feedback residual, quantized per tensor with the new residual
written back), and one of two applies (barriered consensus overwrite,
or the overlap correction ``mean - dequant(own)`` onto params AND
anchor).  Unfused, each is a chain of separate XLA ops that round-trips
the full-model delta / correction through HBM between every step.  The
kernels here do each program as ONE ``pallas_call`` per comm chunk:
grid over the worker dim, every leaf of the chunk rides in as its own
ref (no packing copies), and a static Python loop inside the cell walks
the leaves — read x/a/r once, write q/scale/residual once.

Numerical contract (pinned by ``tests/test_pallas_comm.py``): the fused
kernels are BIT-IDENTICAL to the unfused closures in interpret mode — same op order per element
(delta = (x - a) + r; amax/127 int8 grid with rint+clip; bf16 cast;
err = delta - dequant), so the compress=none/fp32 legs match the
unfused trainer exactly and the compressed legs inherit
``comm.LOSS_BAND`` unchanged.

Routing mirrors every other kernel in ``ops/``: native where
``pallas_attention.lowerable()`` holds, interpreter mode as the
explicit test/bench tool, unfused XLA closures elsewhere (the
``CommPlane(fused=...)`` knob).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops.pallas_attention import VMEM_LIMIT_BYTES, lowerable


_LANES = 128

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _resolve_interpret(interpret):
    if interpret is None:
        return not lowerable()
    return bool(interpret)


def _call(kernel, w, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel,
        grid=(w,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # "arbitrary": the per-worker scalar outputs share one SMEM
        # array across the grid.  A chunk rides whole through VMEM —
        # every leaf's inputs and outputs, double-buffered — so one
        # beyond roughly a tenth of the limit per worker is refused by
        # the compiler, loudly.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_resolve_interpret(interpret),
    )


def _rows(shape):
    """(rows, cols) view of one worker's leaf: lane-dense when the
    element count allows, a single row otherwise.  Every op on a leaf
    is elementwise or a whole-leaf reduction, so the view is free to
    differ from the leaf's own shape."""
    n = math.prod(shape)
    return (n // _LANES, _LANES) if n % _LANES == 0 else (1, n)


def _stacked(leaf):
    """Worker-stacked (W, ...) leaf as (W, rows, cols)."""
    return leaf.reshape((leaf.shape[0],) + _rows(leaf.shape[1:]))


def _leaf_block(leaf3):
    """One worker's (rows, cols) slice per grid cell.  The block's last
    two dims equal the array's, which Mosaic accepts at any size."""
    return pl.BlockSpec((1,) + leaf3.shape[1:], lambda i: (i, 0, 0))


def _whole_block(arr2):
    """Every cell reads the same unstacked (rows, cols) array (a chunk
    mean)."""
    return pl.BlockSpec(arr2.shape, lambda i: (0, 0))


def _quantize(delta, mode):
    """One leaf's per-tensor quantize — the EXACT op order of the
    unfused ``encode_fn`` (bitwise identity is the contract)."""
    if mode == "bf16":
        q = delta.astype(jnp.bfloat16)
        return q, jnp.float32(0.0), q.astype(jnp.float32)
    if mode == "int8":
        amax = jnp.max(jnp.abs(delta))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.rint(delta / scale), -127, 127).astype(jnp.int8)
        return q, scale, q.astype(jnp.float32) * scale
    return delta, jnp.float32(0.0), delta  # fp32 / none


def _encode_kernel(*refs, modes, with_err):
    n = len(modes)
    xs, anchors, resids = refs[0:n], refs[n:2 * n], refs[2 * n:3 * n]
    qs, new_resids = refs[3 * n:4 * n], refs[4 * n:5 * n]
    scales_ref = refs[5 * n]  # SMEM (W, n)
    err_ref = refs[5 * n + 1] if with_err else None  # SMEM (W, 3)
    w = pl.program_id(0)
    max_abs = jnp.float32(0.0)
    delta_sq = jnp.float32(0.0)
    err_sq = jnp.float32(0.0)
    for j, (x_ref, a_ref, r_ref, q_ref, nr_ref, mode) in enumerate(zip(
        xs, anchors, resids, qs, new_resids, modes
    )):
        delta = (x_ref[0] - a_ref[0]) + r_ref[0]
        q, scale, dq = _quantize(delta, mode)
        err = delta - dq
        q_ref[0] = q
        scales_ref[w, j] = scale
        nr_ref[0] = err
        if with_err:
            max_abs = jnp.maximum(max_abs, jnp.max(jnp.abs(err)))
            err_sq = err_sq + jnp.sum(jnp.square(err))
            delta_sq = delta_sq + jnp.sum(jnp.square(delta))
    if with_err:
        err_ref[w, 0] = max_abs
        err_ref[w, 1] = delta_sq
        err_ref[w, 2] = err_sq


@partial(jax.jit, static_argnums=(3, 4, 5))
def fused_encode(leaves, anchors, resids, modes, with_err, interpret):
    """One-pass momentum-delta encode of a comm chunk.

    ``leaves``/``anchors``/``resids``: tuples of worker-stacked (W, ...)
    arrays; ``modes``: matching static tuple from ``COMPRESS_MODES``.
    Returns ``(qs, scales, new_resids, err)`` with per-leaf ``scales``
    shaped (W,) (f32; 0 outside int8, matching the unfused closure) and
    ``err`` the (W, 3) per-worker [max_abs, delta_sq, err_sq] readout
    partials (None unless ``with_err``) — delta, quantize, and the
    error-feedback residual all written in the SAME kernel pass."""
    w = leaves[0].shape[0]
    n = len(leaves)
    modes = tuple(modes)
    kernel = partial(_encode_kernel, modes=modes, with_err=with_err)
    xs = [_stacked(x) for x in leaves]
    ins = xs + [_stacked(a) for a in anchors] + [_stacked(r) for r in resids]
    qdt = {"bf16": jnp.bfloat16, "int8": jnp.int8}
    out_specs = [_leaf_block(x) for x in xs] * 2 + [_SMEM]
    out_shape = (
        [
            jax.ShapeDtypeStruct(x.shape, qdt.get(m, x.dtype))
            for x, m in zip(xs, modes)
        ]
        + [jax.ShapeDtypeStruct(x.shape, r.dtype)
           for x, r in zip(xs, resids)]
        + [jax.ShapeDtypeStruct((w, n), jnp.float32)]
    )
    if with_err:
        out_specs.append(_SMEM)
        out_shape.append(jax.ShapeDtypeStruct((w, 3), jnp.float32))
    outs = _call(
        kernel, w, [_leaf_block(x) for x in ins], out_specs, out_shape,
        interpret,
    )(*ins)
    qs = tuple(q.reshape(x.shape) for q, x in zip(outs[0:n], leaves))
    new_resids = tuple(
        r.reshape(x.shape) for r, x in zip(outs[n:2 * n], leaves)
    )
    scales = tuple(outs[2 * n][:, j] for j in range(n))
    err = outs[2 * n + 1] if with_err else None
    return qs, scales, new_resids, err


def _apply_barriered_kernel(*refs, nleaves):
    n = nleaves
    alive_ref, denom0_ref = refs[0], refs[1]  # SMEM (W,), (1,)
    xs = refs[2:2 + n]
    anchors = refs[2 + n:2 + 2 * n]
    means = refs[2 + 2 * n:2 + 3 * n]
    resids = refs[2 + 3 * n:2 + 4 * n]
    new_xs = refs[2 + 4 * n:2 + 5 * n]
    new_rs = refs[2 + 5 * n:2 + 6 * n]
    have = denom0_ref[0] > 0
    rejoin = jnp.logical_and(alive_ref[pl.program_id(0)] <= 0, have)
    for x_ref, a_ref, m_ref, r_ref, nx_ref, nr_ref in zip(
        xs, anchors, means, resids, new_xs, new_rs
    ):
        x = x_ref[0]
        m = m_ref[...]
        r = r_ref[0]
        nx_ref[0] = jnp.where(have, a_ref[0] + m, x)
        nr_ref[0] = jnp.where(rejoin, jnp.zeros_like(r), r)


@partial(jax.jit, static_argnums=(6,))
def fused_apply_barriered(leaves, anchors, means, resids, alive, denom0,
                          interpret):
    """One-pass barriered consensus apply of a comm chunk: every
    worker lands on ``anchor + mean`` (when any worker survived), a
    masked worker's error-feedback residual resets on rejoin — the
    unfused ``apply_barriered_fn`` semantics, bit-identical, one
    kernel.  ``means`` are the unstacked chunk means; ``alive`` (W,),
    ``denom0`` scalar."""
    w = leaves[0].shape[0]
    n = len(leaves)
    kernel = partial(_apply_barriered_kernel, nleaves=n)
    xs = [_stacked(x) for x in leaves]
    ms = [m.reshape(_rows(m.shape)) for m in means]
    stacked = xs + [_stacked(a) for a in anchors]
    rs = [_stacked(r) for r in resids]
    in_specs = (
        [_SMEM, _SMEM]
        + [_leaf_block(x) for x in stacked]
        + [_whole_block(m) for m in ms]
        + [_leaf_block(r) for r in rs]
    )
    outs = _call(
        kernel, w, in_specs,
        [_leaf_block(x) for x in xs + rs],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs + rs],
        interpret,
    )(
        alive.astype(jnp.float32).reshape(w),
        jnp.asarray(denom0, jnp.float32).reshape(1),
        *stacked, *ms, *rs,
    )
    unview = [o.reshape(x.shape) for o, x in zip(outs, leaves + resids)]
    return tuple(unview[0:n]), tuple(unview[n:2 * n])


def _apply_correction_kernel(*refs, modes):
    n = len(modes)
    scales_ref = refs[0]  # SMEM (W, n)
    xs = refs[1:1 + n]
    anchors = refs[1 + n:1 + 2 * n]
    qs = refs[1 + 2 * n:1 + 3 * n]
    means = refs[1 + 3 * n:1 + 4 * n]
    new_xs = refs[1 + 4 * n:1 + 5 * n]
    new_as = refs[1 + 5 * n:1 + 6 * n]
    w = pl.program_id(0)
    for j, (x_ref, a_ref, q_ref, m_ref, nx_ref, na_ref, mode) in enumerate(
        zip(xs, anchors, qs, means, new_xs, new_as, modes)
    ):
        q = q_ref[0]
        if mode == "int8":
            dq = q.astype(jnp.float32) * scales_ref[w, j]
        elif mode == "bf16":
            dq = q.astype(jnp.float32)
        else:
            dq = q
        corr = m_ref[...] - dq
        nx_ref[0] = x_ref[0] + corr
        na_ref[0] = a_ref[0] + corr


@partial(jax.jit, static_argnums=(5, 6))
def fused_apply_correction(leaves, anchors, qs, scales, means, modes,
                           interpret):
    """One-pass overlap correction of a comm chunk: dequantize the
    worker's own contribution, subtract from the chunk mean, add the
    correction to params AND anchor — the unfused
    ``apply_correction_fn`` semantics, bit-identical, one kernel."""
    w = leaves[0].shape[0]
    n = len(leaves)
    modes = tuple(modes)
    kernel = partial(_apply_correction_kernel, modes=modes)
    xs = [_stacked(x) for x in leaves]
    stacked = (
        xs + [_stacked(a) for a in anchors] + [_stacked(q) for q in qs]
    )
    ms = [m.reshape(_rows(m.shape)) for m in means]
    in_specs = (
        [_SMEM]
        + [_leaf_block(x) for x in stacked]
        + [_whole_block(m) for m in ms]
    )
    outs = _call(
        kernel, w, in_specs,
        [_leaf_block(x) for x in xs] * 2,
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs] * 2,
        interpret,
    )(jnp.stack([s.reshape(w) for s in scales], axis=1), *stacked, *ms)
    unview = [o.reshape(x.shape) for o, x in zip(outs, leaves * 2)]
    return tuple(unview[0:n]), tuple(unview[n:2 * n])
