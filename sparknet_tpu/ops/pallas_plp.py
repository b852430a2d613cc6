"""Fused cross-channel-LRN + 3x3/2 max-pool Pallas kernel (the AlexNet
sandwich ``normK -> poolK``, reference layer pair ``lrn_layer.cpp`` +
``pooling_layer.cpp``).

Why fuse: both layers are HBM-streaming ops on the two largest activation
tensors of the headline step (measured ~8.4 ms of the 20.5 ms AlexNet
iteration on v5e, and bandwidth-bound: every LRN lowering variant hits the
same floor).  Separately they move ~6.5|x| of HBM traffic per iteration;
fused, the LRN output never exists in HBM:

  fwd  r|x| + w|x|/4          (read x, write pooled)
  bwd  r|x| + r|x|/4 + w|x|   (read x + dy, recompute, write dx)

Kernel geometry (NCHW blocks, C on the untiled major axis so the LRN
channel window is free major-dim shifts):

- grid (N, bands): each band computes ``tp`` pooled rows from input rows
  ``[2*j*tp - 2, 2*(j+1)*tp + 1]``; the overlap rows arrive through
  separate halo BlockSpecs (block-granularity can't express overlapping
  main blocks).  Negative offsets are clamped in the index map and the
  affected window slot is masked in-kernel (Mosaic crashes on negative
  block offsets).
- pool rows: sublane-parity reshape (supported) -> window phases.
- pool cols: lane shifts + max, then stride-2 lane packing via a 0/1
  selection matrix on the MXU (Mosaic supports neither lane-dim shape
  casts nor 3-D strided gathers; a dot with [w == 2q+b] is exact).
- backward routes dy to window argmax positions with exclusive
  first-match masks (the reference's first-max rule) in two stages
  (columns in packed space, then rows), recomputing everything from x —
  only x is saved by the custom_vjp.

Geometry gate (``fusable``): MAX pool, kernel 3, stride 2, pad 0, odd
H/W (Caffe ceil mode adds no window), ACROSS_CHANNELS odd-size LRN.
AlexNet's 55x55 and 27x27 sandwiches qualify.

On non-TPU backends the kernel runs in interpreter mode so tests pin it
against the unfused XLA path.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops.vision import _fast_negpow

# Pooled rows per band. Fixed at 8: TPU block shapes need the
# second-minor dim divisible by 8, so the main input block is 16 rows
# and halo rows ride in adjacent 8-row chunks (sliced in-kernel).
# Small bands keep the working set a few MB so Mosaic double-buffers
# the HBM streams (a whole-image block measured 4x SLOWER than
# unfused — no pipelining).
_TP = 8


def pooled_hw(h: int, w: int):
    return (h - 3) // 2 + 1, (w - 3) // 2 + 1


def fusable(norm_region: str, n: int, pool_method: str, kernel, stride,
            pad, h: int, w: int) -> bool:
    """Geometry gate for the fused path (see module doc)."""
    return (
        norm_region.upper() == "ACROSS_CHANNELS"
        and n % 2 == 1
        and pool_method.upper() == "MAX"
        and tuple(kernel) == (3, 3)
        and tuple(stride) == (2, 2)
        and tuple(pad) == (0, 0)
        and h % 2 == 1
        and w % 2 == 1
        and h >= 3
        and w >= 3
    )


# ---------------------------------------------------------------------------
# shared in-kernel pieces
# ---------------------------------------------------------------------------


def _window_sum_c(v, n: int):
    """Centered channel-window sum over axis 0 of (C, R, W) — major-dim
    shifted adds (C is untiled: free slices)."""
    c = v.shape[0]
    pre = (n - 1) // 2
    post = n - 1 - pre
    acc = v
    for d in range(1, min(post, c - 1) + 1):
        acc = acc + jnp.pad(v[d:], ((0, d), (0, 0), (0, 0)))
    for d in range(1, min(pre, c - 1) + 1):
        acc = acc + jnp.pad(v[:-d], ((d, 0), (0, 0), (0, 0)))
    return acc


def _lrn(x, n, alpha, beta, k):
    scale = k + (alpha / n) * _window_sum_c(x * x, n)
    p = _fast_negpow(scale, beta)
    return x * p, scale, p


def _shift_left(v, d):
    """v[..., w] <- v[..., w+d] along lanes, zero fill (stride-1 slice)."""
    if d == 0:
        return v
    return jnp.pad(v[:, :, d:], ((0, 0), (0, 0), (0, d)))


def _row_phases(y, m):
    """(C, R, W) with R even -> window row phases r0/r1/r2 (rows 2u,
    2u+1, 2u+2 for u < m) via sublane-parity reshape."""
    C, R, W = y.shape
    r = y.reshape(C, R // 2, 2, W)
    ev, od = r[:, :, 0, :], r[:, :, 1, :]
    return ev[:, :m], od[:, :m], ev[:, 1 : m + 1]


def _dot3(a, s):
    """(C, m, X) @ (X, Y) -> (C, m, Y) on the MXU (exact for 0/1 s)."""
    return lax.dot_general(
        a, s, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _colpool_unpacked(rowmax):
    """max over the 3-col window anchored at every lane: u[w] =
    max(rm[w], rm[w+1], rm[w+2]); windows live at even lanes."""
    m1 = jnp.maximum(rowmax, _shift_left(rowmax, 1))
    return jnp.maximum(m1, _shift_left(rowmax, 2))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_main, x_post, s0, o_ref, *, n, alpha, beta, k, tp, ph):
    # x_post is the NEXT 16-row chunk; only its first 2 rows are the halo
    xb = jnp.concatenate(
        [x_main[0], x_post[0][:, :2]], axis=1
    )  # (C, 2tp+2, W)
    x = xb.astype(jnp.float32)
    y, _, _ = _lrn(x, n, alpha, beta, k)
    r0, r1, r2 = _row_phases(y, tp)
    rowmax = jnp.maximum(jnp.maximum(r0, r1), r2)  # (C, tp, W)
    pooled = _colpool_unpacked(rowmax)
    o_ref[0] = _dot3(pooled, s0[...]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(
    x_pre, x_main, x_post, dy_halo, dy_main, s0t,
    dx_ref, *, n, alpha, beta, k, tp, ph,
):
    j = pl.program_id(1)
    # x_pre/x_post are the adjacent 8-row chunks; only the 2 rows
    # touching the band are halo, dy_halo's last row is window j*tp-1
    xb = jnp.concatenate(
        [x_pre[0][:, 6:], x_main[0], x_post[0][:, :2]], axis=1
    ).astype(jnp.float32)  # (C, 2tp+4, W)
    C, R, W = xb.shape
    y, scale, p = _lrn(xb, n, alpha, beta, k)
    # tp+1 window slots s = 0..tp; slot s is global window j*tp - 1 + s
    r0, r1, r2 = _row_phases(y, tp + 1)
    rowmax = jnp.maximum(jnp.maximum(r0, r1), r2)  # (C, tp+1, W)
    pooled = _colpool_unpacked(rowmax)
    dyw = jnp.concatenate(
        [dy_halo[0][:, 7:], dy_main[0]], axis=1
    ).astype(jnp.float32)  # (C, tp+1, pw)
    # mask invalid slots: global window index outside [0, ph) — slot 0 of
    # band 0 (the clamped pre-halo) and ragged-tail slots (whose dy block
    # rows were out-of-bounds reads)
    slot = lax.broadcasted_iota(jnp.int32, dyw.shape, 1)
    gwin = j * tp - 1 + slot
    dyw = jnp.where((gwin >= 0) & (gwin < ph), dyw, 0.0)

    # stage 1 (columns): dy -> rowmax positions, exclusive first-match.
    # All comparisons happen UNPACKED in f32 (window q anchored at lane
    # 2q) — the MXU only places dy values (exact: dy is bf16-valued), so
    # packing never perturbs an equality.
    pw = dyw.shape[2]
    dy_up = _dot3(dyw, s0t[...])  # dy at even lanes, (C, tp+1, W)
    lane = lax.broadcasted_iota(jnp.int32, rowmax.shape, 2)
    anchor = (lane % 2 == 0) & (lane <= 2 * (pw - 1))
    d_rowmax = jnp.zeros_like(rowmax)
    taken = None
    for b in range(3):
        m = (_shift_left(rowmax, b) == pooled) & anchor
        if taken is not None:
            m = jnp.logical_and(m, jnp.logical_not(taken))
        taken = m if taken is None else jnp.logical_or(taken, m)
        placed = jnp.where(m, dy_up, 0.0)
        if b:
            placed = jnp.pad(
                placed[:, :, :-b], ((0, 0), (0, 0), (b, 0))
            )
        d_rowmax = d_rowmax + placed

    # stage 2 (rows): rowmax grads -> y rows, exclusive first-match
    da, taken = [], None
    for r in (r0, r1, r2):
        m = r == rowmax
        if taken is not None:
            m = jnp.logical_and(m, jnp.logical_not(taken))
        taken = m if taken is None else jnp.logical_or(taken, m)
        da.append(jnp.where(m, d_rowmax, 0.0))
    # band row t (global 2*j*tp + t, t < 2tp): even t gets phase0 of
    # slot t/2+1 and phase2 of slot t/2; odd t gets phase1 of slot
    # (t-1)/2+1 — interleave via sublane stack+reshape
    ev = da[0][:, 1 : tp + 1] + da[2][:, :tp]
    od = da[1][:, 1 : tp + 1]
    dyp = jnp.stack([ev, od], axis=2).reshape(C, 2 * tp, W)

    xband = xb[:, 2 : 2 * tp + 2]
    pband = p[:, 2 : 2 * tp + 2]
    sband = scale[:, 2 : 2 * tp + 2]
    inner = _window_sum_c(dyp * xband * pband / sband, n)
    dx = pband * dyp - (2.0 * alpha * beta / n) * xband * inner
    dx_ref[0] = dx.astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# host-side plumbing
# ---------------------------------------------------------------------------


def _sel_matrices(w: int, pw: int):
    mats = []
    for b in range(3):
        s = np.zeros((w, pw), np.float32)
        for q in range(pw):
            if 2 * q + b < w:
                s[2 * q + b, q] = 1.0
        mats.append(s)
    return mats


def _use_interpret(interpret):
    if interpret is None:
        # one source of truth for "kernels lower here" — the shared
        # pallas_attention.lowerable() gate, not a local backend check
        from sparknet_tpu.ops.pallas_attention import lowerable

        return not lowerable()
    return interpret


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_maxpool(x, n, alpha, beta, k, interpret=None):
    """maxpool_3x3_s2(lrn_across_channels(x)) on NCHW, fused."""
    y, _ = _fwd(x, n, alpha, beta, k, interpret)
    return y


def _fwd(x, n, alpha, beta, k, interpret):
    N, C, H, W = x.shape
    ph, pw = pooled_hw(H, W)
    tp = _TP
    nb = -(-ph // tp)
    s0, _, _ = _sel_matrices(W, pw)
    interp = _use_interpret(interpret)
    y = pl.pallas_call(
        functools.partial(
            _fwd_kernel, n=n, alpha=float(alpha), beta=float(beta),
            k=float(k), tp=tp, ph=ph,
        ),
        out_shape=jax.ShapeDtypeStruct((N, C, ph, pw), x.dtype),
        grid=(N, nb),
        in_specs=[
            pl.BlockSpec((1, C, 2 * tp, W), lambda i, j: (i, 0, j, 0)),
            # next 8-row chunk (first 2 rows are the halo)
            pl.BlockSpec(
                (1, C, tp, W), lambda i, j: (i, 0, 2 * (j + 1), 0)
            ),
            pl.BlockSpec((W, pw), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, tp, pw), lambda i, j: (i, 0, j, 0)),
        interpret=interp,
        compiler_params=_COMPILER_PARAMS,
    )(x, x, jnp.asarray(s0))
    return y, x


def _bwd(n, alpha, beta, k, interpret, x, dy):
    N, C, H, W = x.shape
    ph, pw = pooled_hw(H, W)
    tp = _TP
    # bands write 2*tp dx rows each; odd H = 2*ph+1 means the final row
    # (phase-2 gradient of the last window) needs one band beyond the
    # pooled-row count
    nb = -(-H // (2 * tp))
    mats = _sel_matrices(W, pw)
    args = [jnp.asarray(mats[0].T.copy())]
    interp = _use_interpret(interpret)
    sel_specs = [pl.BlockSpec((pw, W), lambda i, j: (0, 0))]
    dx = pl.pallas_call(
        functools.partial(
            _bwd_kernel, n=n, alpha=float(alpha), beta=float(beta),
            k=float(k), tp=tp, ph=ph,
        ),
        out_shape=jax.ShapeDtypeStruct((N, C, H, W), dy.dtype),
        grid=(N, nb),
        in_specs=[
            # previous 16-row chunk (last 2 rows are the pre-halo) —
            # clamped at band 0, the affected window slot is masked
            pl.BlockSpec(
                (1, C, 8, W),
                lambda i, j: (i, 0, jnp.maximum(2 * j - 1, 0), 0),
            ),
            pl.BlockSpec((1, C, 2 * tp, W), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec(
                (1, C, tp, W), lambda i, j: (i, 0, 2 * (j + 1), 0)
            ),
            # previous 8-row dy chunk (last row is window j*tp-1)
            pl.BlockSpec(
                (1, C, tp, pw),
                lambda i, j: (i, 0, jnp.maximum(j - 1, 0), 0),
            ),
            pl.BlockSpec((1, C, tp, pw), lambda i, j: (i, 0, j, 0)),
            *sel_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, C, 2 * tp, W), lambda i, j: (i, 0, j, 0)
        ),
        interpret=interp,
        compiler_params=_COMPILER_PARAMS,
    )(x, x, x, dy, dy, *args)
    return (dx,)


lrn_maxpool.defvjp(_fwd, _bwd)
