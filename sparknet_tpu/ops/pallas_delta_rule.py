"""The gated delta rule's work inside chunks as two Pallas kernels — what
``ops/delta_rule._within_chunks`` computes (the decay matrix, the strictly
lower ``L``, ``(I + L)^-1``, ``u``, ``w``, the masked ``q k^T``, ``q_in``,
``k_out``), with every ``(C, C)`` matrix kept in VMEM, and its backward.

A grid cell is one (batch, head) and ``CELL_GROUPS`` groups of 128 tokens.
A group holds ``128 / chunk`` chunks side by side: their ``(C, C)`` matrices
are the diagonal blocks of one 128 x 128 matrix (the rest masked to zero), so
that every product has the MXU's shape and every elementwise pass full
registers; the inverse of a block-diagonal unit lower triangle is the
block-diagonal of the inverses, by the same doubling as
``delta_rule._unit_lower_inverse`` (no power of ``L`` is formed).

Layouts: ``q``, ``k``, ``v`` are read where the caller has them,
``(B, T, H * d)`` a head's 128 lanes at a time; ``g`` and ``beta`` as
``(B, H, T / 128, 128)``, a group a row; the outputs are written chunk-major,
``(N, B, H, C, d)``, as the scan over chunks reads them.  A per-token scalar
arrives as a row (tokens on lanes) and is needed as a column as well (tokens
on sublanes, to scale ``k``'s rows): the two are exchanged through the
diagonal of a 128 x 128 select and a reduction.

Precision is ``_within_chunks``': ``g``, ``gamma``, every ``exp`` and the
inverse's operands float32; the inverse's products at three bf16 passes (a
hi/lo split and three dots, ``Precision.HIGH``'s arithmetic) beside a lower
``compute_dtype`` and at full precision in float32; the other products take
their operands in ``compute_dtype`` and accumulate in float32.

The backward kernel takes the forward's inputs as its only residuals,
rebuilds the group's matrices in VMEM and returns the cotangents of ``q``,
``k``, ``v``, ``g``, ``beta``.  The inverse ``T = (I + L)^-1`` is
differentiated by its identity, ``dL = -tril(T^T dT T^T, -1)``: two products,
not the transposes of the doubling's twelve.

Off the TPU the kernels run in interpreter mode (tests only: the program
takes ``_within_chunks`` there, ``delta_rule.gated_delta_rule`` selects).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops.pallas_attention import (
    VMEM_LIMIT_BYTES,
    _out_struct,
    lowerable,
)

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128
CELL_GROUPS = 8  # groups of 128 tokens a grid cell: a (8, 128) tile of g
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def accepts(chunk: int, dk: int, dv: int) -> bool:
    """The shapes the kernels are written for: chunks that tile a group of
    128 tokens in whole bf16 register tiles, heads of whole lanes."""
    return (chunk % 16 == 0 and LANES % chunk == 0
            and dk % LANES == 0 and dv % LANES == 0)


def padded_length(t: int) -> int:
    """``t`` tokens rounded up to whole grid cells (to whole groups where one
    cell holds them all)."""
    groups = -(-t // LANES)
    if groups > CELL_GROUPS:
        groups = -(-groups // CELL_GROUPS) * CELL_GROUPS
    return groups * LANES


# -- products ---------------------------------------------------------------
def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=F32)


def _mm(a, b, dims, cd):
    """A product as ``_within_chunks`` makes it: operands in ``cd``,
    accumulated in float32 (in float32, at full precision)."""
    if cd == F32:
        return _dot(a, b, dims, HIGHEST)
    return _dot(a.astype(cd), b.astype(cd), dims)


def _pieces(x, cd):
    """A float32 operand of one of the inverse's products: itself beside
    float32, its bf16 head and the bf16 of what the head leaves otherwise."""
    if cd == F32:
        return (x,)
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


def _mm_pieces(a, b, dims):
    """The product of two ``_pieces``: full precision, or three bf16 passes
    (hi hi + hi lo + lo hi)."""
    if len(a) == 1:
        return _dot(a[0], b[0], dims, HIGHEST)
    return (_dot(a[0], b[1], dims) + _dot(a[1], b[0], dims)
            + _dot(a[0], b[0], dims))


# -- a group of 128 tokens ---------------------------------------------------
class _Masks(NamedTuple):
    row: jax.Array  # (128, 128) int32, the row's index
    col: jax.Array
    eye: jax.Array
    same: jax.Array  # row and column in one chunk
    lower: jax.Array  # ... and column <= row
    strict: jax.Array  # ... and column < row


def _masks(chunk: int) -> _Masks:
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    same = (row ^ col) < chunk
    return _Masks(row, col, row == col, same, same & (col <= row),
                  same & (col < row))


def _to_col(x_row, m: _Masks):
    """``(1, 128)`` (tokens on lanes) -> ``(128, 1)`` (tokens on sublanes)."""
    return jnp.sum(jnp.where(m.eye, x_row, 0.0), axis=1, keepdims=True)


def _to_row(x_col, m: _Masks):
    return jnp.sum(jnp.where(m.eye, x_col, 0.0), axis=0, keepdims=True)


def _sum_matrices(m: _Masks):
    """0/1: row ``k`` counts in column ``j``'s running sum (same chunk,
    ``k <= j``), and in its chunk's whole sum."""
    return (jnp.where(m.same & (m.row <= m.col), 1.0, 0.0),
            jnp.where(m.same, 1.0, 0.0))


def _running_sums(g, m: _Masks):
    """``g``: ``(groups, 128)``.  ``gamma``, the running sum of ``g`` inside
    each chunk, and ``last``, the chunk's whole sum at each of its tokens:
    two products with 0/1 matrices at full precision."""
    upto, whole = _sum_matrices(m)
    return _dot(g, upto, _NN, HIGHEST), _dot(g, whole, _NN, HIGHEST)


def _running_sums_transposed(d_gamma, d_last, m: _Masks):
    upto, whole = _sum_matrices(m)
    return (_dot(d_gamma, upto, _NT, HIGHEST)
            + _dot(d_last, whole, _NN, HIGHEST))


def _unit_lower_inverses(stricts, m: _Masks, chunk: int, cd):
    """``(I + L)^-1`` for each ``L`` of ``stricts``, strictly lower
    triangular inside each chunk of its group:
    ``delta_rule._unit_lower_inverse``'s doubling.  The first level needs no
    product (the blocks of size one are ones).  Level by level over all of
    them: a level's two products wait for one another, those of different
    groups do not, and the MXU is fed from one while another's result is
    on its way."""
    eye = jnp.where(m.eye, 1.0, 0.0)
    invs = [eye] * len(stricts)
    s = 1
    while s < chunk:
        quarter = (((m.row ^ m.col) < 2 * s)
                   & ((m.row & s) != 0) & ((m.col & s) == 0))
        ps = [jnp.where(quarter, strict, 0.0) for strict in stricts]
        if s == 1:
            invs = [inv - p for inv, p in zip(invs, ps)]
        else:
            held = [_pieces(inv, cd) for inv in invs]
            xs = [_mm_pieces(h, _pieces(p, cd), _NN)
                  for h, p in zip(held, ps)]
            invs = [inv - _mm_pieces(_pieces(x, cd), h, _NN)
                    for inv, x, h in zip(invs, xs, held)]
        s *= 2
    return invs


def _inverse_cotangents(invs, d_invs, m: _Masks, cd):
    """The cotangent of each ``L`` from that of its ``T = (I + L)^-1``:
    ``-T^T dT T^T`` on the strict lower triangle of each chunk (product by
    product over all of them, as ``_unit_lower_inverses``)."""
    held = [_pieces(inv, cd) for inv in invs]
    xs = [_mm_pieces(h, _pieces(jnp.where(m.lower, d, 0.0), cd), _TN)
          for h, d in zip(held, d_invs)]
    return [jnp.where(m.strict, -_mm_pieces(_pieces(x, cd), h, _NT), 0.0)
            for x, h in zip(xs, held)]


class _Group(NamedTuple):
    e: jax.Array  # (128, 1) exp(gamma)
    eo: jax.Array  # (128, 1) exp(last - gamma)
    b: jax.Array  # (128, 1) beta
    decay: jax.Array  # (128, 128) exp(gamma_i - gamma_j), lower, per chunk
    kb: jax.Array  # k beta
    vb: jax.Array  # v beta
    kbe: jax.Array  # k beta exp(gamma)
    kk: jax.Array  # (k beta) k^T, unmasked
    qk: jax.Array  # q k^T, unmasked
    strict: jax.Array  # L


def _group(q, k, v, gamma, last, beta, m: _Masks, cd) -> _Group:
    """``q``, ``k``, ``v``: ``(128, d)`` float32; ``gamma``, ``last``,
    ``beta``: ``(1, 128)``."""
    g_col = _to_col(gamma, m)
    b = _to_col(beta, m)
    e = jnp.exp(g_col)
    eo = jnp.exp(_to_col(last - gamma, m))
    # the masked half is never exponentiated
    decay = jnp.where(
        m.lower, jnp.exp(jnp.where(m.lower, g_col - gamma, 0.0)), 0.0)
    kb = k * b
    kk = _mm(kb, k, _NT, cd)
    return _Group(e, eo, b, decay, kb, v * b, kb * e, kk,
                  _mm(q, k, _NT, cd), jnp.where(m.strict, kk * decay, 0.0))


def _groups(q_ref, k_ref, v_ref, g_ref, beta_ref, m, chunk, cd):
    """The cell's groups: (index, rows of the token blocks, ``_Group``,
    ``(I + L)^-1``) of each."""
    gamma, last = _running_sums(g_ref[...], m)
    rows = [slice(i * LANES, (i + 1) * LANES) for i in range(g_ref.shape[0])]
    groups = [
        _group(q_ref[r, :], k_ref[r, :], v_ref[r, :], gamma[i:i + 1],
               last[i:i + 1], beta_ref[i:i + 1, :], m, cd)
        for i, r in enumerate(rows)]
    invs = _unit_lower_inverses([c.strict for c in groups], m, chunk, cd)
    return list(zip(range(len(rows)), rows, groups, invs))


def _fold_matrix(chunk: int, transposed: bool):
    """0/1, ``(128, chunk)``: column ``c`` gathers the lanes ``c`` modulo
    ``chunk`` (``transposed``: ``(chunk, 128)``, spreads them)."""
    shape = (chunk, LANES) if transposed else (LANES, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transposed else 1)
    return jnp.where((lane & (chunk - 1)) == c, 1.0, 0.0)


# -- the kernels --------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                u_ref, w_ref, qk_ref, q_in_ref, k_out_ref, *, chunk, cd):
    m = _masks(chunk)
    per_group = LANES // chunk
    fold = _fold_matrix(chunk, False)
    for i, rows, c, inv in _groups(
            q_ref, k_ref, v_ref, g_ref, beta_ref, m, chunk, cd):
        u = _mm(inv, c.vb, _NN, cd)
        w = _mm(inv, c.kbe, _NN, cd).astype(cd)
        qk = (c.qk * c.decay).astype(cd)
        if per_group > 1:  # the diagonal blocks, one under the other
            qk = _mm(qk, fold, _NN, cd).astype(cd)
        q_in = (q_ref[rows, :] * c.e).astype(cd)
        k_out = (k_ref[rows, :] * c.eo).astype(cd)
        for r in range(per_group):
            n, part = i * per_group + r, slice(r * chunk, (r + 1) * chunk)
            u_ref[n] = u[part]
            w_ref[n] = w[part]
            qk_ref[n] = qk[part]
            q_in_ref[n] = q_in[part]
            k_out_ref[n] = k_out[part]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                du_ref, dw_ref, dqk_ref, dq_in_ref, dk_out_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_last_ref,
                *, chunk, cd):
    m = _masks(chunk)
    per_group = LANES // chunk
    unfold = _fold_matrix(chunk, True)

    def stacked(ref, i):  # the group's chunks, one under the other
        x = ref[i * per_group:(i + 1) * per_group]
        return x.reshape(LANES, x.shape[-1])

    groups = _groups(q_ref, k_ref, v_ref, g_ref, beta_ref, m, chunk, cd)
    # u = T (v beta), w = T (k beta e): T's cotangent, then L's
    d_stricts = _inverse_cotangents(
        [inv for _, _, _, inv in groups],
        [_mm(stacked(du_ref, i), c.vb, _NT, cd)
         + _mm(stacked(dw_ref, i), c.kbe, _NT, cd) for i, _, c, _ in groups],
        m, cd)
    for (i, rows, c, inv), d_strict in zip(groups, d_stricts):
        q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
        dq_in = stacked(dq_in_ref, i).astype(F32)
        dk_out = stacked(dk_out_ref, i).astype(F32)
        dqk = stacked(dqk_ref, i)
        if per_group > 1:
            dqk = _mm(dqk, unfold, _NN, cd)
        d_vb = _mm(inv, stacked(du_ref, i), _TN, cd)
        d_kbe = _mm(inv, stacked(dw_ref, i), _TN, cd)
        d_kk = d_strict * c.decay
        d_qk = dqk.astype(F32) * c.decay
        # both masked products hang on exp(gamma_i - gamma_j)
        mix = d_kk * c.kk + d_qk * c.qk
        d_kb = _mm(d_kk, k, _NN, cd) + d_kbe * c.e
        dk_ref[rows, :] = (
            _mm(d_kk, c.kb, _TN, cd) + _mm(d_qk, q, _TN, cd)
            + dk_out * c.eo + d_kb * c.b)
        dq_ref[rows, :] = _mm(d_qk, k, _NN, cd) + dq_in * c.e
        dv_ref[rows, :] = d_vb * c.b
        d_beta = (jnp.sum(d_kb * k, axis=1, keepdims=True)
                  + jnp.sum(d_vb * v, axis=1, keepdims=True))
        d_e = c.e * (jnp.sum(dq_in * q, axis=1, keepdims=True)
                     + jnp.sum(d_kbe * c.kb, axis=1, keepdims=True))
        d_eo = c.eo * jnp.sum(dk_out * k, axis=1, keepdims=True)
        d_gamma = jnp.sum(mix, axis=1, keepdims=True) + d_e - d_eo
        dbeta_ref[i:i + 1, :] = _to_row(d_beta, m)
        dg_ref[i:i + 1, :] = (
            _to_row(d_gamma, m) - jnp.sum(mix, axis=0, keepdims=True))
        d_last_ref[i:i + 1, :] = _to_row(d_eo, m)
    dg_ref[...] = _running_sums_transposed(dg_ref[...], d_last_ref[...], m)


# -- the calls ----------------------------------------------------------------
def _flat(x):  # (B, T, H, d) -> (B, T, H * d): a head is a block of lanes
    return x.reshape(*x.shape[:2], -1)


def _rows(x):  # (B, T, H) -> (B, H, T / 128, 128): a group is a row
    b, t, h = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b, h, t // LANES, LANES)


def _unrows(x):
    b, h = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, h, -1), 1, 2)


def _call(kernel, name, chunk, cd, q, k, v, g, beta, cotangents=()):
    """One kernel over the grid (batch, head, cell of groups): ``q``, ``k``,
    ``v`` ``(B, T, H, d)``, ``g``, ``beta`` ``(B, T, H)``, and the five
    chunk-major arrays — the forward's outputs, the backward's further
    inputs (its outputs are shaped as its first five inputs)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t != padded_length(t):
        raise ValueError(f"T={t} is not padded_length(T)={padded_length(t)}")
    groups = t // LANES
    cell = min(groups, CELL_GROUPS)
    n, n_cell = t // chunk, cell * (LANES // chunk)
    operands = (q, k, v, g, beta, *cotangents)
    tokens = lambda d: (  # noqa: E731
        pl.BlockSpec((None, cell * LANES, d), lambda bi, hi, j: (bi, j, hi)),
        _out_struct((b, t, h * d), F32, *operands))
    scalars = (
        pl.BlockSpec((None, None, cell, LANES),
                     lambda bi, hi, j: (bi, hi, j, 0)),
        _out_struct((b, h, groups, LANES), F32, *operands))
    chunks = lambda d, dtype: (  # noqa: E731
        pl.BlockSpec((n_cell, None, None, chunk, d),
                     lambda bi, hi, j: (j, bi, hi, 0, 0)),
        _out_struct((n, b, h, chunk, d), dtype, *operands))
    first = [tokens(dk), tokens(dk), tokens(dv), scalars, scalars]
    second = [chunks(dv, F32), chunks(dk, cd), chunks(chunk, cd),
              chunks(dk, cd), chunks(dk, cd)]
    ins, outs = (first + second, first) if cotangents else (first, second)
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, cd=cd),
        grid=(b, h, groups // cell),
        in_specs=[spec for spec, _ in ins],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        # the backward's: d last, a row a group, until d g is summed
        scratch_shapes=[pltpu.VMEM((cell, LANES), F32)] if cotangents else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=not lowerable(),
        name=name,
    )(_flat(q), _flat(k), _flat(v), _rows(g), _rows(beta), *cotangents)


def _forward(q, k, v, g, beta, chunk, cd):
    return _call(_fwd_kernel, "delta_rule_within_chunks", chunk, cd,
                 q, k, v, g, beta)


def _backward(q, k, v, g, beta, cotangents, chunk, cd):
    dq, dk, dv, dg, dbeta = _call(
        _bwd_kernel, "delta_rule_within_chunks_backward", chunk, cd,
        q, k, v, g, beta, cotangents)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            _unrows(dg), _unrows(dbeta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def within_chunks(q, k, v, g, beta, chunk, cd):
    """``q``, ``k``: ``(B, T, H, dk)``, ``v``: ``(B, T, H, dv)``, ``g``,
    ``beta``: ``(B, T, H)``, all float32, ``T == padded_length(T)``; ``cd`` a
    ``jnp.dtype``.  Returns ``u`` (float32), ``w``, ``qk``, ``q_in``,
    ``k_out`` (``cd``) as ``_within_chunks`` does, chunk-major:
    ``(T / chunk, B, H, chunk, .)``."""
    return _forward(q, k, v, g, beta, chunk, cd)


def _within_chunks_fwd(q, k, v, g, beta, chunk, cd):
    return _forward(q, k, v, g, beta, chunk, cd), (q, k, v, g, beta)


def _within_chunks_bwd(chunk, cd, residuals, cotangents):
    return _backward(*residuals, cotangents, chunk, cd)


within_chunks.defvjp(_within_chunks_fwd, _within_chunks_bwd)
