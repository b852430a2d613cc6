"""The alignment loss with its gradient as ONE Pallas kernel
(``alignment_gradient``): what ``ops/sparse_attention._alignment_with_
gradient`` computes in XLA — ``L_I = sum_t KL(p[t, .] || softmax_{S_t}
(I[t, .]))`` and its closed-form gradient to ``qI``, ``w`` and ``kI`` —
without a ``(block_q, keys)`` tile of ``p``, ``I``, ``log q``, ``g`` or ``M``
leaving VMEM.

The walk is the masked flash kernels' (``ops/pallas_attention.py``): a query
block outer, the key blocks inner up to the causal edge, the selection's
bits as the keep-mask (``pack_mask``'s layout: a block of ``block_k`` keys
is ``block_k / words`` bits of every word, unpacked in VMEM by
``pallas_attention._keep``), the blocks above the diagonal neither computed
nor fetched (``_walk``, ``_last_key_block``; ``blocks_met`` counts the rest).
A query block sweeps its key blocks TWICE (the grid's inner axis is twice
the key blocks):

1. the index scores, ``I = sum_j w_j relu(qI_j . kI)``, and each row's
   running maximum and sum of ``exp(I)`` over its kept keys, the indexer's
   softmax's normaliser; each block's ``I`` and its heads' signs (``[s_j >
   0]``, head ``j`` bit ``j`` of an int32) stay in VMEM for the second sweep
   (``KEYS_VMEM_BYTES`` bounds them with ``dkI``'s accumulator);
2. the 32 heads' scores ONCE, ``p = mean_h exp(q_h . k - lse_h)`` over the
   kept keys, the KL terms, ``g = softmax_{S_t}(I) * sum_s p - p`` where ``I
   != 0``, and for each index head ``M_j = g [s_j > 0]`` (rounded to the
   operand dtype once), ``U_j += M_j @ kI`` into the query block's float32
   accumulator and ``dkI += M_j^T @ (w_j qI_j)`` into a float32 accumulator
   over every key (the output, resident in VMEM across the grid).

Of the 64-deep or 64-wide products, which half fill the MXU whichever
operand is stationary, that is three a block pair (the index scores once,
``U``, ``dkI``) where the XLA form runs four; the heads' product runs once.

``sum_s p``, the row's share of the attention's probability on its kept
keys, is taken as 1 for a row that keeps a key and 0 for one that keeps
none: each head's ``exp(s - lse)`` over the kept keys is normalised by the
masked pass's own ``lse`` over the same keys, from the same ``q`` and ``k``,
and every real row keeps ``min(row + 1, topk) >= 1`` keys.  That is what
lets ``p``'s product run once a block pair: summing ``p`` before the first
``g`` would take a sweep of its own.  It is the one departure from the XLA
form's order of operations (``tests/test_alignment_kernel.py`` shows the
identity and the agreement).

Layout: keys on rows, queries on lanes, as the flash backward forms its
transposed scores: a per-query scalar is a ``(1, block_q)`` row and a sum
over keys runs down sublanes.  ``qI`` and ``w`` come in heads first,
``(B, J * Di, T)`` and ``(B, J, T)``; ``kI`` as it lies ``(B, T, Di)`` and
transposed ``(B, Di, T)`` for ``U``'s product; ``q``, ``k`` as the attention
left them, ``(B, T, H * D)``; the log-sum-exp heads first ``(B, Hq, T)``.
The gradient to ``qI`` comes back heads first and ``w``'s transposed; XLA
moves them (16 MiB and 1 MiB at the cell's shapes).

Arithmetic as the XLA form's: the products take their operands in the
compute dtype and accumulate in float32 (float32 ones at full precision),
``p``, ``I``, the KL and every sum are float32, the ``1e-30`` floor of the
normaliser stays.  A sequence that is not whole blocks is padded: rows with
no bits, keys to the mask's ``32 * words``.  Off the TPU the kernel runs in
interpreter mode (tests only: the program takes the XLA form there,
``sparse_attention._alignment_with_gradient`` selects).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops import pallas_attention
from sparknet_tpu.ops.attention import BITS
from sparknet_tpu.ops.pallas_grouped_matmul import _bind
from sparknet_tpu.ops.pallas_attention import (
    F32,
    LANES,
    MASK,
    _NN,
    _NT,
    _compiler_params,
    _keep,
    _last_key_block,
    _mm,
    _out_struct,
    _pad_rows,
    _walk,
    lowerable,
)

BLOCK_Q = 512
BLOCK_K = 512  # keys a block, in whole bits of every word of the mask
# What the kernel holds in VMEM over every key: the first sweep's index
# scores and their heads' signs a query block keeps for the second (float32
# and int32, 8 bytes a key and query) and dkI's float32 accumulator (a key's
# 64 in a row of 128 lanes, double-buffered: 1 KiB a key); 80 MiB at 16,384
# keys x 512 queries.  A longer sequence takes smaller query blocks.
KEYS_VMEM_BYTES = 80 << 20

# What ``accepts`` asks, as the ``alignment_loss_path`` instant tells it of
# a shape turned away.  Float32 stays on the XLA form for the reason the
# masked pass keeps it there (``sparse_attention.kernels_refuse``): a Mosaic
# call changes the scoped VMEM of the fusions beside it.
ACCEPTS = ("the kernel takes bfloat16, heads of whole lanes in whole groups, "
           "at most 32 index heads of whole bfloat16 tiles within a row of "
           "lanes, query blocks of "
           "at least 128 within its VMEM and a mask's row of whole lanes of "
           "words: T % 4096 == 0")


def accepts(t: int, hq: int, hkv: int, d: int, j: int, di: int,
            dtype) -> bool:
    """The shapes ``sparse_attention.alignment_loss`` hands the kernel under
    differentiation (``ACCEPTS``)."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and hq % hkv == 0 and d % LANES == 0 and di % 16 == 0
            and di <= LANES and j <= BITS and t % (BITS * LANES) == 0
            and blocks(t, t // BITS)[0] >= LANES)


def blocks(t: int, words: int):
    """``(block_q, block_k)`` the kernel walks a sequence of ``t`` in: a
    key block is whole bits of every word of the mask's row; a query block
    as large as ``BLOCK_Q`` where what is held over the mask's ``32 * words``
    keys fits ``KEYS_VMEM_BYTES``, else halved until it does."""
    block_q = min(BLOCK_Q, t)
    while block_q > 1 and BITS * words * 8 * (block_q + LANES) > (
            KEYS_VMEM_BYTES):
        block_q //= 2
    return block_q, words * max(1, BLOCK_K // words)


class _Shape(NamedTuple):
    """What the kernel is specialised on: ``walk``, the flash kernels'
    ``_Shape`` of the causal walk under a keep-mask; the attention's
    ``heads`` of ``d`` in groups of ``group`` a K/V head; ``index_heads``
    of ``di``; ``dtype``, the products' operands."""
    walk: pallas_attention._Shape
    heads: int
    group: int
    d: int
    index_heads: int
    di: int
    dtype: np.dtype


def _bit(h: int):
    """Bit ``h`` of an int32 word."""
    return jnp.left_shift(jnp.int32(1), h)


def _rows(ref, j, c: _Shape):
    """Index head ``j``'s ``di`` rows of a heads-first block."""
    return ref[j * c.di:(j + 1) * c.di, :]


def _kernel(offs_ref, bits_ref, qit_ref, wt_ref, ki_ref, kit_ref, q_ref,
            k_ref, lse_ref, kl_ref, dqit_ref, dwt_ref, dki_ref,
            m_scr, l_scr, ut_scr, wqt_scr, scores_scr, signs_scr, *,
            c: _Shape):
    walk = c.walk
    i, x = pl.program_id(1), pl.program_id(2)
    nk = walk.tk // walk.block_k
    first = x < nk  # the first sweep, else the second
    j = jnp.where(first, x, x - nk)

    @pl.when(jnp.logical_and(i == 0, x == 0))
    def _():  # dkI over every key, a sequence
        dki_ref[...] = jnp.zeros(dki_ref.shape, F32)

    @pl.when(x == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, MASK, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        kl_ref[...] = jnp.zeros(kl_ref.shape, F32)
        ut_scr[...] = jnp.zeros(ut_scr.shape, F32)
        for h in range(c.index_heads):
            wqt_scr[h * c.di:(h + 1) * c.di, :] = (
                wt_ref[h:h + 1, :] * _rows(qit_ref, h, c).astype(F32)
            ).astype(wqt_scr.dtype)

    keys = pl.ds(pl.multiple_of(j * walk.block_k, walk.block_k), walk.block_k)

    def normaliser(keep):
        # I of the block, (block_k, block_q) float32, and the signs of its
        # heads' products, head h bit h: kept for the second sweep
        ki = ki_ref[...]
        scores = signs = None
        for h in range(c.index_heads):
            s = _mm(ki, _rows(qit_ref, h, c), _NN)
            pos = s > 0.0
            part = jnp.where(pos, s, 0.0) * wt_ref[h:h + 1, :]
            bit = jnp.where(pos, _bit(h), jnp.int32(0))
            scores = part if scores is None else scores + part
            signs = bit if signs is None else signs | bit
        scores_scr[keys, :] = scores
        signs_scr[keys, :] = signs
        i_s = jnp.where(keep, scores, MASK)
        m_prev = m_scr[...]
        m = jnp.maximum(m_prev, jnp.max(i_s, axis=0, keepdims=True))
        l_scr[...] = jnp.exp(m_prev - m) * l_scr[...] + jnp.sum(
            jnp.where(keep, jnp.exp(i_s - m), 0.0), axis=0, keepdims=True)
        m_scr[...] = m

    def gradient(keep):
        p = None
        for h in range(c.heads):
            kv = h // c.group
            s = _mm(k_ref[:, kv * c.d:(kv + 1) * c.d],
                    q_ref[:, h * c.d:(h + 1) * c.d], _NT)
            e = jnp.exp(s - lse_ref[h:h + 1, :])
            p = e if p is None else p + e
        p = jnp.where(keep, p * (1.0 / c.heads), 0.0)
        scores, signs = scores_scr[keys, :], signs_scr[keys, :]
        l = l_scr[...]  # 0 where the row keeps no key (the floor's case)
        log_q = (scores - m_scr[...]) - jnp.log(jnp.maximum(l, 1e-30))
        live = jnp.logical_and(keep, p > 0.0)
        kl_ref[...] += jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_q), 0.0),
            axis=0, keepdims=True)
        kept = (l > 0.0).astype(F32)  # sum_s p: the identity
        g = jnp.where(jnp.logical_and(keep, scores != 0.0),
                      jnp.exp(log_q) * kept - p, 0.0).astype(c.dtype)
        dki = None
        for h in range(c.index_heads):
            m = jnp.where((signs & _bit(h)) != 0, g, 0.0)
            rows = slice(h * c.di, (h + 1) * c.di)
            ut_scr[rows, :] += _mm(kit_ref[...], m, _NN)
            part = _mm(m, wqt_scr[rows, :], _NT)
            dki = part if dki is None else dki + part
        dki_ref[keys, :] += dki

    def step(masked):
        keep = _keep(offs_ref, bits_ref, i, j, walk, True)
        pl.when(first)(lambda: normaliser(keep))
        pl.when(jnp.logical_not(first))(lambda: gradient(keep))

    _walk(offs_ref, i, j, nk, walk, step)

    @pl.when(x == 2 * nk - 1)
    def _():
        for h in range(c.index_heads):
            rows = slice(h * c.di, (h + 1) * c.di)
            u = ut_scr[rows, :]
            dqit_ref[rows, :] = (wt_ref[h:h + 1, :] * u).astype(dqit_ref.dtype)
            dwt_ref[h:h + 1, :] = jnp.sum(
                _rows(qit_ref, h, c).astype(F32) * u, axis=0, keepdims=True)


@partial(jax.jit, static_argnums=(0,))
def _call(c: _Shape, offs, bits, qit, wt, ki, kit, q, k, lse):
    """The kernel, one module-level ``jax.jit``: lowered once a program
    however many layers call it (``pallas_grouped_matmul``'s module
    docstring)."""
    walk = c.walk
    b, tq = bits.shape[:2]
    nq, nk = tq // walk.block_q, walk.tk // walk.block_k
    jd = c.index_heads * c.di

    def key_block(bi, i, x, offs_ref):  # the first sweep's, else the second's
        return jnp.minimum(jnp.where(x < nk, x, x - nk),
                           _last_key_block(i, offs_ref, nk, walk))

    def second_key_block(bi, i, x, offs_ref):  # block 0 in the first sweep
        return jnp.where(x < nk, 0, key_block(bi, i, x, offs_ref))

    rows_first = lambda n: pl.BlockSpec(  # noqa: E731
        (None, n, walk.block_q), lambda bi, i, x, o: (bi, 0, i))
    operands = (offs, bits, qit, wt, ki, kit, q, k, lse)
    return pl.pallas_call(
        partial(_kernel, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nq, 2 * nk),
            in_specs=[
                pl.BlockSpec((None, walk.block_q, walk.words),
                             lambda bi, i, x, o: (bi, i, 0)),
                rows_first(jd),
                rows_first(c.index_heads),
                pl.BlockSpec((None, walk.block_k, c.di),
                             lambda *g: (g[0], key_block(*g), 0)),
                pl.BlockSpec((None, c.di, walk.block_k),
                             lambda *g: (g[0], 0, second_key_block(*g))),
                pl.BlockSpec((None, walk.block_q, q.shape[-1]),
                             lambda bi, i, x, o: (bi, i, 0)),
                pl.BlockSpec((None, walk.block_k, k.shape[-1]),
                             lambda *g: (g[0], second_key_block(*g), 0)),
                rows_first(c.heads),
            ],
            out_specs=[
                rows_first(1), rows_first(jd), rows_first(c.index_heads),
                pl.BlockSpec((None, walk.tk, c.di),
                             lambda bi, i, x, o: (bi, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, walk.block_q), F32),
                pltpu.VMEM((1, walk.block_q), F32),
                pltpu.VMEM((jd, walk.block_q), F32),
                pltpu.VMEM((jd, walk.block_q), c.dtype),
                pltpu.VMEM((walk.tk, walk.block_q), F32),
                pltpu.VMEM((walk.tk, walk.block_q), jnp.int32),
            ]),
        out_shape=[
            _out_struct((b, 1, tq), F32, *operands),
            _out_struct((b, jd, tq), c.dtype, *operands),
            _out_struct((b, c.index_heads, tq), F32, *operands),
            _out_struct((b, walk.tk, c.di), F32, *operands),
        ],
        compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=walk.interpret,
        name="alignment_gradient",
    )(*operands)


def alignment_gradient(qi, w, ki, q, k, lse, mask, *, block_q: int,
                       block_k: int, interpret=None):
    """``(L_I, (dL/dqI, dL/dw, dL/dkI))``: the loss summed over every row
    and its gradient at cotangent 1, as ``sparse_attention.
    _alignment_with_gradient`` returns them.  ``qi`` ``(B, T, J, Di)`` and
    ``ki`` ``(B, T, Di)`` in the compute dtype, ``w`` ``(B, T, J)`` float32;
    ``q`` ``(B, T, Hq, D)`` scaled and ``k`` ``(B, T, Hkv, D)``, the
    attention's; ``lse`` ``(B, T, Hq)`` float32, its rows' log-sum-exp;
    ``mask`` the selection's bits ``(B, T, words)`` uint32.  ``block_k`` is
    whole bits of every word, ``32 * words`` whole blocks of it."""
    if interpret is None:
        interpret = not lowerable()
    b, t, nj, di = qi.shape
    hq, d = q.shape[2:]
    hkv = k.shape[2]
    words = mask.shape[-1]
    keys = BITS * words
    if not (block_k % words == 0 and keys % block_k == 0 and hq % hkv == 0
            and keys >= t and nj <= BITS):
        raise ValueError(
            f"a mask of {words} words a row wants key blocks of whole bits "
            f"of every word, and the signs of at most {BITS} index heads an "
            f"int32: T {t}, block_k {block_k}, heads {hq} / {hkv}, index "
            f"heads {nj}")
    cd = np.dtype(qi.dtype)
    tq = -(-t // block_q) * block_q
    walk = pallas_attention._Shape(
        True, 1.0, 1, d, keys, block_q, block_k, np.dtype(F32),
        bool(interpret), words, 0, 0)
    c = _Shape(walk, hq, hq // hkv, d, nj, di, cd)
    rows = lambda x: _pad_rows(x, block_q)  # noqa: E731
    heads_first = lambda x: jnp.swapaxes(  # noqa: E731
        rows(x).reshape(b, tq, -1), 1, 2)
    kip = _pad_rows(ki.astype(cd), keys)
    kl, dqit, dwt, dki = _bind(
        _call, c, jnp.zeros((2,), jnp.int32),
        jax.lax.bitcast_convert_type(rows(mask), jnp.int32),
        heads_first(qi), heads_first(w.astype(F32)), kip,
        jnp.swapaxes(kip, 1, 2), rows(q.astype(cd)).reshape(b, tq, hq * d),
        _pad_rows(k.astype(cd), keys).reshape(b, keys, hkv * d),
        heads_first(lse.astype(F32)))
    d_qi = jnp.swapaxes(dqit, 1, 2)[:, :t].reshape(b, t, nj, di)
    return jnp.sum(kl), (d_qi, jnp.swapaxes(dwt, 1, 2)[:, :t].astype(w.dtype),
                         dki[:, :t].astype(ki.dtype))
