"""Fused attention kernels in Pallas — the hot-op custom kernel path.

One family of K/V-blocked flash kernels, a forward and ONE backward (with
the dq and dk/dv passes it replaces kept for a K/V head too long for its
VMEM budget), behind one ``jax.custom_vjp`` (``_flash_core``) serves the
dense path (``flash_attention``: ``models/transformer_lm.py`` and, with
grouped K/V heads, ``ops/attention.causal_gqa_attention``) and the ring's
per-shard step (``flash_attention_step``).  No score leaves VMEM, forward or backward, and
neither K/V nor a score matrix is ever held whole: the grid walks (batch, K/V
head, query block, key block) with the key block innermost, and the running
maximum, the running sum and the float32 accumulator live in VMEM scratch.

Grouped heads: the ``group = Hq // Hkv`` query heads of a K/V head meet the
same K/V block in VMEM, stacked as rows — a block of ``block_q`` tokens is
``(group * block_q, D)`` against ``(block_k, D)`` — so K and V are read once
a group and never repeated in memory, and the backward sums dk and dv over
the group in its contraction.  Where a head is whole lanes (``D % 128 ==
0``) the kernels read ``q``, ``k``, ``v`` where they lie, ``(B, T, H * D)``
with a head as a block of lanes, and write ``o`` the same way; other widths
go heads-first, a K/V head's group side by side.

Causality by blocks: a key block wholly above the diagonal is neither
computed (``pl.when``) nor fetched (its index map stays on the last block
needed); the mask is applied on the blocks the diagonal crosses only.

A window (``flash_attention(.., window=W)``: query ``i`` sees the keys ``i -
W < j <= i``, causal self-attention) is a static specialisation too
(``_Shape.window``, 0 without one): the grid's inner axis spans the BAND, the
most key blocks a query block meets (``band``; of the two-pass backward's
dk/dv pass, the most query blocks a key block is seen by), and walks them
from the first the window reaches (``_first_key_block``;
``_last_query_block``), so the blocks outside the band are not grid steps
at all; a step past the band's end is skipped and its index map stays on
the band's last block.  The mask is the
window's and the diagonal's where either crosses a block.

A keep-mask (``masked_flash_attention``: the selected-key attention of
``ops/sparse_attention.py``) is a static specialisation of the same
kernels: one more operand, the selection as bits in ``ops/attention.
pack_mask``'s layout — key ``s`` is bit ``s // words`` of word ``s % words``,
so a key block of ``words`` keys is ONE bit of every word and a block of
``block_q`` queries' words, ``(block_q, words)`` int32, fetched once a query
block, holds the mask of every key block it meets.  The blocks above the
diagonal are skipped as before; every block that is met takes the masked
step with the tile unpacked in VMEM (an AND and a compare, the same for the
heads of a group) in place of the positions' iotas; the backward, whose
scores have the keys on rows, transposes the unpacked tile (32-bit, 2-D).

A second score term (``mla_flash_attention``: latent attention, whose key is
part per head, ``d`` wide, and part ONE rotary key shared by every head,
``rope`` wide, against values ``d`` wide) is the other static specialisation
(``_Shape.rope``, 0 without one): ``s = q k^T + q_rope k_rope^T``, two
products into one float32 tile, so the 192-wide score never exists as one
block and ``v`` and ``o`` stay ``d`` wide, read and written where they lie.
The rope queries come heads-first, ``(B, H, T, rope)`` (a block of 64 lanes
is whole only where 64 is the array's width), the rope key as it is, ``(B, T,
rope)``, the same block for every head and never repeated in memory; the
backward hands back ``dq_rope`` the same way and a float32 ``dk_rope`` a
head, ``(B, H, T, rope)``, summed over the heads in XLA.  On the 128 x 128
MXU the rope term's contraction fills half a pass: of the forward's three
passes a block 2.5 are asked for, of the backward's eight 6.5 (a 192-wide
block padded to 256 in VMEM would waste the same).

Backward: the FlashAttention recipe from the residuals ``(q, k, v, o, lse)``,
in ONE pass (``flash_attention_backward``).  It walks the forward's grid,
query block outer and key blocks inner, the blocks above the diagonal
skipped, and forms ``delta = rowsum(do * o) - dlse`` once a query block.  A
block pair's scores are formed once, transposed (``s^T = k q^T``, ``dp^T =
v do^T``), and with ``p`` and ``ds`` feed all three gradients: ``dv += p^T
do`` and ``dk += ds^T q`` into float32 accumulators that hold the K/V head's
every key in VMEM and are written when its last query block ends, ``dq^T +=
k^T ds^T`` into one that holds the query block and is written transposed
once: five products a block pair where a dq pass and a dk/dv pass run seven,
and one exponential where they run two.  Nothing accumulates in HBM.  Where
the accumulators of a K/V head, ``Tk x (2 d + rope) x 4`` bytes, pass
``BACKWARD_ACCUMULATOR_BYTES`` (a long ring shard), the backward is the two
passes (``backward_path`` says which): the dq pass (query block outer)
hands ``delta`` on to the dk/dv pass (key block outer), each rebuilding ``p
= exp(s - lse)``.  The ``dlse`` term makes ``(o, lse)`` an honest
differentiable pair, which is what lets the ring merge per-step partial
attentions.

Arithmetic: products take their operands in the compute dtype (the inputs')
and accumulate in float32 — float32 operands at ``Precision.HIGHEST`` —;
scores, mask, maximum, exponent and sums are float32.  The mask is finite
(-1e30: PERF.md section 6, PR 27); a row that sees no key at all (a ring step
ahead of the causal frontier) comes out ``(o = 0, lse = -inf)``.

Position bookkeeping is absolute: a traced ``(q_offset, k_offset)`` pair
rides in as scalar prefetch, so the same code serves the end-aligned dense
convention (``mha_reference``'s ``tril(k=tk-tq)`` — offset ``(tk - tq, 0)``)
and the ring's per-shard global positions.  Ragged lengths are end-padded to
whole blocks: padded keys are masked, padded query rows are sliced off (their
cotangents are zero) — only T_q=0 errors.

On non-TPU backends the kernels run in interpreter mode so tests pin
forward AND backward against ``mha_reference`` / ``jax.grad`` of it
everywhere.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lowerable() -> bool:
    """True when the Pallas kernels lower natively on this backend.
    The single source of truth for "custom kernels run here": serving
    decode, the LM train-step attention, the sequence models' delta
    rule, loss, expert and alignment kernels and the comm plane's fused
    epilogue all gate on this — TPU takes the kernel, everything else takes the dense/XLA reference, and
    interpreter mode stays a test-only tool (it is far slower than the
    XLA-compiled reference on CPU)."""
    return jax.default_backend() in ("tpu",)


F32 = jnp.float32
LANES = 128
MASK = -1e30  # finite: see the module docstring
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T without materializing b.T


def accepts(hq: int, hkv: int, d: int, dtype, rope: int = 0) -> bool:
    """What ``flash_attention`` is worth taking for: query heads in whole
    groups, a dtype the MXU takes, and heads of whole lanes (read in place)
    or a K/V head's group of whole lanes side by side (heads-first: four
    heads of 64 are 256 lanes of queries against 64 of keys).  With a second
    score term ``rope`` wide (``mla_flash_attention``: scores ``d + rope``
    wide against values ``d`` wide): a key head a query head, read in place,
    and a rope part of whole sublanes within one row of lanes."""
    if rope and not (hq == hkv and d % LANES == 0
                     and rope % 8 == 0 and rope <= LANES):
        return False
    return (hq % hkv == 0
            and (d % LANES == 0 or (hq // hkv * d) % LANES == 0)
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)))


class _Shape(NamedTuple):
    """What a kernel is specialised on.  ``q``, ``o``: ``(B, Tq, Hkv *
    group * d)``, ``k``, ``v``: ``(B, Tk, Hkv * d)``, both lengths whole
    blocks; ``tk`` counts the real keys.  ``words``: the words a row of the
    keep-mask, 0 without one.  ``rope``: the width of a second score term,
    0 without one (then ``group`` is 1).  ``window``: the keys a query sees,
    itself the last, 0 for all before it (then queries and keys are one
    sequence, ``tk`` long)."""
    causal: bool
    scale: float
    group: int
    d: int
    tk: int
    block_q: int
    block_k: int
    out_dtype: np.dtype
    interpret: bool
    words: int
    rope: int
    window: int


def _mm(a, b, dims):
    """A product as the docstring states it: the operands as they come,
    float32 ones at full precision, accumulated in float32."""
    full = a.dtype == F32 and b.dtype == F32
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if full else None)


# -- which blocks a pass meets ------------------------------------------------
# Block (i, j) holds the queries q_off + i block_q + [0, block_q) and the keys
# k_off + j block_k + [0, block_k).  These take ints, numpy arrays and traced
# scalars alike: the kernels, their index maps and ``blocks_met`` share them.
def _needed(i, j, q_off, k_off, block_q, block_k):
    """Some key of the block is visible to some query of it."""
    return k_off + j * block_k <= q_off + (i + 1) * block_q - 1


def _whole(i, j, q_off, k_off, block_q, block_k):
    """Every key of the block is visible to every query of it."""
    return k_off + (j + 1) * block_k - 1 <= q_off + i * block_q


def _reached(i, j, q_off, k_off, block_q, block_k, window):
    """Some key of the block lies within ``window`` of some query of it."""
    return k_off + (j + 1) * block_k - 1 > q_off + i * block_q - window


def _inside(i, j, q_off, k_off, block_q, block_k, window):
    """Every key of the block lies within ``window`` of every query of it."""
    return k_off + j * block_k > q_off + (i + 1) * block_q - 1 - window


def _met(tq: int, tk: int, block_q: int, block_k: int, window=None):
    """Which blocks causal attention (within ``window`` where given) over
    ``tq`` queries end-aligned to ``tk`` keys computes, ``(query blocks, key
    blocks)`` bool."""
    i = np.arange(-(-tq // block_q))[:, None]
    j = np.arange(-(-tk // block_k))[None, :]
    met = _needed(i, j, tk - tq, 0, block_q, block_k)
    if window:
        met = met & _reached(i, j, tk - tq, 0, block_q, block_k, window)
    return met


def blocks_met(tq: int, tk: int, block_q: int, block_k: int, window=None):
    """``(computed, total)`` key blocks of causal attention (within
    ``window`` where given) over ``tq`` queries end-aligned to ``tk`` keys:
    what the kernels' grid runs of the blocks the whole score matrix
    spans."""
    met = _met(tq, tk, block_q, block_k, window)
    return int(np.sum(met)), met.size


def band(t: int, block_q: int, block_k: int, window: int):
    """``(key blocks, query blocks)``: the most key blocks a query block
    meets, and the most query blocks a key block is seen by, under a
    ``window`` over one sequence of ``t``: the inner grid axis of the
    forward, the backward and the dq pass, and of the dk/dv pass."""
    met = _met(t, t, block_q, block_k, window)
    return int(met.sum(1).max()), int(met.sum(0).max())


def _last_key_block(i, offs, nk, c: _Shape):
    """The last key block query block ``i`` needs (block 0 if none)."""
    reach = offs[0] - offs[1] + (i + 1) * c.block_q - 1
    return jnp.minimum(jax.lax.div(jnp.maximum(reach, 0), c.block_k), nk - 1)


def _first_query_block(j, offs, nq, c: _Shape):
    """The first query block that sees key block ``j`` (the last if none)."""
    reach = offs[1] - offs[0] + j * c.block_k
    return jnp.minimum(jax.lax.div(jnp.maximum(reach, 0), c.block_q), nq - 1)


def _first_key_block(i, offs, c: _Shape):
    """The first key block within the window of query block ``i``."""
    reach = offs[0] - offs[1] + i * c.block_q - c.window + 1
    return jax.lax.div(jnp.maximum(reach, 0), c.block_k)


def _last_query_block(j, offs, nq, c: _Shape):
    """The last query block that key block ``j`` lies within the window of."""
    reach = offs[1] - offs[0] + (j + 1) * c.block_k - 1 + c.window - 1
    return jnp.minimum(jax.lax.div(reach, c.block_q), nq - 1)


def _blocks_of(c: _Shape):
    """``(query blocks, key blocks)`` of a windowed call: one sequence."""
    return -(-c.tk // c.block_q), -(-c.tk // c.block_k)


def _banded(offs_ref, outer, inner, key_outer: bool, c: _Shape):
    """Under a window, the grid's ``(outer, inner)`` step -> ``(query block,
    key block, in the band)``: the inner step counts from the band's first
    block."""
    nq, nk = _blocks_of(c)
    if key_outer:
        i = _first_query_block(outer, offs_ref, nq, c) + inner
        return i, outer, i <= _last_query_block(outer, offs_ref, nq, c)
    j = _first_key_block(outer, offs_ref, c) + inner
    return outer, j, j <= _last_key_block(outer, offs_ref, nk, c)


def _walk(offs_ref, i, j, nk, c: _Shape, step, in_band=None):
    """``step(masked)`` on block ``(i, j)``: not at all above the diagonal,
    with the mask where the diagonal or the end of the real keys crosses the
    block, without it elsewhere; under a keep-mask with it on every block
    met; under a window only ``in_band``, with the mask where the window's
    edge crosses the block too."""
    ragged = c.tk % c.block_k != 0  # the last key block holds padding
    if c.window:
        where = (i, j, offs_ref[0], offs_ref[1], c.block_q, c.block_k)
        whole = jnp.logical_and(
            _whole(*where), _inside(*where, c.window))
        if ragged:
            whole = jnp.logical_and(whole, j < nk - 1)
        pl.when(jnp.logical_and(in_band, whole))(lambda: step(False))
        pl.when(jnp.logical_and(in_band, jnp.logical_not(whole)))(
            lambda: step(True))
        return
    if not (c.causal or ragged):
        step(False)
        return
    whole = j < nk - 1 if ragged else True
    if not c.causal:
        pl.when(whole)(lambda: step(False))
        pl.when(jnp.logical_not(whole))(lambda: step(True))
        return
    where = (i, j, offs_ref[0], offs_ref[1], c.block_q, c.block_k)
    needed = _needed(*where)
    if c.words:
        pl.when(needed)(lambda: step(True))
        return
    whole = jnp.logical_and(whole, _whole(*where))
    pl.when(jnp.logical_and(needed, whole))(lambda: step(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(whole)))(
        lambda: step(True))


def _keep(offs_ref, bits_ref, i, j, c: _Shape, transposed: bool):
    """The block's mask, ``(group * block_q, block_k)`` (``transposed``: keys
    on rows): a real key, at or before the query's position; under a
    keep-mask its bits alone, a subset of those."""
    q_dim = 1 if transposed else 0
    if c.words:
        # key block j is bits j * per + [0, per) of every word, side by side
        per = c.block_k // c.words
        bits = bits_ref[...]
        tile = jnp.concatenate(
            [bits & jnp.left_shift(jnp.int32(1), j * per + r)
             for r in range(per)], axis=1)
        keep = (jnp.transpose(tile) if transposed else tile) != 0
    else:
        one = (c.block_k, c.block_q) if transposed else (c.block_q, c.block_k)
        key = jax.lax.broadcasted_iota(jnp.int32, one, 1 - q_dim)
        ahead = key - jax.lax.broadcasted_iota(jnp.int32, one, q_dim)
        keep = None
        if c.causal:
            behind = offs_ref[0] - offs_ref[1] + i * c.block_q - j * c.block_k
            keep = ahead <= behind
            if c.window:
                keep = jnp.logical_and(keep, ahead > behind - c.window)
        if c.tk % c.block_k:
            real = key < c.tk - j * c.block_k
            keep = real if keep is None else jnp.logical_and(keep, real)
    return jnp.concatenate([keep] * c.group, axis=q_dim)


def _bits_first(refs, c: _Shape):
    """A kernel's operands after the offsets: the keep-mask's block first
    where there is one."""
    return (refs[0], refs[1:]) if c.words else (None, refs)


def _rope_apart(refs, c: _Shape, *at):
    """The refs of the second score term, which sit at ``at`` among a
    kernel's where there is one, and the others; Nones without one."""
    if not c.rope:
        return (None,) * len(at), refs
    return (tuple(refs[i] for i in at),
            tuple(r for i, r in enumerate(refs) if i not in at))


def _scores(q, k, qr_ref, kr_ref, c: _Shape):
    """``q k^T`` (``k q^T`` for the backward and the dk/dv pass, which hand
    the key's side first) and, where there is one, the rope term into the
    same tile."""
    s = _mm(q, k, _NT)
    if c.rope:
        s = s + _mm(qr_ref[...], kr_ref[...], _NT)
    return s


# -- a K/V head's group, stacked as rows --------------------------------------
def _stacked(ref, c: _Shape):
    """``(block_q, group * d)`` -> ``(group * block_q, d)``, head after head."""
    if c.group == 1:
        return ref[...]
    return jnp.concatenate(
        [ref[:, g * c.d:(g + 1) * c.d] for g in range(c.group)], axis=0)


def _unstack_to(ref, x, c: _Shape):
    for g in range(c.group):
        ref[:, g * c.d:(g + 1) * c.d] = (
            x[g * c.block_q:(g + 1) * c.block_q].astype(ref.dtype))


# A per-row scalar lives in HBM with tokens on lanes, ``(group, block_q)`` a
# block; the forward and dq passes want it as a column beside the scores'
# rows, the backward and the dk/dv pass as a row above the transposed
# scores' columns.
def _row(ref, c: _Shape):
    """``(group, block_q)`` -> ``(1, group * block_q)``."""
    if c.group == 1:
        return ref[...]
    return jnp.concatenate([ref[g:g + 1, :] for g in range(c.group)], axis=1)


def _column(ref, c: _Shape):
    """``(group, block_q)`` -> ``(group * block_q, 1)``."""
    row = _row(ref, c)
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _as_row(col):
    """``(n, 1)`` -> ``(1, n)``."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _column_to(ref, col, c: _Shape):
    row = _as_row(col)
    for g in range(c.group):
        ref[g:g + 1, :] = row[:, g * c.block_q:(g + 1) * c.block_q]


# -- the kernels ----------------------------------------------------------------
def _fwd_kernel(offs_ref, *refs, c: _Shape):
    bits_ref, refs = _bits_first(refs, c)
    (qr_ref, kr_ref), refs = _rope_apart(refs, c, 3, 4)  # the last inputs
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    i, j, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    inner, n_inner, in_band = j, nk, None
    if c.window:
        i, j, in_band = _banded(offs_ref, i, j, False, c)
        nk = _blocks_of(c)[1]

    @pl.when(inner == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASK, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def step(masked):
        s = _scores(_stacked(q_ref, c), k_ref[...], qr_ref, kr_ref, c)
        if c.scale != 1.0:
            s = s * c.scale
        if masked:
            keep = _keep(offs_ref, bits_ref, i, j, c, False)
            s = jnp.where(keep, s, MASK)
        m_prev = m_ref[...]
        m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m)
        if masked:  # a row that has met no key yet has m == MASK
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _mm(
            p.astype(v_ref.dtype), v_ref[...], _NN)
        m_ref[...] = m

    _walk(offs_ref, i, j, nk, c, step, in_band)

    @pl.when(inner == n_inner - 1)
    def _():
        l = l_ref[...]
        seen = l > 0.0
        l = jnp.where(seen, l, 1.0)
        _unstack_to(o_ref, acc_ref[...] * (1.0 / l), c)
        _column_to(lse_ref,
                   jnp.where(seen, m_ref[...] + jnp.log(l), -jnp.inf), c)


def _probabilities(s, lse, keep, c: _Shape):
    """``p = exp(s - lse)`` from the scores as the product left them; a
    masked entry is 0 whatever its row's ``lse`` (``-inf`` where the row saw
    no key)."""
    if c.scale != 1.0:
        s = s * c.scale
    p = jnp.exp(s - lse)
    return p if keep is None else jnp.where(keep, p, 0.0)


def _dq_kernel(offs_ref, *refs, c: _Shape):
    bits_ref, refs = _bits_first(refs, c)
    # the rope term's: the last inputs, the last output, the last scratch
    (qr_ref, kr_ref, dqr_ref, dqr_scr), refs = _rope_apart(
        refs, c, 7, 8, 11, 16)
    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref,
     dq_ref, delta_ref, do_scr, lse_scr, delta_scr, dq_scr) = refs
    i, j, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    inner, n_inner, in_band = j, nk, None
    if c.window:
        i, j, in_band = _banded(offs_ref, i, j, False, c)
        nk = _blocks_of(c)[1]

    @pl.when(inner == 0)
    def _():
        do = _stacked(do_ref, c)
        delta = jnp.sum(do.astype(F32) * _stacked(o_ref, c).astype(F32),
                        axis=1, keepdims=True) - _column(dlse_ref, c)
        _column_to(delta_ref, delta, c)
        delta_scr[...] = delta
        lse_scr[...] = _column(lse_ref, c)
        do_scr[...] = do.astype(do_scr.dtype)
        dq_scr[...] = jnp.zeros(dq_scr.shape, F32)
        if c.rope:
            dqr_scr[...] = jnp.zeros(dqr_scr.shape, F32)

    def step(masked):
        k = k_ref[...]
        keep = _keep(offs_ref, bits_ref, i, j, c, False) if masked else None
        p = _probabilities(_scores(_stacked(q_ref, c), k, qr_ref, kr_ref, c),
                           lse_scr[...], keep, c)
        ds = p * (_mm(do_scr[...], v_ref[...], _NT) - delta_scr[...])
        if c.scale != 1.0:
            ds = ds * c.scale
        dq_scr[...] += _mm(ds.astype(k.dtype), k, _NN)
        if c.rope:
            dqr_scr[...] += _mm(ds.astype(k.dtype), kr_ref[...], _NN)

    _walk(offs_ref, i, j, nk, c, step, in_band)

    @pl.when(inner == n_inner - 1)
    def _():
        _unstack_to(dq_ref, dq_scr[...], c)
        if c.rope:
            dqr_ref[...] = dqr_scr[...].astype(dqr_ref.dtype)


def _dkv_kernel(offs_ref, *refs, c: _Shape):
    bits_ref, refs = _bits_first(refs, c)
    # the rope term's: the last inputs, the last output, the last scratch
    (qr_ref, kr_ref, dkr_ref, dkr_scr), refs = _rope_apart(
        refs, c, 6, 7, 10, 13)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = refs
    # key block outer, query blocks inner; the scores transposed, keys on rows
    j, i, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    inner, n_inner, in_band = i, nq, None
    if c.window:
        i, j, in_band = _banded(offs_ref, j, i, True, c)

    @pl.when(inner == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, F32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, F32)
        if c.rope:
            dkr_scr[...] = jnp.zeros(dkr_scr.shape, F32)

    def step(masked):
        q, do = _stacked(q_ref, c), _stacked(do_ref, c)
        keep = _keep(offs_ref, bits_ref, i, j, c, True) if masked else None
        p = _probabilities(_scores(k_ref[...], q, kr_ref, qr_ref, c),
                           _row(lse_ref, c), keep, c)
        dv_scr[...] += _mm(p.astype(do.dtype), do, _NN)
        ds = p * (_mm(v_ref[...], do, _NT) - _row(delta_ref, c))
        if c.scale != 1.0:
            ds = ds * c.scale
        dk_scr[...] += _mm(ds.astype(q.dtype), q, _NN)  # sums over the group
        if c.rope:  # this head's part of the shared key's gradient
            dkr_scr[...] += _mm(ds.astype(q.dtype), qr_ref[...], _NN)

    _walk(offs_ref, i, j, pl.num_programs(2), c, step, in_band)

    @pl.when(inner == n_inner - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
        if c.rope:
            dkr_ref[...] = dkr_scr[...]


def _bwd_kernel(offs_ref, *refs, c: _Shape):
    bits_ref, refs = _bits_first(refs, c)
    # the rope term's: the last inputs, the last outputs, the last scratch
    (qr_ref, kr_ref, dqr_ref, dkr_ref, dqr_scr), refs = _rope_apart(
        refs, c, 7, 8, 12, 13, 19)
    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref, dq_ref, dk_ref,
     dv_ref, q_scr, do_scr, lse_scr, delta_scr, dq_scr, *acc) = refs
    # float32 dk / dv accumulate in their outputs, others in scratch
    dk_acc, dv_acc = acc or (dk_ref, dv_ref)
    # query block outer, key blocks inner, as the dq pass walks them; the
    # scores transposed, keys on rows, as the dk/dv pass forms them
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    i, j, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    inner, n_inner, in_band = j, nk, None
    if c.window:
        i, j, in_band = _banded(offs_ref, i, j, False, c)
        nk = _blocks_of(c)[1]

    @pl.when(jnp.logical_and(first, inner == 0))
    def _():  # a K/V head's dk, dv over every key
        dk_acc[...] = jnp.zeros(dk_acc.shape, F32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, F32)
        if c.rope:
            dkr_ref[...] = jnp.zeros(dkr_ref.shape, F32)

    @pl.when(inner == 0)
    def _():
        do = _stacked(do_ref, c)
        delta = jnp.sum(do.astype(F32) * _stacked(o_ref, c).astype(F32),
                        axis=1, keepdims=True)
        delta_scr[...] = _as_row(delta) - _row(dlse_ref, c)
        lse_scr[...] = _row(lse_ref, c)
        q_scr[...] = _stacked(q_ref, c)
        do_scr[...] = do.astype(do_scr.dtype)
        dq_scr[...] = jnp.zeros(dq_scr.shape, F32)
        if c.rope:
            dqr_scr[...] = jnp.zeros(dqr_scr.shape, F32)

    def step(masked):
        q, do, k = q_scr[...], do_scr[...], k_ref[...]
        keep = _keep(offs_ref, bits_ref, i, j, c, True) if masked else None
        p = _probabilities(_scores(k, q, kr_ref, qr_ref, c), lse_scr[...],
                           keep, c)
        ds = p * (_mm(v_ref[...], do, _NT) - delta_scr[...])
        if c.scale != 1.0:
            ds = ds * c.scale
        ds = ds.astype(q.dtype)
        keys = pl.ds(pl.multiple_of(j * c.block_k, c.block_k), c.block_k)
        dv_acc[keys, :] += _mm(p.astype(do.dtype), do, _NN)
        dk_acc[keys, :] += _mm(ds, q, _NN)  # sums over the group
        dq_scr[...] += _mm(jnp.transpose(k), ds, _NN)  # dq^T, (d, rows)
        if c.rope:  # this head's part of the shared key's gradient
            dkr_ref[keys, :] += _mm(ds, qr_ref[...], _NN)
            dqr_scr[...] += _mm(jnp.transpose(kr_ref[...]), ds, _NN)

    _walk(offs_ref, i, j, nk, c, step, in_band)

    @pl.when(inner == n_inner - 1)
    def _():
        _unstack_to(dq_ref, jnp.transpose(dq_scr[...]), c)
        if c.rope:
            dqr_ref[...] = jnp.transpose(dqr_scr[...]).astype(dqr_ref.dtype)

    if acc:
        @pl.when(jnp.logical_and(last, inner == n_inner - 1))
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# Scoped-VMEM ceiling handed to Mosaic.  The default (16 MiB on v5e) is
# less than a block of grouped heads with its float32 scores takes, well
# before the chip's 128 MiB runs out.
VMEM_LIMIT_BYTES = 100 << 20
BLOCK_K = 512  # keys a block, where the caller does not say
# What the one-pass backward holds in VMEM for a whole K/V head beside its
# blocks: dk and dv (and a head's part of dk_rope) over every key, float32;
# with the outputs they are written to, double-buffered, twice this in
# either dtype.  16 MiB is 16,384 keys of heads of 128.
BACKWARD_ACCUMULATOR_BYTES = 16 << 20


def backward_path(tk: int, d: int, rope: int = 0, block_k: int = BLOCK_K):
    """``(path, why)``: which backward the kernels take over ``tk`` keys
    (padded to whole blocks of ``block_k``) of heads ``d`` wide with a
    second score term ``rope`` wide: ``"fused"``, one pass, where a K/V
    head's float32 dk, dv and dk_rope over every key fit
    ``BACKWARD_ACCUMULATOR_BYTES``; ``"two_pass"``, the dq and dk/dv
    passes, with the reason, where they do not."""
    keys = -(-tk // block_k) * block_k
    need = keys * (2 * d + rope) * 4
    if need > BACKWARD_ACCUMULATOR_BYTES:
        return "two_pass", (
            f"a K/V head's dk/dv over {keys} keys is {need >> 20} MiB of "
            f"VMEM, over {BACKWARD_ACCUMULATOR_BYTES >> 20}")
    return "fused", ""


def _compiler_params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES
    )


def _out_struct(shape, dtype, *operands):
    """Output aval for a ``pallas_call``.  Under ``shard_map`` the
    output's varying mesh axes must be declared; they are the union of
    the operands' (the empty set outside ``shard_map``)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# whole small array of scalars (offsets, lengths) in scalar memory
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# -- the calls ------------------------------------------------------------------
def _call(kernel, name, c: _Shape, key_outer, ins, outs, scratch, offs, keep,
          *operands, outer="parallel"):
    """One kernel over (batch, K/V head, outer block, inner block), the inner
    blocks one after another.  ``ins`` / ``outs``: a letter an operand —
    ``q`` a block of queries' rows ``(block_q, group * d)``, ``k`` a block of
    keys' ``(block_k, d)``, ``K`` a K/V head's every key ``(Tk, d)``, ``r`` a
    per-row scalar ``(group, block_q)``; of the rope term ``a`` a block of a
    head's queries ``(block_q, rope)`` of ``(B, H, Tq, rope)``, ``b`` a block
    of the ONE key ``(block_k, rope)`` of ``(B, Tk, rope)``, ``c`` a block of
    a head's part of that key's gradient and ``C`` all of it, of ``(B, H,
    Tk, rope)`` — and for an output its dtype.  ``keep``: the keep-mask's
    words ``(B, Tq, words)`` int32 or None; a block of queries' whole rows of
    it goes first, fetched again only when the query block changes.
    ``outer``: the outer block's axis semantics, ``"arbitrary"`` where a
    kernel accumulates over it."""
    b, tq = operands[0].shape[:2]
    tk = operands[1].shape[1]
    hkv = operands[1].shape[2] // c.d
    nq, nk = tq // c.block_q, tk // c.block_k

    def blocks(x, y):  # the grid's last two axes -> (query block, key block)
        i, j = (y, x) if key_outer else (x, y)
        return i, j

    def q_block(bi, hi, x, y, offs_ref):
        i, j = blocks(x, y)
        if c.window and key_outer:  # the band's steps; past its end, its last
            i = jnp.minimum(_first_query_block(j, offs_ref, nq, c) + i,
                            _last_query_block(j, offs_ref, nq, c))
        elif c.causal and key_outer:  # query blocks before the first: not fetched
            i = jnp.maximum(i, _first_query_block(j, offs_ref, nq, c))
        return i

    def k_block(bi, hi, x, y, offs_ref):
        i, j = blocks(x, y)
        if c.window and not key_outer:  # as q_block's
            j = jnp.minimum(_first_key_block(i, offs_ref, c) + j,
                            _last_key_block(i, offs_ref, nk, c))
        elif c.causal and not key_outer:  # key blocks after the last: not fetched
            j = jnp.minimum(j, _last_key_block(i, offs_ref, nk, c))
        return j

    specs = {
        "q": (pl.BlockSpec((None, c.block_q, c.group * c.d),
                           lambda *g: (g[0], q_block(*g), g[1])),
              (b, tq, hkv * c.group * c.d)),
        "k": (pl.BlockSpec((None, c.block_k, c.d),
                           lambda *g: (g[0], k_block(*g), g[1])),
              (b, tk, hkv * c.d)),
        "K": (pl.BlockSpec((None, tk, c.d), lambda *g: (g[0], 0, g[1])),
              (b, tk, hkv * c.d)),
        "r": (pl.BlockSpec((None, None, c.group, c.block_q),
                           lambda *g: (g[0], g[1], 0, q_block(*g))),
              (b, hkv, c.group, tq)),
    }
    if c.rope:
        specs.update({
            "a": (pl.BlockSpec((None, None, c.block_q, c.rope),
                               lambda *g: (g[0], g[1], q_block(*g), 0)),
                  (b, hkv, tq, c.rope)),
            "b": (pl.BlockSpec((None, c.block_k, c.rope),
                               lambda *g: (g[0], k_block(*g), 0)),
                  (b, tk, c.rope)),
            "c": (pl.BlockSpec((None, None, c.block_k, c.rope),
                               lambda *g: (g[0], g[1], k_block(*g), 0)),
                  (b, hkv, tk, c.rope)),
            "C": (pl.BlockSpec((None, None, tk, c.rope),
                               lambda *g: (g[0], g[1], 0, 0)),
                  (b, hkv, tk, c.rope)),
        })
    in_specs = [specs[x][0] for x in ins]
    if c.words:  # heads-first, a sequence's K/V heads share its mask
        per_mask = b // keep.shape[0]
        mask_spec = pl.BlockSpec(
            (None, c.block_q, c.words),
            lambda *g: (g[0] // per_mask, q_block(*g), 0))
        in_specs = [mask_spec] + in_specs
        operands = (keep, *operands)
    everything = (offs, *operands)
    inner = nq if key_outer else nk
    if c.window:  # the band's blocks alone
        inner = band(c.tk, c.block_q, c.block_k, c.window)[int(key_outer)]
    return pl.pallas_call(
        partial(kernel, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nk, inner) if key_outer else (b, hkv, nq, inner),
            in_specs=in_specs,
            out_specs=[specs[x][0] for x, _ in outs],
            scratch_shapes=scratch),
        out_shape=[_out_struct(specs[x][1], dtype, *everything)
                   for x, dtype in outs],
        compiler_params=_compiler_params(
            "parallel", "parallel", outer, "arbitrary"),
        interpret=c.interpret,
        name=name,
    )(offs, *operands)


def _forward(q, k, v, offs, keep, rope, c: _Shape):
    rows = c.group * c.block_q
    ab, rope = ("ab", rope) if rope else ("", ())
    return _call(
        _fwd_kernel, "flash_attention_forward", c, False, "qkk" + ab,
        [("q", c.out_dtype), ("r", F32)],
        [pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, 1), F32),
         pltpu.VMEM((rows, c.d), F32)],
        offs, keep, q, k, v, *rope)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash_core(q, k, v, offs, keep, rope, c: _Shape):
    """``(o, lse)`` from ``q`` ``(B, Tq, Hkv * group * d)`` and ``k``, ``v``
    ``(B, Tk, Hkv * d)``, both lengths whole blocks.  ``offs`` is the int32
    (2,) absolute (q_offset, k_offset) pair; ``keep`` the keep-mask's words
    (``_call``) or None; ``rope`` the second score term's ``(q_rope (B, H,
    Tq, rope), k_rope (B, Tk, rope))`` or None; ``lse`` is ``(B, Hkv, group,
    Tq)`` float32, tokens on lanes.  Differentiable in q/k/v and the rope
    pair AND honest in the lse output (nonzero dlse cotangents — the ring
    merge — feed the backward's delta term)."""
    return _forward(q, k, v, offs, keep, rope, c)


# What a ``jax.checkpoint`` around the caller may keep (``policy=jax.
# checkpoint_policies.save_only_these_names(*SAVED)``) so that its
# recomputation does not run the forward kernel a second time.
SAVED = ("flash_attention_o", "flash_attention_lse")


def _flash_core_fwd(q, k, v, offs, keep, rope, c):
    o, lse = _forward(q, k, v, offs, keep, rope, c)
    o, lse = (checkpoint_name(x, name) for x, name in zip((o, lse), SAVED))
    return (o, lse), (q, k, v, offs, keep, rope, o, lse)


def _flash_core_bwd(c, res, cts):
    q, k, v, offs, keep, rope, o, lse = res
    do, dlse = cts
    rows = c.group * c.block_q
    # the rope term's inputs, outputs and scratch come last (``_rope_apart``)
    ab, parts, n = ("ab", rope, 1) if rope else ("", (), 0)
    if backward_path(k.shape[1], c.d, c.rope, c.block_k)[0] == "fused":
        dq, dk, dv, *dr = _call(
            _bwd_kernel, "flash_attention_backward", c, False,
            "qkkqqrr" + ab,
            [("q", q.dtype), ("K", k.dtype), ("K", v.dtype)]
            + [("a", q.dtype), ("C", F32)] * n,
            [pltpu.VMEM((rows, c.d), q.dtype)] * 2
            + [pltpu.VMEM((1, rows), F32)] * 2
            + [pltpu.VMEM((c.d, rows), F32)]
            + [pltpu.VMEM((c.rope, rows), F32)] * n
            + [pltpu.VMEM((k.shape[1], c.d), F32)] * 2 * (k.dtype != F32),
            offs, keep, q, k, v, do, o, lse, dlse.astype(F32), *parts,
            outer="arbitrary")
        return _cotangents(dq, dk, dv, rope, *dr)
    dq, delta, *dqr = _call(
        _dq_kernel, "flash_attention_dq", c, False, "qkkqqrr" + ab,
        [("q", q.dtype), ("r", F32)] + [("a", q.dtype)] * n,
        [pltpu.VMEM((rows, c.d), q.dtype), pltpu.VMEM((rows, 1), F32),
         pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, c.d), F32)]
        + [pltpu.VMEM((rows, c.rope), F32)] * n,
        offs, keep, q, k, v, do, o, lse, dlse.astype(F32), *parts)
    dk, dv, *dkr = _call(
        _dkv_kernel, "flash_attention_dkv", c, True, "qkkqrr" + ab,
        [("k", k.dtype), ("k", v.dtype)] + [("c", F32)] * n,
        [pltpu.VMEM((c.block_k, c.d), F32)] * 2
        + [pltpu.VMEM((c.block_k, c.rope), F32)] * n,
        offs, keep, q, k, v, do.astype(q.dtype), lse, delta, *parts)
    return _cotangents(dq, dk, dv, rope, *dqr, *dkr)


def _cotangents(dq, dk, dv, rope, dqr=None, dkr=None):
    if rope:  # the shared key's gradient: the heads' parts, summed in float32
        rope = dqr, jnp.sum(dkr, axis=1).astype(rope[1].dtype)
    # the integer offsets and the mask's bits carry no cotangent
    return dq, dk, dv, None, None, rope


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# -- layouts ----------------------------------------------------------------------
def _heads_first(x, hkv):
    """``(B, T, H, D)`` -> ``(B * hkv, T, (H // hkv) * D)``: a K/V head's
    group side by side, for head widths that are not whole lanes."""
    b, t, h, d = x.shape
    x = x.reshape(b, t, hkv, h // hkv, d)
    return jnp.transpose(x, (0, 2, 1, 3, 4)).reshape(b * hkv, t, -1)


def _pad_rows(x, block):
    """End-pad ``(B, T, ...)`` to whole blocks of rows."""
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x


def _attend(q, k, v, offs, causal, block_q, block_k, interpret, scale,
            out_dtype, keep=None, rope=None, window=None):
    """``(o, lse)`` of ``(B, Tq, Hq, D)`` against ``(B, Tk, Hkv, D)``, as
    ``(B, Tq, Hq, D)`` and ``(B, Hq, Tq)``; ``keep``: a keep-mask's bits
    ``(B, Tq, words)`` uint32 (``masked_flash_attention``); ``rope``: a
    second score term's ``(q_rope (B, Tq, Hq, R), k_rope (B, Tk, R))``
    (``mla_flash_attention``); ``window``: the keys a query sees, itself
    the last (``flash_attention``)."""
    if interpret is None:
        interpret = not lowerable()
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1:3]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not divide by {hkv} K/V heads")
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    if window is not None and not (causal and tq == tk and keep is None
                                   and rope is None and window >= 1):
        raise ValueError(
            f"a window of {window} wants causal self-attention without a "
            f"keep-mask or a second score term: T {tq} x {tk}")
    width = 0
    if rope is not None:
        width = rope[0].shape[-1]
        if not accepts(hq, hkv, d, q.dtype, width):
            raise ValueError(
                f"a second score term of {width} wants a key head a query "
                f"head of whole lanes: {hq} / {hkv} heads of {d}")
        # heads first: a block of `width` lanes is whole where the array ends
        rope = (jnp.transpose(_pad_rows(rope[0], block_q), (0, 2, 1, 3)),
                _pad_rows(rope[1], block_k))
    words = 0
    if keep is not None:
        words = keep.shape[-1]
        if not (causal and tq == tk and tq % block_q == 0
                and tk % block_k == 0 and block_k % words == 0
                and tk <= 32 * words):
            raise ValueError(
                f"a keep-mask of {words} words a row wants causal "
                f"self-attention in whole blocks of whole words: T {tq} x "
                f"{tk}, blocks {block_q} x {block_k}")
        keep = jax.lax.bitcast_convert_type(keep, jnp.int32)
    in_place = d % LANES == 0
    if in_place:  # a head is a block of lanes
        flat = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
    else:
        flat = partial(_heads_first, hkv=hkv)
    c = _Shape(bool(causal), float(d ** -0.5 if scale is None else scale),
               hq // hkv, d, tk, block_q, block_k,
               np.dtype(out_dtype or q.dtype), bool(interpret), words, width,
               int(window or 0))
    o, lse = _flash_core(
        _pad_rows(flat(q), block_q), _pad_rows(flat(k), block_k),
        _pad_rows(flat(v), block_k), offs, keep, rope, c)
    o, lse = o[:, :tq], lse[..., :tq]
    if in_place:
        return o.reshape(b, tq, hq, d), lse.reshape(b, hq, tq)
    o = o.reshape(b, hkv, tq, hq // hkv, d)
    return (jnp.transpose(o, (0, 2, 1, 3, 4)).reshape(b, tq, hq, d),
            lse.reshape(b, hq, tq))


def flash_attention(
    q, k, v, causal: bool = False, block_q: int = 128, interpret=None,
    *, block_k: int = BLOCK_K, scale=None, out_dtype=None, window=None
):
    """Fused attention on ``q`` (B, T, Hq, D) and ``k``, ``v`` (B, T, Hkv,
    D) — K/V head ``j`` serves query heads ``[j, j + 1) * Hq // Hkv`` —
    with a fused flash backward; bit-comparable to ``mha_reference`` (same
    softmax, same end-aligned ``tril(k=tk-tq)`` causal convention, fp32
    accumulation) and grad-pinned against ``jax.grad`` of it.  Any T_q >= 1
    works — ragged lengths are end-padded to whole blocks internally.
    ``scale`` multiplies the scores (default ``D ** -0.5``); the output
    takes ``out_dtype`` (default: the inputs').  ``window`` (causal
    self-attention): query ``i`` sees the keys ``i - window < j <= i``, and
    the key blocks outside that band are neither computed nor fetched."""
    tq, tk = q.shape[1], k.shape[1]
    if tq == 0:
        raise ValueError(
            "flash_attention: T_q=0 — an empty query block has no "
            "attention output (check the caller's slicing)"
        )
    offs = jnp.asarray([tk - tq, 0], jnp.int32)
    return _attend(q, k, v, offs, causal, block_q, block_k, interpret,
                   scale, out_dtype, window=window)[0]


def masked_flash_attention(q, k, v, keep, *, block_q: int, block_k: int,
                           interpret=None, scale=None, out_dtype=None):
    """Causal self-attention over the keys a keep-mask holds: ``flash_
    attention``'s kernels and layouts given ``keep``, ``(B, T, words)``
    uint32 in ``ops/attention.pack_mask``'s layout, a subset of the causal
    keys (a bit above the diagonal in a block the diagonal crosses WOULD be
    attended).  ``T`` is whole blocks and ``block_k`` whole rows of words.
    Returns ``(o (B, T, Hq, D), lse (B, Hq, T) float32)``, both
    differentiable; a row that keeps no key comes out ``(0, -inf)``."""
    return _attend(q, k, v, jnp.zeros((2,), jnp.int32), True, block_q,
                   block_k, interpret, scale, out_dtype, keep)


def mla_flash_attention(q_nope, q_rope, k_nope, k_rope, v, *, block_q: int,
                        block_k: int = BLOCK_K, interpret=None, scale=None,
                        out_dtype=None):
    """Causal self-attention whose score is two terms, ``q_nope . k_nope +
    q_rope . k_rope`` (latent attention: ``q_nope``, ``k_nope``, ``v`` ``(B,
    T, H, D)`` with ``D`` whole lanes, ``q_rope`` ``(B, T, H, R)`` against
    the ONE ``k_rope`` ``(B, T, R)`` every head shares): ``flash_attention``'s
    kernels and layouts given the second term (the module docstring), the
    values and the output ``D`` wide.  ``scale`` multiplies the scores
    (default ``(D + R) ** -0.5``).  Returns ``(B, T, H, D)``."""
    d_qk = q_nope.shape[-1] + q_rope.shape[-1]
    return _attend(q_nope, k_nope, v, jnp.zeros((2,), jnp.int32), True,
                   block_q, block_k, interpret,
                   d_qk ** -0.5 if scale is None else scale, out_dtype,
                   rope=(q_rope, k_rope))[0]


def flash_attention_step(
    q, k, v, q_offset, k_offset, causal: bool = False,
    block_q: int = 128, interpret=None
):
    """One partial-attention step over a KV shard, for the ring path.

    ``q``/``k``/``v`` are (B, T_q, H, D)/(B, T_k, H, D) local shards;
    ``q_offset``/``k_offset`` are ABSOLUTE global positions of their
    first rows (traced scalars — ring-index arithmetic).  Returns
    ``(o (B, H, T_q, D), lse (B, H, T_q))`` — normalized within the
    shard, with the row logsumexp so the caller can merge steps via
    the online-softmax combine; a fully-masked row is (0, -inf).
    Gradients are exact through BOTH outputs (the dlse term)."""
    if causal:
        offs = jnp.stack(
            [jnp.asarray(q_offset, jnp.int32),
             jnp.asarray(k_offset, jnp.int32)]
        )
    else:
        # non-causal kernels never read the offsets; keeping the traced
        # axis-index arithmetic out of the (DCE'd) operand sidesteps an
        # XLA SPMD PartitionId lowering bug under shard_map
        offs = jnp.zeros((2,), jnp.int32)
    o, lse = _attend(q, k, v, offs, causal, block_q, BLOCK_K, interpret,
                     scale=None, out_dtype=None)
    return jnp.transpose(o, (0, 2, 1, 3)), lse


# ----------------------------------------------------------------------
# Decode attention: q_len == 1 over a (possibly over-allocated) context
# ----------------------------------------------------------------------
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale, s):
    q = q_ref[0]  # (1, d)
    k = k_ref[0]  # (s, d)
    n = len_ref[pl.program_id(0)]
    scores = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32) * scale
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    scores = jnp.where(k_pos < n, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    o = jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    o_ref[0] = (o / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _decode_reference(q, k, v, lengths=None):
    """Dense masked decode attention — the non-TPU fallback and the
    correctness pin for the kernel path.  Shapes as
    ``decode_attention``."""
    b, _, h, d = q.shape
    s = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    def bhtd(x):
        return jnp.transpose(x, (0, 2, 1, 3)).astype(jnp.float32)

    scores = jnp.einsum("bhqd,bhkd->bhqk", bhtd(q), bhtd(k)) * scale
    if lengths is not None:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (b, h, 1, s), 3)
        scores = jnp.where(
            k_pos < lengths.astype(jnp.int32)[:, None, None, None],
            scores,
            -jnp.inf,
        )
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, bhtd(v))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def decode_attention(q, k, v, lengths=None, interpret=None):
    """Single-position attention for autoregressive decode.

    ``q`` is (B, 1, H, D) — the one new position per sequence; ``k``/``v``
    are (B, S, H, D) gathered context where only the first ``lengths[b]``
    rows of sequence b are valid (the paged-KV gather over-allocates to
    the static S).  ``lengths`` None means the whole context is valid.

    Routing: the Pallas kernel where it lowers natively
    (``lowerable()``, i.e. TPU), the dense masked reference elsewhere;
    ``interpret=True`` forces the kernel in interpreter mode so CPU
    tests can pin the kernel itself against the reference."""
    b, tq, h, d = q.shape
    if tq != 1:
        raise ValueError(f"decode_attention wants q_len=1, got {tq}")
    s = k.shape[1]
    if not (lowerable() or interpret):
        return _decode_reference(q, k, v, lengths)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    scale = 1.0 / math.sqrt(d)

    def flat(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)

    # one grid cell per (batch*head); the sequence lengths ride in as
    # an int32 vector in SMEM, each cell reads its own entry as a scalar
    len_bh = jnp.repeat(lengths.astype(jnp.int32), h)
    kernel = partial(_decode_kernel, scale=scale, s=s)
    out = pl.pallas_call(
        kernel,
        grid=(b * h,),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        compiler_params=_compiler_params("parallel"),
        interpret=bool(interpret),
    )(len_bh, flat(q), flat(k), flat(v))
    return jnp.transpose(out.reshape(b, h, 1, d), (0, 2, 1, 3))
