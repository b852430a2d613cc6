"""The next-token loss's pass over the vocabulary as two Pallas kernels —
what ``ops/lm_loss._xla_nll_sum`` computes (``-sum log_softmax(x @ head)
[target]``) without a ``(rows, vocab)`` float32 tensor anywhere: no logit
leaves VMEM in float32, forward or backward.

Forward (``lm_loss_forward``): the grid walks (row block, vocabulary block)
with the vocabulary innermost.  A step forms the block's logits on the MXU,
``x_blk @ head_blk`` with float32 accumulation, and folds them into a running
row maximum and sum (an online logsumexp) and, by an iota compare, the
target's logit, all three float32 in VMEM scratch; the last step writes the
row's ``lse`` and its loss ``lse - logit[target]``.

Backward (``lm_loss_backward``): one kernel over the same blocks recomputes
the logits and writes ``(exp(logit - lse) - onehot(target)) * ct`` once, in
the compute dtype (268 MB at 16,384 x 8,192 in bfloat16); the two gradient
products, ``dx = g @ head^T`` and ``dhead = x^T @ g``, are XLA's, which
contracts either layout of the head where it lies: four products in all,
where two flash-style kernels that each recompute the logits make five.  The
residuals are the operands and ``lse`` ``(rows, 1)``.

The head is read as it lies, ``(E, vocab)`` or ``(vocab, E)`` (a tied head is
the embedding): the product contracts over the width either way and no
transpose is materialised.  A vocabulary that is not whole blocks (18,992 =
18 x 1,024 + 560) needs no padded copy: the last block hangs over the edge,
its columns past the vocabulary are masked with the finite ``MASK`` in the
forward kernel (PERF.md section 6, PR 27) and are never written by the
backward's, whose ``g`` is ``(rows, vocab)``.

Arithmetic: the products take their operands in the compute dtype (the
operands') and accumulate in float32 — float32 operands at
``Precision.HIGHEST`` —; maximum, exponential, sum, ``lse`` and the target's
logit are float32; ``g`` is rounded to the compute dtype once, as an operand
of the gradient products, which accumulate in float32 and are returned so.

Off the TPU the kernels run in interpreter mode (tests only: the program
takes ``lm_loss._xla_nll_sum`` there, ``lm_loss.nll_sum`` selects).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops.lm_loss import SAVED
from sparknet_tpu.ops.pallas_attention import (
    F32,
    LANES,
    MASK,
    _NN,
    _NT,
    _compiler_params,
    _mm,
    _out_struct,
    lowerable,
)

_TN = (((0,), (0,)), ((), ()))  # a.T @ b without materializing a.T

# The kernels' blocks, rows x vocabulary.  On the v5e at 16,384 rows of 2,048
# in bfloat16, forward + backward with the two gradient products: 12.40 ms at
# 8,192 columns and 28.69 at 18,992; 1,024 rows 12.38 / 32.67, 2,048 columns
# 12.31 / 29.11, 512 columns 12.62 / 29.16 at 1,024 rows (PERF.md section 6,
# PR 32).
BLOCK_ROWS = 512
BLOCK_VOCAB = 1024


def blocks(rows: int, vocab: int):
    """``(block_rows, block_vocab)`` the kernels walk ``rows`` x ``vocab``
    in: the vocabulary's in whole lanes, the last hanging over its edge."""
    return min(BLOCK_ROWS, rows), min(BLOCK_VOCAB, -(-vocab // LANES) * LANES)


# What ``accepts`` asks, as ``loss_path`` tells it of a shape turned away.  The
# kernels themselves take a vocabulary with a ragged tail (``nll_rows``), and
# alone are faster there than XLA (28.7 ms against 40.7 at 18,992 columns);
# but in qwen3next-train-8k, a program at the edge of the chip's memory, any
# other loss than the parent's made XLA's memory scheduler choose its
# depth-first order over its list order, which holds every weight gradient's
# operands until the step's end: 6.8 -> 10.4 GiB of temporaries, XLA's own
# rematerialisation, ``gdn_device_ms`` +49 (PERF.md section 6, PR 32).
ACCEPTS = ("the kernels take a width and a vocabulary of whole lanes, rows in "
           "whole blocks, bfloat16 or float32")


def accepts(rows: int, width: int, vocab: int, dtype) -> bool:
    """The shapes ``lm_loss.nll_sum`` hands the kernels (``ACCEPTS``): a
    width and a vocabulary of whole lanes, rows in whole blocks of whole
    register tiles, a dtype the MXU takes."""
    block_rows, _ = blocks(rows, vocab)
    return (width % LANES == 0 and vocab % LANES == 0
            and block_rows % 16 == 0 and rows % block_rows == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)))


class _Shape(NamedTuple):
    """What a kernel is specialised on."""
    vocab: int
    vocab_first: bool  # the head is (vocab, E), else (E, vocab)
    dtype: np.dtype  # the products' operands
    block_rows: int
    block_vocab: int
    interpret: bool


def _logits(x_ref, head_ref, c: _Shape):
    return _mm(x_ref[...], head_ref[...], _NT if c.vocab_first else _NN)


def _column(j, c: _Shape):
    """Each logit's index in the vocabulary, ``(block_rows, block_vocab)``."""
    return j * c.block_vocab + jax.lax.broadcasted_iota(
        jnp.int32, (c.block_rows, c.block_vocab), 1)


def _fwd_kernel(x_ref, head_ref, target_ref, lse_ref, nll_ref,
                m_ref, l_ref, hit_ref, *, c: _Shape):
    j, nv = pl.program_id(1), pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASK, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        hit_ref[...] = jnp.zeros(hit_ref.shape, F32)

    def step(masked):
        s = _logits(x_ref, head_ref, c)
        column = _column(j, c)
        if masked:  # a block's real columns come first: m stays a real logit
            s = jnp.where(column < c.vocab, s, MASK)
        hit_ref[...] += jnp.sum(
            jnp.where(column == target_ref[...], s, 0.0), axis=1,
            keepdims=True)
        m_prev = m_ref[...]
        m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        l_ref[...] = jnp.exp(m_prev - m) * l_ref[...] + jnp.sum(
            jnp.exp(s - m), axis=1, keepdims=True)
        m_ref[...] = m

    if c.vocab % c.block_vocab:  # the last block hangs over the edge
        pl.when(j < nv - 1)(lambda: step(False))
        pl.when(j == nv - 1)(lambda: step(True))
    else:
        step(False)

    @pl.when(j == nv - 1)
    def _():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = lse
        nll_ref[...] = lse - hit_ref[...]


def _bwd_kernel(x_ref, head_ref, target_ref, lse_ref, ct_ref, g_ref, *,
                c: _Shape):
    # columns past the vocabulary hold whatever was read, and are not written
    p = jnp.exp(_logits(x_ref, head_ref, c) - lse_ref[...])
    onehot = _column(pl.program_id(1), c) == target_ref[...]
    g_ref[...] = ((p - onehot.astype(F32)) * ct_ref[...]).astype(g_ref.dtype)


def _call(kernel, name, c: _Shape, outs, scratch, semantics, x, head,
          *per_row):
    """One kernel over (row block, vocabulary block) of ``x`` ``(rows, E)``
    and the head, both in the compute dtype, and ``(rows, 1)`` scalars a
    row.  ``outs``: a letter an output — ``r`` a per-row float32 scalar,
    ``g`` a ``(rows, vocab)`` tensor in the compute dtype."""
    rows, width = x.shape
    per_row_spec = pl.BlockSpec((c.block_rows, 1), lambda i, j: (i, 0))
    head_spec = (pl.BlockSpec((c.block_vocab, width), lambda i, j: (j, 0))
                 if c.vocab_first else
                 pl.BlockSpec((width, c.block_vocab), lambda i, j: (0, j)))
    specs = {
        "r": (per_row_spec, (rows, 1), F32),
        "g": (pl.BlockSpec((c.block_rows, c.block_vocab), lambda i, j: (i, j)),
              (rows, c.vocab), c.dtype),
    }
    operands = (x, head, *per_row)
    return pl.pallas_call(
        partial(kernel, c=c),
        grid=(rows // c.block_rows, pl.cdiv(c.vocab, c.block_vocab)),
        in_specs=[pl.BlockSpec((c.block_rows, width), lambda i, j: (i, 0)),
                  head_spec] + [per_row_spec] * len(per_row),
        out_specs=[specs[o][0] for o in outs],
        out_shape=[_out_struct(*specs[o][1:], *operands) for o in outs],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(*semantics),
        interpret=c.interpret,
        name=name,
    )(*operands)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _nll_core(x, head, targets, c: _Shape):
    return _nll_core_fwd(x, head, targets, c)[0]


def _nll_core_fwd(x, head, targets, c):
    x = x.astype(c.dtype)
    lse, nll = _call(
        _fwd_kernel, "lm_loss_forward", c, "rr",
        [pltpu.VMEM((c.block_rows, 1), F32)] * 3, ("parallel", "arbitrary"),
        x, head.astype(c.dtype), targets)
    lse = checkpoint_name(lse, *SAVED)
    # the head is a parameter, alive anyway: kept as it came and cast again
    return nll, (x, head, targets, lse)


def _nll_core_bwd(c, res, ct):
    x, head, targets, lse = res
    head = head.astype(c.dtype)
    (g,) = _call(
        _bwd_kernel, "lm_loss_backward", c, "g", [], ("parallel", "parallel"),
        x, head, targets, lse, ct.astype(F32))
    if c.vocab_first:
        dx, dhead = _mm(g, head, _NN), _mm(g, x, _TN)
    else:
        dx, dhead = _mm(g, head, _NT), _mm(x, g, _TN)
    return dx, dhead, None


_nll_core.defvjp(_nll_core_fwd, _nll_core_bwd)


def nll_rows(x, head, targets, compute_dtype=None, *, vocab_first=False,
             block_rows=None, block_vocab=None, interpret=None):
    """``-log_softmax(x @ head)[target]`` a row, ``(rows,)`` float32, from
    float32 ``x`` ``(rows, E)``, a float32 head ``(E, vocab)`` —
    ``(vocab, E)`` with ``vocab_first`` — and ``targets`` ``(rows,)``, with
    the kernels' own backward: float32 cotangents for ``x`` and the head.
    ``rows`` is whole blocks of ``block_rows``; ``vocab`` need not be of
    ``block_vocab``; both default to ``blocks``'."""
    if interpret is None:
        interpret = not lowerable()
    vocab = head.shape[0 if vocab_first else 1]
    default = blocks(x.shape[0], vocab)
    c = _Shape(vocab, bool(vocab_first), np.dtype(compute_dtype or F32),
               block_rows or default[0], block_vocab or default[1],
               bool(interpret))
    targets = targets.astype(jnp.int32).reshape(-1, 1)
    return _nll_core(x.astype(F32), head.astype(F32), targets, c)[:, 0]
