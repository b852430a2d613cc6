"""Fused attention kernels in Pallas — the hot-op custom kernel path.

Forward, per-(batch*head, q-block) grid cell: one MXU matmul Q.K^T,
masked softmax on the VPU, one MXU matmul P.V — all in VMEM, no HBM
round-trip for the scores matrix (the thing that makes naive attention
bandwidth-bound).  K/V live whole in VMEM per cell, which is fine for
the single-chip sequence lengths this framework targets; beyond that
the ring path (``parallel.ring_attention``) shards the sequence first
and each shard's local attention goes through this kernel.

Backward (``jax.custom_vjp``): the FlashAttention recipe — RECOMPUTE
the scores from the saved ``(q, k, v, o, lse)`` residuals instead of
ever writing the (T_q, T_k) probability matrix to HBM.  Two kernels:
a dq pass gridded like the forward (per q-block, scores live only in
VMEM) and a dk/dv pass per (batch*head) cell.  Both use the identity
``ds = p * (dp - (rowsum(do*o) - dlse))`` where ``p = exp(s - lse)``
is rebuilt in-cell; the ``dlse`` term makes the (o, lse) pair an
honest differentiable output, which is what lets the ring path merge
per-step partial attentions and still get exact gradients.

Position bookkeeping is absolute: kernels take a (q_offset, k_offset)
pair so the same code serves the end-aligned dense convention
(``mha_reference``'s ``tril(k=tk-tq)`` — offset ``(tk - tq, 0)``) and
the ring's per-shard global positions.  A T_q that does not divide
``block_q`` is end-padded (padded rows attend unmasked, stay finite,
and are sliced off; their cotangents are zero) — only T_q=0 errors.

On non-TPU backends the kernels run in interpreter mode so tests pin
forward AND backward against ``mha_reference`` / ``jax.grad`` of it
everywhere.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lowerable() -> bool:
    """True when the Pallas kernels lower natively on this backend.
    The single source of truth for "custom kernels run here": serving
    decode, the LM train-step attention, the comm plane's fused
    epilogue and the LRN/pool kernels all gate on this — TPU takes the
    kernel, everything else takes the dense/XLA reference, and
    interpreter mode stays a test-only tool (it is far slower than the
    XLA-compiled reference on CPU)."""
    return jax.default_backend() in ("tpu",)


_NT = (((1,), (1,)), ((), ()))  # a @ b.T without materializing b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32
    )


def _causal_mask(offs_ref, rows, tk, row0):
    """(rows, tk) bool mask from ABSOLUTE positions: query row r of
    this block sits at ``q_offset + row0 + r``, key column c at
    ``k_offset + c``.  Offsets ride in as two int32 scalars in SMEM
    (traced — the ring's ``axis_index`` arithmetic — so they can't be
    static kernel params)."""
    q_pos = offs_ref[0] + row0 + jax.lax.broadcasted_iota(
        jnp.int32, (rows, tk), 0
    )
    k_pos = offs_ref[1] + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1)
    return k_pos <= q_pos


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, block_q):
    j = pl.program_id(1)
    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (tk, d)
    v = v_ref[0]
    tk = k.shape[0]
    s = _dot(q, k, _NT) * scale
    if causal:
        mask = _causal_mask(offs_ref, q.shape[0], tk, j * block_q)
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows (ring steps ahead of the causal frontier) must
    # come out (o=0, lse=-inf), not NaN — guard the exp and the divide
    m_safe = jnp.where(m == -jnp.inf, 0.0, m)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l > 0, m_safe + jnp.log(l), -jnp.inf)


def _recompute_p(offs_ref, q, k, lse, scale, causal, row0):
    """Normalized probabilities rebuilt from the lse residual — the
    flash backward's recompute.  A fully-masked row has lse=-inf and
    s=-inf: substitute lse=0 so exp(-inf - 0) = 0 instead of exp(nan)."""
    s = _dot(q, k, _NT) * scale
    if causal:
        mask = _causal_mask(offs_ref, q.shape[0], k.shape[0], row0)
        s = jnp.where(mask, s, -jnp.inf)
    return jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0))


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                   lse_ref, dlse_ref, dq_ref, *, scale, causal, block_q):
    j = pl.program_id(1)
    k = k_ref[0]
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    p = _recompute_p(
        offs_ref, q_ref[0], k, lse_ref[0], scale, causal, j * block_q
    )
    delta = jnp.sum(do * o, axis=-1, keepdims=True) - dlse_ref[0]
    dp = _dot(do, v_ref[0].astype(jnp.float32), _NT)
    ds = p * (dp - delta) * scale
    dq_ref[0] = jnp.dot(
        ds, k.astype(jnp.float32), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                    lse_ref, dlse_ref, dk_ref, dv_ref, *, scale, causal):
    q = q_ref[0]  # (tq, d) — whole padded T_q per (batch*head) cell
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    p = _recompute_p(offs_ref, q, k_ref[0], lse_ref[0], scale, causal, 0)
    dv_ref[0] = _dot(p, do, _TN).astype(dv_ref.dtype)
    delta = jnp.sum(do * o, axis=-1, keepdims=True) - dlse_ref[0]
    dp = _dot(do, v_ref[0].astype(jnp.float32), _NT)
    ds = p * (dp - delta) * scale
    dk_ref[0] = _dot(ds, q.astype(jnp.float32), _TN).astype(dk_ref.dtype)


# Scoped-VMEM ceiling handed to Mosaic.  The default (16 MiB on v5e)
# refuses the dk/dv pass — whole q/k/v/do/o plus four (T_q, T_k) f32
# temporaries per cell — well before the chip's 128 MiB runs out.
VMEM_LIMIT_BYTES = 100 << 20


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


def _fwd_call(qf, kf, vf, offs, causal, block_q, interpret):
    n, tq, d = qf.shape
    tk = kf.shape[1]
    scale = 1.0 / math.sqrt(d)
    kernel = partial(_fwd_kernel, scale=scale, causal=causal,
                     block_q=block_q)
    return pl.pallas_call(
        kernel,
        grid=(n, tq // block_q),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _out_struct((n, tq, d), qf.dtype, qf, kf, vf, offs),
            _out_struct((n, tq, 1), jnp.float32, qf, kf, vf, offs),
        ],
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(offs, qf, kf, vf)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core(qf, kf, vf, offs, causal, block_q, interpret):
    """(o, lse) over flattened (B*H, T, D) inputs; T_q already padded
    to a ``block_q`` multiple.  ``offs`` is the int32 (2,) absolute
    (q_offset, k_offset) pair; ``lse`` is (B*H, T_q, 1) — rows on the
    sublane axis, the layout every kernel consumes it in.
    Differentiable in q/k/v AND honest in the lse output (nonzero dlse
    cotangents — the ring merge — feed the backward's delta term)."""
    return _fwd_call(qf, kf, vf, offs, causal, block_q, interpret)


def _flash_core_fwd(qf, kf, vf, offs, causal, block_q, interpret):
    o, lse = _fwd_call(qf, kf, vf, offs, causal, block_q, interpret)
    return (o, lse), (qf, kf, vf, offs, o, lse)


def _flash_core_bwd(causal, block_q, interpret, res, cts):
    qf, kf, vf, offs, o, lse = res
    do, dlse = cts
    n, tq, d = qf.shape
    tk = kf.shape[1]
    scale = 1.0 / math.sqrt(d)
    dlse = dlse.astype(jnp.float32)
    operands = (offs, qf, kf, vf, do, o, lse, dlse)
    dq_kernel = partial(_bwd_dq_kernel, scale=scale, causal=causal,
                        block_q=block_q)
    block_qd = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    block_q1 = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    all_k = pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(n, tq // block_q),
        in_specs=[
            _SMEM, block_qd, all_k, all_k, block_qd, block_qd,
            block_q1, block_q1,
        ],
        out_specs=block_qd,
        out_shape=_out_struct((n, tq, d), qf.dtype, *operands),
        compiler_params=_compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(*operands)
    dkv_kernel = partial(_bwd_dkv_kernel, scale=scale, causal=causal)
    whole_q = pl.BlockSpec((1, tq, d), lambda i: (i, 0, 0))
    whole_k = pl.BlockSpec((1, tk, d), lambda i: (i, 0, 0))
    col_q = pl.BlockSpec((1, tq, 1), lambda i: (i, 0, 0))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(n,),
        in_specs=[
            _SMEM,
            whole_q, whole_k, whole_k, whole_q, whole_q, col_q, col_q,
        ],
        out_specs=[whole_k, whole_k],
        out_shape=[
            _out_struct((n, tk, d), kf.dtype, *operands),
            _out_struct((n, tk, d), vf.dtype, *operands),
        ],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
    )(*operands)
    return dq, dk, dv, None  # integer offsets carry no cotangent


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flatten_heads(x):
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _pad_to_block(qf, block_q):
    """End-pad the flattened query rows to a block_q multiple; real
    rows keep their original absolute positions (the offset is derived
    from the UNPADDED T_q), padded rows attend unmasked (finite, no
    NaN) and are sliced off by the caller."""
    tq = qf.shape[1]
    pad = (-tq) % block_q
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
    return qf, pad


def flash_attention(
    q, k, v, causal: bool = False, block_q: int = 128, interpret=None
):
    """Fused attention on (B, T, H, D) with a fused flash backward;
    bit-comparable to ``mha_reference`` (same softmax, same end-aligned
    ``tril(k=tk-tq)`` causal convention, fp32 accumulation) and
    grad-pinned against ``jax.grad`` of it.  Any T_q >= 1 works — a
    ragged T_q is end-padded to the q-block internally."""
    if interpret is None:
        interpret = not lowerable()
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if tq == 0:
        raise ValueError(
            "flash_attention: T_q=0 — an empty query block has no "
            "attention output (check the caller's slicing)"
        )
    block_q = min(block_q, tq)
    qf, pad = _pad_to_block(_flatten_heads(q), block_q)
    kf, vf = _flatten_heads(k), _flatten_heads(v)
    offs = jnp.asarray([tk - tq, 0], jnp.int32)
    o, _ = _flash_core(qf, kf, vf, offs, causal, block_q, bool(interpret))
    if pad:
        o = o[:, :tq]
    return jnp.transpose(o.reshape(b, h, tq, d), (0, 2, 1, 3))


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
    if interpret is None:
        interpret = not lowerable()
    b, tq, h, d = q.shape
    block_q = min(block_q, tq)
    qf, pad = _pad_to_block(_flatten_heads(q), block_q)
    kf, vf = _flatten_heads(k), _flatten_heads(v)
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
    o, lse = _flash_core(qf, kf, vf, offs, causal, block_q, bool(interpret))
    if pad:
        o, lse = o[:, :tq], lse[:, :tq]
    return o.reshape(b, h, tq, d), lse.reshape(b, h, tq)


# ----------------------------------------------------------------------
# Decode attention: q_len == 1 over a (possibly over-allocated) context
# ----------------------------------------------------------------------
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale, s):
    q = q_ref[0]  # (1, d)
    k = k_ref[0]  # (s, d)
    n = len_ref[pl.program_id(0)]
    scores = _dot(q, k, _NT) * scale
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
