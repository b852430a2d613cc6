"""Causal grouped attention over the keys a learned indexer selects (the
sparse attention of DeepSeek-V3.2-Exp, as Keye-VL-2.0's ``sa_config`` states
it), in four pieces that a layer runs under four scopes:

- ``index_scores``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for
  ``s <= t``, one small key shared by all index heads; the products take
  their operands in the compute dtype and accumulate in float32, the ReLU,
  the weighting and the sum over heads are float32.
- ``select``: ``S_t``, the ``min(t + 1, topk)`` keys ``s <= t`` of largest
  ``I[t, s]``, ties to the lower ``s``, as a bit mask.  No sort: the k-th
  largest score of a row is found bit by bit on the scores' order-preserving
  integer image (32 counts over the row), the mask is ``score >= it``, and a
  row in which more than the k-th tie at that value takes them by position.
  No gradient passes through it.
- ``masked_attention``: ``softmax_{s in S_t}(q . k / sqrt(D)) v`` with
  grouped K/V heads, and the row's log-sum-exp.
- ``alignment_loss``: ``sum_t KL(p[t, .] || softmax_{S_t}(I[t, .]))`` with
  ``p = stop_gradient(mean_h A[t, h, .])``, the attention's own
  probabilities computed once a step from ``q``, ``k`` and the log-sum-exp;
  its gradient reaches the index scores alone, and comes with its value:
  under differentiation the ONE pass over the blocks that computes the loss
  computes its gradient to ``qI``, ``w`` and ``kI`` in closed form
  (``_alignment_with_gradient``: on the TPU one Pallas kernel,
  ``ops/pallas_alignment.py``), and the backward pass scales those three.

Why a MASKED pass and not a gather: on the v5e a gather of 2,048 K/V rows a
query is 4 MB a token (69 GB a layer at 16,384 tokens, ~84 ms at 819 GB/s)
where the masked dense product is 2.2 TFLOP (~14 ms at 80% of the bf16
peak); PERF.md section 6, PR 33 has what was read.

The index scores, the selection and the loss's value run in XLA on the ONE
blockwise loop of ``ops/attention.py`` (``by_run``: a block of ``block_q``
queries at a time, ``lax.map`` over the blocks, the sequence cut into
``segments`` runs that each meet only the keys up to their own end), so that
no more than ``block_q x T`` scores a head exist at once; nothing
differentiates through that loop here (``_blockwise_gqa`` and the plain
``alignment_value`` wrap their blocks in ``jax.checkpoint`` for whoever does).
The masked pass takes the K/V-blocked flash kernels given the selection's bits
(``pallas_attention.masked_flash_attention``: no score leaves VMEM) where
Pallas lowers and they accept the shapes, and elsewhere ``_blockwise_gqa``
given the keep-mask, the same arithmetic on that loop and the oracle the
kernels are tested against; so does the loss with its gradient
(``pallas_alignment.alignment_gradient``, on the same kernels' walk and bits;
elsewhere its XLA form on that loop, the kernel's oracle).  An ``obs`` instant
names the path at each trace (``sparse_attention_path``;
``alignment_loss_path`` says whether the loss was traced for its value or with
its gradient, and on which path).  Between the pieces go the indexer's ``qI``
/ ``kI`` / ``w``, the mask as bits (``T * T / 8`` bytes: 33.5 MB at 16,384
tokens, no float tensor of ``(T, T)``), ``q`` / ``k`` and the log-sum-exp;
from the forward pass to the backward the loss keeps its three gradients (the
sizes of ``qI``, ``w`` and ``kI``) and nothing else.  ``index_scores_by_run``
alone materialises float32 scores of whole runs, ``(B, T, T)`` x the causal
share, forward only: ``select`` consumes them and nothing keeps them for the
backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sparknet_tpu import obs
from sparknet_tpu.ops import attention, pallas_alignment, pallas_attention
from sparknet_tpu.ops.attention import (  # noqa: F401  (the mask's layout)
    BITS, NEG, _blockwise_gqa, blocked, by_run, joined, pack_mask, runs_of,
    unpack_mask, words_of)

F32 = jnp.float32
U32 = jnp.uint32
BLOCK_Q = 512
SEGMENTS = 8
# Keys a block of the flash kernels under a keep-mask, times the head's
# width: 1,024 keys of 128 (``attention.KERNEL_BLOCK_K`` keys of 256).  A
# step's two products and its softmax run one after the other, and a wider
# tile lets Mosaic overlap them: on the v5e at T = 16,384, 32 / 4 heads of
# 128 in bfloat16, forward + backward 65.2 ms at 1,024 keys against 70.9 at
# 512 (forward 19.9 against 25.9; PERF.md section 6, PR 34).
KERNEL_KEYS_X_WIDTH = attention.KERNEL_BLOCK_K * 256
# What a ``jax.checkpoint`` around the caller may keep (``policy=jax.
# checkpoint_policies.save_only_these_names(*SAVED)``) so that its
# recomputation does not run the attention's blocks a second time.
SAVED = ("sparse_attention_o", "sparse_attention_lse")


# -- the indexer's scores -----------------------------------------------------
def index_scores(qi, w, ki):
    """``qi``: ``(B, Q, J, Di)`` and ``ki``: ``(B, S, Di)`` in the compute
    dtype; ``w``: ``(B, Q, J)`` float32, the scale factors in it.  Returns
    ``(B, Q, S)`` float32, no mask applied; a ``-0.0`` reads ``0.0``, so
    that equal scores are equal bit patterns."""
    s = jnp.einsum("bqjd,bsd->bqjs", qi, ki.astype(qi.dtype),
                   preferred_element_type=F32)
    i = jnp.sum(jax.nn.relu(s) * w[..., None].astype(F32), axis=2)
    return jnp.where(i == 0.0, 0.0, i)


def index_scores_by_run(qi, w, ki, *, block_q: int = BLOCK_Q,
                        segments: int = SEGMENTS):
    """The scores of every causal pair, a list with one ``(run's blocks, B,
    block_q, keys)`` float32 array a run (``runs_of``): what ``select``
    reads.  Forward only."""
    t = qi.shape[1]
    block_q, _ = runs_of(t, block_q, segments)
    return by_run(
        lambda keys, first, qb, wb: index_scores(qb, wb, ki[:, :keys]),
        (blocked(qi, block_q), blocked(w, block_q)), t, block_q, segments)


# -- the selection ------------------------------------------------------------
def _ordered(x):
    """Float32 -> uint32 with the floats' order (finite values and the
    infinities; every image is above 0)."""
    u = jax.lax.bitcast_convert_type(x, U32)
    return jnp.where(u >> U32(31) == U32(1), ~u, u | U32(1 << 31))


def select_block(scores, first, t: int, topk: int):
    """``scores``: ``(B, Q, S)`` of the queries ``first .. first + Q - 1``
    against the keys ``0 .. S - 1``.  Returns the keep-mask ``(B, Q, S)``
    bool: of each row's causal keys the ``min(row + 1, topk)`` of largest
    score, ties to the lower key."""
    q, s = scores.shape[1:]
    rows = jnp.minimum(first + jnp.arange(q), t - 1)  # rows of padding: the last
    causal = jnp.arange(s)[None, :] <= rows[:, None]
    key = jnp.where(causal, _ordered(scores), U32(0))
    want = jnp.minimum(rows + 1, topk)[None, :]

    def bit(i, prefix):
        # the k-th largest image, one bit at a time from the top: a bit stays
        # where at least k images reach the prefix with it set
        cand = prefix | (U32(1) << (U32(BITS - 1) - i.astype(U32)))
        reach = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= want, cand, prefix)

    # (zeros of the scores' own type: inside a shard_map the carry varies
    # over the mesh axis as they do)
    kth = jax.lax.fori_loop(
        0, BITS, bit, jnp.zeros_like(scores[..., 0], U32))[..., None]
    above, tied = key > kth, key == kth
    room = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    ties = jnp.sum(tied, axis=-1, dtype=jnp.int32)
    keep = jax.lax.cond(
        jnp.any(ties > room),
        lambda: above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                                 <= room[..., None])),
        lambda: above | tied)
    return keep & causal


def select(scores_by_run, t: int, topk: int, *, block_q: int = BLOCK_Q,
           segments: int = SEGMENTS):
    """The selection as bits: ``(B, T, words_of(T))`` uint32 from
    ``index_scores_by_run``'s scores (``pack_mask``'s layout)."""
    words = words_of(t)
    block_q, _ = runs_of(t, block_q, segments)
    packed = by_run(
        lambda keys, first, scores: pack_mask(
            select_block(scores, first, t, topk), words),
        (scores_by_run,), t, block_q, segments)
    return jax.lax.stop_gradient(joined(packed, t))


def causal_mask_bits(b: int, t: int):
    """The mask that keeps every causal key, in ``select``'s layout."""
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return jnp.broadcast_to(pack_mask(keep, words_of(t)), (b, t, words_of(t)))


# -- attention over the selection ---------------------------------------------
def scaled_queries(q, compute_dtype=None):
    """``q * D ** -0.5`` in the compute dtype, scaled before the cast: what
    ``masked_attention``, ``alignment_loss`` and ``selection_mass`` take, so
    that all three multiply the same numbers."""
    return (q.astype(F32) * q.shape[-1] ** -0.5).astype(compute_dtype or F32)


def _heads_first(q, k, block_q: int):
    """Queries as ``(blocks, B, Hkv, G, block_q, D)``; keys (or values) as
    ``(B, Hkv, T, D)`` in the queries' dtype."""
    hq, d = q.shape[2:]
    hkv = k.shape[2]
    q = blocked(q, block_q)
    q = q.reshape(*q.shape[:3], hkv, hq // hkv, d).transpose(0, 1, 3, 4, 2, 5)
    return q, k.astype(q.dtype).transpose(0, 2, 1, 3)


def _scores(qb, kb):
    return jnp.einsum("bkgqd,bksd->bkgqs", qb, kb, preferred_element_type=F32)


def _blocks_met(t: int, block_q: int, segments: int):
    """``(computed, total)`` blocks of ``block_q x block_q`` scores where a
    run's query blocks each meet the keys up to the run's end."""
    block_q, runs = runs_of(t, block_q, segments)
    return (sum((hi - lo) * -(-keys // block_q) for lo, hi, keys in runs),
            (-(-t // block_q)) ** 2)


def kernels_refuse(t: int, hq: int, hkv: int, d: int, dtype) -> str:
    """Why ``masked_attention`` does not take the flash kernels for these
    shapes on this backend; empty where it takes them.

    Float32 stays on the XLA pass: its products are six MXU passes either
    way (on the v5e 326.8 ms against 344.5 forward + backward at the cell's
    widths), and a Mosaic call in a program changes the scoped VMEM XLA
    gives every fusion beside it, hence their tiling and their rounding: in
    ``keye2-train-16k``'s float32 check of the selection the reference's two
    passes over the index scores then break a near-tie differently, one pair
    of 31 million, which its bound on the attention cannot take (PERF.md
    section 6, PR 34).  The kernels themselves are as exact as the XLA pass
    (``tests/test_keye_vl2.py``; on the chip 4.8e-7 against float64 where
    the XLA pass reads 3.7e-7)."""
    if not attention.lowerable():
        return f"no Pallas lowering on {jax.default_backend()}"
    if not pallas_attention.accepts(hq, hkv, d, dtype):
        return "heads of whole lanes in whole groups, bfloat16 or float32"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "float32 products are six MXU passes on either path"
    if t % (BITS * pallas_attention.LANES):
        return "a keep-mask's row in whole lanes of words: T % 4096 == 0"
    return ""


def masked_attention(q, k, v, mask, *, block_q: int = BLOCK_Q,
                     segments: int = SEGMENTS):
    """``q``: ``scaled_queries`` ``(B, T, Hq, D)``; ``k``, ``v``: ``(B, T,
    Hkv, D)``; ``mask``: ``select``'s bits.  Softmax over each query's
    selected keys (scores and softmax float32, the products' operands in
    ``q``'s dtype).  Returns the output ``(B, T, Hq, D)`` float32 and the
    rows' log-sum-exp ``(B, T, Hq)`` float32, both named for a caller's
    checkpoint policy (``SAVED``).

    Where Pallas lowers, ``pallas_attention.accepts`` the heads, ``q`` is
    bfloat16 (``kernels_refuse`` says why) and a row of the mask is whole
    lanes of words (``T % 4096 == 0``, so that a key block is whole bits of
    every word), the flash kernels given the bits, in
    ``causal_gqa_attention``'s query blocks (cut to a power of two, which
    divides T) and key blocks of ``KERNEL_KEYS_X_WIDTH`` over the head's
    width, in whole rows of words.  Elsewhere ``ops/attention._blockwise_gqa``
    given the keep-mask; ``block_q`` and ``segments`` are its."""
    cd = q.dtype
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    words = mask.shape[-1]
    why = kernels_refuse(t, hq, hkv, d, cd)
    if why:  # a run's query blocks each meet the keys up to its end
        block_q, _ = runs_of(t, block_q, segments)
        block_k = block_q
        met = _blocks_met(t, block_q, segments)
    else:
        block_k = words * max(1, KERNEL_KEYS_X_WIDTH // d // words)
        block_q = attention.kernel_block_q(hq // hkv, d, cd, block_k)
        block_q = 1 << (block_q.bit_length() - 1)  # divides T
        met = pallas_attention.blocks_met(t, t, block_q, block_k)
    backward, backward_why = ("xla", "") if why else (
        pallas_attention.backward_path(t, d, 0, block_k))
    obs.instant("sparse_attention_path", cat="kernel",
                path="xla" if why else "pallas", why=why,
                backend=jax.default_backend(), t=t, hq=hq, hkv=hkv, d=d,
                dtype=cd.name, block_q=block_q, block_k=block_k, words=words,
                segments=segments,
                blocks_computed=met[0], blocks_total=met[1],
                backward=backward, backward_why=backward_why)
    k, v = k.astype(cd), v.astype(cd)
    if why:
        out = _blockwise_gqa(q, k, v, block_q, segments, keep=mask)
    else:
        o, lse = pallas_attention.masked_flash_attention(
            q, k, v, mask, block_q=block_q, block_k=block_k, scale=1.0,
            out_dtype=F32)
        out = o, jnp.transpose(lse, (0, 2, 1))
    return tuple(checkpoint_name(x, name) for x, name in zip(out, SAVED))


def _head_mean_probabilities(qi, kh, lse, keep):
    """``mean_h A[t, h, s]`` over the kept keys from the scaled queries, the
    keys and the rows' log-sum-exp: ``(B, Q, S)`` float32."""
    a = jnp.where(keep[:, None, None], jnp.exp(_scores(qi, kh) - lse[..., None]),
                  0.0)
    return jnp.mean(a, axis=(1, 2))


def _kl(keep, p, scores, first, t: int):
    """Of a block of rows ``first ..`` of a sequence of ``t``: ``sum_{s in
    S_t} p (log p - log softmax_{S_t}(scores)[s])`` summed over the real rows,
    that log-softmax ``(B, Q, S)`` and which rows are real ``(Q,)``."""
    i = jnp.where(keep, scores, NEG)
    i = i - jnp.max(i, axis=-1, keepdims=True)
    # (a row of padding keeps no key: the floor keeps its gradient finite)
    log_q = i - jnp.log(jnp.maximum(jnp.sum(
        jnp.where(keep, jnp.exp(i), 0.0), axis=-1, keepdims=True), 1e-30))
    live = keep & (p > 0.0)
    kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_q), 0.0)
    real = first + jnp.arange(kl.shape[1]) < t
    return (jnp.sum(jnp.where(real[None, :], jnp.sum(kl, axis=-1), 0.0)),
            log_q, real)


def _alignment_blocks(qi, w, q, k, lse, mask, block_q: int):
    """What ``by_run`` hands a block of the alignment loss: the indexer's
    queries and weights, the attention's scaled queries heads first and
    log-sum-exp ``(.., B, Hkv, G, block_q)``, both constants, and the mask's
    words, each ``(blocks, B, block_q, ...)``; and the keys heads first."""
    b, t, hq, _ = q.shape
    hkv = k.shape[2]
    qb, kh = jax.lax.stop_gradient(_heads_first(q, k, block_q))
    lse = jax.lax.stop_gradient(lse).reshape(b, t, hkv, hq // hkv)
    return (blocked(qi, block_q), blocked(w, block_q), qb,
            blocked(lse, block_q).transpose(0, 1, 3, 4, 2),
            blocked(mask, block_q)), kh


def alignment_value(qi, w, ki, q, k, lse, mask, *, block_q: int = BLOCK_Q,
                    segments: int = SEGMENTS):
    """``sum_{b, t} sum_{s in S_t} p (log p - log softmax_{S_t}(I)[s])`` with
    ``p = mean_h A``, the attention's probabilities over its selected keys
    (``q``, ``k``, ``lse`` as ``masked_attention`` took and gave them; no
    gradient reaches them or ``mask``).  Float32 but for the two products'
    operands.  The plain function: ``alignment_loss``'s value, and under
    ``jax.grad`` (a ``jax.checkpoint`` a block, autodiff through
    ``index_scores``) the oracle its closed-form gradient is tested
    against."""
    t = q.shape[1]
    block_q, _ = runs_of(t, block_q, segments)
    blocks, kh = _alignment_blocks(qi, w, q, k, lse, mask, block_q)

    def block(keys, first, qib, wb, qm, lb, bits):
        keep = unpack_mask(bits, keys)
        p = _head_mean_probabilities(qm, kh[:, :, :keys], lb, keep)
        return _kl(keep, p, index_scores(qib, wb, ki[:, :keys]), first, t)[0]

    out = by_run(jax.checkpoint(block, static_argnums=(0,)), blocks, t,
                 block_q, segments)
    return sum(jnp.sum(x) for x in out)


def alignment_kernel_refuses(t: int, hq: int, hkv: int, d: int, j: int,
                             di: int, dtype) -> str:
    """Why ``alignment_loss`` under differentiation does not take the
    kernel (``ops/pallas_alignment.py``) for these shapes on this backend;
    empty where it takes it."""
    if not attention.lowerable():
        return f"no Pallas lowering on {jax.default_backend()}"
    if not pallas_alignment.accepts(t, hq, hkv, d, j, di, dtype):
        return pallas_alignment.ACCEPTS
    return ""


def _alignment_event(path: str, qi, mask, block_q: int, segments: int,
                     why: str = ""):
    t = qi.shape[1]
    if path == "pallas":  # the flash kernels' walk
        block_q, block_k = pallas_alignment.blocks(t, mask.shape[-1])
        met = pallas_attention.blocks_met(t, t, block_q, block_k)
    else:  # a run's query blocks each meet the keys up to its end
        block_q, _ = runs_of(t, block_q, segments)
        block_k, met = block_q, _blocks_met(t, block_q, segments)
    obs.instant("alignment_loss_path", cat="kernel", path=path, why=why,
                backend=jax.default_backend(), t=t,
                ds_dtype=qi.dtype.name if path != "value" else "",
                block_q=block_q, block_k=block_k, segments=segments,
                blocks_computed=met[0], blocks_total=met[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _alignment(qi, w, ki, q, k, lse, mask, block_q, segments):
    _alignment_event("value", qi, mask, block_q, segments)
    return alignment_value(qi, w, ki, q, k, lse, mask, block_q=block_q,
                           segments=segments)


def _alignment_with_gradient(qi, w, ki, q, k, lse, mask, block_q, segments):
    """``alignment_value`` and, from the same pass over the blocks, its
    gradient to ``qi``, ``w`` and ``ki`` in closed form: the kernel
    (``pallas_alignment.alignment_gradient``, its own blocks) where
    ``alignment_kernel_refuses`` is empty, else this XLA form on ``by_run``'s
    blocks, the kernel's oracle.  With ``g =
    dL/dI = softmax_{S_t}(I) * sum_s p - p`` on the kept keys of the real
    rows where ``I != 0`` (``index_scores``' ``where``), float32, and ``M_j =
    g [s_j > 0]`` in the products' operand dtype (what the MXU is fed of a
    float32 cotangent at default precision: the v5e rounds it to bfloat16,
    bit for bit what the cast gives): ``U_j = M_j @ kI``, ``dL/dqI_j = w_j
    U_j``, ``dL/dw_j = qI_j . U_j`` (``relu(s_j) = [s_j > 0] qI_j . kI``:
    no sum over the keys of a ``(.., J, keys)`` array) and ``dL/dkI = sum_j
    M_j^T @ (w_j qI_j)``, every sum float32.  (Autodiff sums ``dL/dw``'s terms
    in float32 with ``g`` unrounded: in bfloat16 this ``dL/dw`` is 4.9e-4
    from it, alone on the v5e at T = 16,384; inside a step nothing a check
    can read, PERF.md section 6, PR 36.)  The index scores are formed twice,
    for ``I`` and again for ``M`` once the rows' softmax is known: nothing
    of ``(.., J, keys)`` is float32 in HBM (on the v5e XLA keeps the sixteen
    ``[s_j > 0]`` as the bits of one ``u16 (block_q, keys)`` and forms ``M``
    inside each product's fusion)."""
    t, hq, d = q.shape[1:]
    why = alignment_kernel_refuses(t, hq, k.shape[2], d, *qi.shape[2:],
                                   qi.dtype)
    if not why:
        _alignment_event("pallas", qi, mask, block_q, segments)
        block_q, block_k = pallas_alignment.blocks(t, mask.shape[-1])
        return pallas_alignment.alignment_gradient(
            qi, w, ki, q, k, lse, mask, block_q=block_q, block_k=block_k)
    _alignment_event("with_gradient", qi, mask, block_q, segments, why)
    cd = qi.dtype
    block_q, _ = runs_of(t, block_q, segments)
    blocks, kh = _alignment_blocks(qi, w, q, k, lse, mask, block_q)

    def block(keys, first, qib, wb, qm, lb, bits):
        kib = ki[:, :keys].astype(cd)
        keep = unpack_mask(bits, keys)
        p = _head_mean_probabilities(qm, kh[:, :, :keys], lb, keep)
        scores = index_scores(qib, wb, kib)
        loss, log_q, real = _kl(keep, p, scores, first, t)
        g = jnp.where(
            keep & (scores != 0.0) & real[None, :, None],
            jnp.exp(log_q) * jnp.sum(p, axis=-1, keepdims=True) - p, 0.0)
        # (behind a barrier, or XLA keeps the first product's float32 scores
        # for this second use instead of forming them again)
        qib, kib, g = jax.lax.optimization_barrier((qib, kib, g))
        s = jnp.einsum("bqjd,bsd->bqjs", qib, kib, preferred_element_type=F32)
        m = jnp.where(s > 0.0, g.astype(cd)[:, :, None], 0.0)
        u = jnp.einsum("bqjs,bsd->bqjd", m, kib, preferred_element_type=F32)
        wb = wb[..., None].astype(F32)
        return (loss, (wb * u).astype(cd), jnp.sum(qib.astype(F32) * u, -1),
                jnp.einsum("bqjs,bqjd->bsd", m, (wb * qib).astype(cd),
                           preferred_element_type=F32))

    out = by_run(block, blocks, t, block_q, segments)
    d_qi, d_w = joined([x[1:3] for x in out], t)
    # a run's blocks meet the keys up to its end: the sum over them, after
    # which the later keys read 0
    d_ki = sum(jnp.pad(jnp.sum(x[3], axis=0),
                       ((0, 0), (0, t - x[3].shape[2]), (0, 0))) for x in out)
    return (sum(jnp.sum(x[0]) for x in out),
            (d_qi, d_w.astype(w.dtype), d_ki.astype(ki.dtype)))


def _alignment_backward(block_q, segments, gradients, ct):
    return (*((g * ct).astype(g.dtype) for g in gradients),
            None, None, None, None)


_alignment.defvjp(_alignment_with_gradient, _alignment_backward)


def alignment_loss(qi, w, ki, q, k, lse, mask, *, block_q: int = BLOCK_Q,
                   segments: int = SEGMENTS):
    """``alignment_value`` that hands back its gradient with its value: under
    differentiation ONE blockwise pass computes the loss and, in closed
    form, its gradient to ``qi``, ``w`` and ``ki`` at cotangent 1
    (``_alignment_with_gradient``); those three are all it keeps for the
    backward pass, which scales them by the loss's cotangent.  Not
    differentiated it is ``alignment_value``.  No gradient reaches ``q``,
    ``k``, ``lse`` or ``mask``.  An ``obs`` instant, ``alignment_loss_path``,
    names the path at each trace."""
    # (constants before the rule sees them: it hands nothing back for them)
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _alignment(qi, w, ki, q, k, lse, mask, block_q, segments)


def selection_mass(q, k, mask, *, block_q: int = BLOCK_Q,
                   segments: int = SEGMENTS):
    """The share of the DENSE causal attention's probability that the
    selected keys hold, the mean over heads and queries (``q``:
    ``scaled_queries``): what the alignment loss raises.  A reading, outside
    any training step."""
    b, t = q.shape[:2]
    block_q, _ = runs_of(t, block_q, segments)
    qb, kh = _heads_first(q, k, block_q)

    def block(keys, first, qi, bits):
        s = _scores(qi, kh[:, :, :keys])
        rows = jnp.minimum(first + jnp.arange(s.shape[-2]), t - 1)
        causal = jnp.arange(keys)[None, :] <= rows[:, None]
        e = jnp.where(causal, jnp.exp(
            s - jnp.max(jnp.where(causal, s, NEG), axis=-1, keepdims=True)), 0.0)
        held = jnp.sum(jnp.where(unpack_mask(bits, keys)[:, None, None], e, 0.0),
                       axis=-1) / jnp.sum(e, axis=-1)
        real = (first + jnp.arange(s.shape[-2])) < t
        return jnp.sum(jnp.where(real, jnp.mean(held, axis=(1, 2)), 0.0))

    out = by_run(block, (qb, blocked(mask, block_q)), t, block_q, segments)
    return sum(jnp.sum(x) for x in out) / (b * t)
