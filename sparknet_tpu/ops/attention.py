"""Multi-head attention layer — TPU-native extension.

The reference is a 2015 convnet framework with no attention anywhere
(SURVEY §5: "Long-context / sequence parallelism: absent entirely"), but
long-context is first-class here: this layer provides the single-device
path, ``sparknet_tpu.parallel.ring_attention`` provides the
sequence-parallel path over a mesh axis, and ``sparknet_tpu.ops.
pallas_attention`` the fused TPU kernel.  All three compute the same
function and are cross-checked in tests.

Blob layout (Caffe-style ordered list): [w_qkv (E, 3E), b_qkv (3E),
w_out (E, E), b_out (E)] with E = num_heads * head_dim.  Input/output
(B, T, E).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.config.schema import AttentionParameter, FillerParameter
from sparknet_tpu.ops.base import BlobDef, Layer, register


def mha_reference(q, k, v, causal: bool = False):
    """Plain attention on (B, T, H, D) tensors; the semantic ground truth
    for the blockwise/ring/pallas variants."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def blockwise_attention(q, k, v, block_size: int, causal: bool = False):
    """Online-softmax blockwise attention over the KV sequence — the
    memory-bounded form that ring attention distributes.  Matches
    ``mha_reference`` exactly (up to float assoc)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    nblocks = max(1, -(-tk // block_size))
    pad = nblocks * block_size - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblocks, block_size, h, d)
    vb = v.reshape(b, nblocks, block_size, h, d)
    scale = 1.0 / math.sqrt(d)
    # end-aligned causal convention, same as mha_reference's tril(k=tk-tq):
    # the last query attends to the last key
    q_pos = (tk - tq) + jnp.arange(tq)

    def body(i, carry):
        acc, m, l = carry
        k_i = kb[:, i]
        v_i = vb[:, i]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_i) * scale
        k_pos = i * block_size + jnp.arange(block_size)
        valid = k_pos < tk
        if causal:
            valid = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(valid[None, None], s, -jnp.inf)
        else:
            s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(-inf - -inf) guard: blocks where everything is masked
        alpha = jnp.exp(jnp.where(m == -jnp.inf, 0.0, m - m_new))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isnan(p), 0.0, p)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_i)
        return acc_new, m_new, l_new

    acc = jnp.zeros((b, h, tq, d), q.dtype)
    m = jnp.full((b, h, tq), -jnp.inf, q.dtype)
    l = jnp.zeros((b, h, tq), q.dtype)
    acc, m, l = jax.lax.fori_loop(0, nblocks, body, (acc, m, l))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3))  # -> (B, T, H, D)


@register
class Attention(Layer):
    """Self-attention over (B, T, E) bottoms."""

    TYPE = "Attention"

    def _p(self) -> AttentionParameter:
        return self.lp.attention_param or AttentionParameter()

    def _dims(self, bshape):
        p = self._p()
        e = bshape[-1]
        head_dim = p.head_dim or e // max(1, p.num_heads)
        if p.num_heads * head_dim != e:
            raise ValueError(
                f"layer {self.name!r}: num_heads*head_dim "
                f"{p.num_heads}x{head_dim} != embed dim {e}"
            )
        return p.num_heads, head_dim, e

    def blob_defs(self, bottom_shapes):
        p = self._p()
        _, _, e = self._dims(bottom_shapes[0])
        wf = p.weight_filler or FillerParameter(type="xavier")
        defs = [BlobDef((e, 3 * e), wf)]
        if p.bias_term:
            defs.append(BlobDef((3 * e,), FillerParameter(type="constant")))
        defs.append(BlobDef((e, e), wf))
        if p.bias_term:
            defs.append(BlobDef((e,), FillerParameter(type="constant")))
        return defs

    def out_shapes(self, bottom_shapes):
        return [bottom_shapes[0]]

    def apply(self, blobs, bottoms, rng, train):
        p = self._p()
        x = bottoms[0]
        h, d, e = self._dims(x.shape)
        b, t, _ = x.shape
        qkv = x @ blobs[0]
        if p.bias_term:
            qkv = qkv + blobs[1]
        q, k, v = jnp.split(qkv.reshape(b, t, 3, h, d), 3, axis=2)
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        out = blockwise_attention(
            q, k, v, block_size=min(p.block_size, t), causal=p.causal
        )
        from sparknet_tpu.ops.common import inverted_dropout

        out = inverted_dropout(out, rng, p.dropout_ratio, train, self.name)
        w_out_idx = 2 if p.bias_term else 1
        y = out.reshape(b, t, e) @ blobs[w_out_idx]
        if p.bias_term:
            y = y + blobs[3]
        return [y], None


# The kernels' blocks: a MiB of queries (a K/V head's group of heads, stacked
# as rows: 2,048 rows of 256 in bfloat16) against KERNEL_BLOCK_K keys.  On the
# v5e at T = 8,192, 16 / 2 heads of 256, forward + backward: 35.5 ms; twice
# the rows 34.6 ms and more than twice the compile time, twice the keys 36.2
# (PERF.md section 6, PR 30).
KERNEL_Q_BYTES = 1 << 20
KERNEL_BLOCK_K = 512


def kernel_block_q(group: int, d: int, cd, block_k: int) -> int:
    """The kernels' query block: ``KERNEL_Q_BYTES`` of a K/V head's group
    in whole rows of lanes (a group of 6 heads of 128 in float32 would be
    341 rows, which Mosaic refuses), at least one and at most a key
    block."""
    rows = KERNEL_Q_BYTES // (group * d * jnp.dtype(cd).itemsize)
    return min(max(rows // 128 * 128, 128), block_k)


def lowerable() -> bool:
    """``pallas_attention.lowerable``, imported when asked: ``ops/`` imports
    this module for every net, and Pallas takes 0.9 s that the image nets'
    set-up has no use for."""
    from sparknet_tpu.ops import pallas_attention

    return pallas_attention.lowerable()


def _path(t: int, hq: int, hkv: int, d: int, cd, block_q: int,
          segments: int, rope: int = 0, window=None):
    """Which of the two paths a causal attention of these shapes takes, and
    the ``attention_path`` instant that says so: ``(why not the kernels, ""
    where they run; block_q; block_k)``.  ``rope``: the width of a second
    score term (``causal_mla_attention``), whose values stay ``d`` wide;
    ``window``: the keys a query sees (``causal_gqa_attention``)."""
    from sparknet_tpu.ops import pallas_attention  # see lowerable

    backend = jax.default_backend()
    if not lowerable():
        why = f"no Pallas lowering on {backend}"
    elif not pallas_attention.accepts(hq, hkv, d, cd, rope):
        why = "heads of whole lanes in whole groups, bfloat16 or float32"
    else:
        why = ""
    if why:  # a run's query blocks each meet the key blocks up to its end
        block_k = block_q
        blocks = -(-t // block_q)
        ends = [min(lo + -(-blocks // segments), blocks)
                for lo in range(0, blocks, -(-blocks // segments))]
        met = (sum(hi * (hi - lo) for lo, hi in zip([0] + ends, ends)),
               blocks * blocks)
    else:
        # a second score term: a head a K/V head, so a block's rows are one
        # head's; keys x width as the selected-key attention holds them
        # (1,024 keys at D = 128): on the v5e at 2 x 8,192 tokens, 32 heads,
        # forward + backward 59.0 ms against 71.3 at 512 x 512, 63.0 at
        # 2,048 x 1,024 (PERF.md section 6, PR 37)
        block_k = min(KERNEL_BLOCK_K * 256 // d if rope else KERNEL_BLOCK_K, t)
        block_q = kernel_block_q(hq // hkv, d + rope, cd, block_k)
        met = pallas_attention.blocks_met(t, t, block_q, block_k, window)
    backward, backward_why = ("xla", "") if why else (
        pallas_attention.backward_path(t, d, rope, block_k))
    obs.instant("attention_path", cat="kernel",
                path="xla" if why else "pallas", why=why, backend=backend,
                t=t, hq=hq, hkv=hkv, d=d, d_qk=d + rope, d_v=d,
                dtype=cd.name, block_q=block_q, window=window,
                block_k=block_k, blocks_computed=met[0], blocks_total=met[1],
                backward=backward, backward_why=backward_why)
    return why, block_q, block_k


def causal_gqa_attention(q, k, v, *, block_q: int = 512, segments: int = 4,
                         compute_dtype=None, window=None,
                         out_dtype=jnp.float32):
    """Causal softmax attention with grouped K/V heads.

    ``q``: ``(B, T, Hq, D)``; ``k``, ``v``: ``(B, T, Hkv, D)``, each K/V head
    serving ``Hq // Hkv`` query heads (never repeated in memory).  Scores
    and softmax are float32; the products take their operands in
    ``compute_dtype``, ``q`` scaled by ``D ** -0.5`` before it is cast.
    ``window``: query ``i`` sees the keys ``i - window < j <= i`` alone.
    Returns ``(B, T, Hq, D)`` in ``out_dtype`` (``None``: the compute
    dtype, as ``causal_mla_attention`` hands it over, and why).

    Where Pallas lowers and ``pallas_attention.accepts`` the shapes, the
    K/V-blocked flash kernels (``ops/pallas_attention.py``): no score leaves
    VMEM, the key blocks above the diagonal are neither computed nor fetched
    (``(n + 1) / 2n`` of the score matrix at ``n`` key blocks; under a
    window the key blocks before it neither, so a query block meets the
    band alone), and the backward is the kernels' own.  Elsewhere
    ``_blockwise_gqa``, the same
    arithmetic in XLA, the fallback and the oracle the kernels are tested
    against; ``block_q`` and ``segments`` are its.  No switch picks between
    them: an ``obs`` instant names the path at each trace."""
    from sparknet_tpu.ops import pallas_attention  # see lowerable

    b, t, hq, d = q.shape
    hkv = k.shape[2]
    cd = jnp.dtype(compute_dtype or jnp.float32)
    why, block_q, block_k = _path(t, hq, hkv, d, cd, block_q, segments,
                                  window=window)
    q = (q.astype(jnp.float32) * d ** -0.5).astype(cd)
    out_dtype = out_dtype or cd
    if why:
        return _blockwise_gqa(q, k.astype(cd), v.astype(cd), block_q, segments,
                              window=window).astype(out_dtype)
    return pallas_attention.flash_attention(
        q, k.astype(cd), v.astype(cd), causal=True, block_q=block_q,
        block_k=block_k, scale=1.0, out_dtype=out_dtype, window=window)


def causal_mla_attention(q_nope, q_rope, k_nope, k_rope, v, *,
                         block_q: int = 512, segments: int = 4,
                         compute_dtype=None):
    """Causal softmax attention whose key is part per head and part one
    rotary key every head shares (multi-head latent attention, training
    form), the score wider than the value.

    ``q_nope``, ``k_nope``, ``v``: ``(B, T, H, D)``; ``q_rope``: ``(B, T, H,
    R)``; ``k_rope``: ``(B, T, 1, R)``.  ``s = (q_nope . k_nope + q_rope .
    k_rope) (D + R) ** -0.5``; dtypes as ``causal_gqa_attention`` (both
    parts of ``q`` scaled before they are cast).  Returns ``(B, T, H, D)``,
    the values never padded to the score's width, in the COMPUTE dtype: the
    output projection that follows rounds it there in any case, and what a
    layer's recomputation keeps of the kernels (their output, and the
    cotangent XLA hands them) is then half of float32's, 0.67 GiB over five
    layers at 2 x 8,192 tokens.

    Where the kernels take the shapes (``pallas_attention.accepts(.., rope=
    R)``), ``mla_flash_attention``: the score's two terms are two products
    into one tile in VMEM, the one rope key is read where it lies by every
    head (never repeated in memory), and of the MXU's passes 18% run half
    empty (the 64-deep rope contraction; ``ops/pallas_attention.py``).
    Elsewhere the rope key is repeated over the heads and joined to the
    other part, and ``_blockwise_gqa`` runs on heads ``D + R`` wide against
    values ``D`` wide: the CPU's path and the kernels' oracle."""
    from sparknet_tpu.ops import pallas_attention  # see lowerable

    b, t, h, d = q_nope.shape
    rope = q_rope.shape[-1]
    cd = jnp.dtype(compute_dtype or jnp.float32)
    why, block_q, block_k = _path(t, h, h, d, cd, block_q, segments, rope)
    scale = (d + rope) ** -0.5
    q_nope, q_rope = ((x.astype(jnp.float32) * scale).astype(cd)
                      for x in (q_nope, q_rope))
    k_nope, k_rope, v = (x.astype(cd) for x in (k_nope, k_rope, v))
    if why:
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, t, h, rope))], axis=-1)
        return _blockwise_gqa(q, k, v, block_q, segments).astype(cd)
    return pallas_attention.mla_flash_attention(
        q_nope, q_rope, k_nope, k_rope[:, :, 0], v, block_q=block_q,
        block_k=block_k, scale=1.0)


# -- the XLA blockwise passes: query blocks in runs, a keep-mask as bits ------
NEG = -1e30  # a finite mask: see ``_blockwise_gqa``


def runs_of(t: int, block_q: int, segments: int):
    """``(block_q, [(first block, end block, keys)])``: the query blocks of a
    sequence of ``t`` in at most ``segments`` runs; a run's queries meet the
    first ``keys`` keys, which reach its own end."""
    block_q = min(block_q, t)
    blocks = -(-t // block_q)
    per_run = -(-blocks // segments)
    return block_q, [
        (lo, min(lo + per_run, blocks), min(min(lo + per_run, blocks) * block_q, t))
        for lo in range(0, blocks, per_run)]


def blocked(x, block_q: int):
    """``(B, T, ...)`` -> ``(blocks, B, block_q, ...)``, zeros after ``T``."""
    b, t = x.shape[:2]
    pad = (-t) % block_q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape(b, (t + pad) // block_q, block_q, *x.shape[2:])
    return jnp.moveaxis(x, 1, 0)


def unblocked(x, t: int):
    """``(blocks, B, block_q, ...)`` -> ``(B, T, ...)``."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])[:, :t]


def by_run(fn, queries, t: int, block_q: int, segments: int):
    """``fn(keys, first, *blocks)`` over every query block, one ``lax.map`` a
    run (a ``lax.map`` needs one shape for all its blocks, and a run meets
    only the keys up to its own end, a static slice); ``queries`` are
    ``(blocks, B, block_q, ...)`` arrays or, a run, lists of them.  Returns
    the outputs a run, each ``(run's blocks, ...)``."""
    block_q, runs = runs_of(t, block_q, segments)
    out = []
    for r, (lo, hi, keys) in enumerate(runs):
        mine = [x[r] if isinstance(x, (list, tuple)) else x[lo:hi]
                for x in queries]
        out.append(jax.lax.map(
            lambda xs, keys=keys: fn(keys, xs[0], *xs[1:]),
            (jnp.arange(lo, hi) * block_q, *mine)))
    return out


def joined(per_run, t: int):
    """The runs' outputs as one ``(B, T, ...)`` array a leaf."""
    return jax.tree_util.tree_map(
        lambda *xs: unblocked(jnp.concatenate(xs, axis=0), t), *per_run)


BITS = 32


def words_of(t: int) -> int:
    """Words of 32 bits a row of a keep-mask: key ``s`` is bit ``s // words``
    of word ``s % words``, so that packing and unpacking cut the key axis
    into whole slices and never reshape it."""
    return -(-t // BITS)


def pack_mask(keep, words: int):
    """``(..., S)`` bool -> ``(..., words)`` uint32; ``S <= 32 * words``."""
    s = keep.shape[-1]
    out = jnp.zeros((*keep.shape[:-1], words), jnp.uint32)
    for bit in range(-(-s // words)):
        piece = keep[..., bit * words:(bit + 1) * words].astype(jnp.uint32)
        if piece.shape[-1] < words:
            piece = jnp.pad(piece, [(0, 0)] * (piece.ndim - 1)
                            + [(0, words - piece.shape[-1])])
        out = out | (piece << jnp.uint32(bit))
    return out


def unpack_mask(packed, s: int):
    """The first ``s`` keys of ``pack_mask``'s rows: ``(..., s)`` bool."""
    words = packed.shape[-1]
    pieces = [(packed >> jnp.uint32(bit)) & jnp.uint32(1)
              for bit in range(-(-s // words))]
    return jnp.concatenate(pieces, axis=-1)[..., :s].astype(bool)


def _blockwise_gqa(q, k, v, block_q, segments, keep=None, window=None):
    """``causal_gqa_attention`` in XLA, in query blocks; ``q`` comes scaled
    and all three in the compute dtype.  The query blocks run one after
    another (``lax.map`` over ``jax.checkpoint``ed blocks), so no more than
    ``block_q x T`` scores a head exist at once, forward or backward; with
    four runs (``by_run``) 5/8 of the full score matrix is computed where
    causality needs 1/2.  A ``window`` is a mask on the same blocks.

    With ``keep`` (``(B, T, words_of(T))`` bits, ``pack_mask``'s layout, a
    subset of the causal keys, unpacked a block at a time) the softmax runs
    over each query's kept keys alone, and the rows' log-sum-exp ``(B, T,
    Hq)`` float32 is returned beside the output."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    f32 = jnp.float32
    cd = q.dtype
    block_q, _ = runs_of(t, block_q, segments)
    # heads before positions, the layout batched products are made for: a
    # block of queries is (B, Hkv, group * block_q, D) against (B, Hkv, S, D)
    qb = blocked(q, block_q)
    blocks = qb.shape[0]
    qb = qb.reshape(blocks, b, block_q, hkv, group, d).transpose(0, 1, 3, 4, 2, 5)
    qb = qb.reshape(blocks, b, hkv, group * block_q, d)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    row = jnp.tile(jnp.arange(block_q), group)[:, None]

    def block(keys, first, qi, *bits):
        ki, vi = kh[:, :, :keys], vh[:, :, :keys]
        s = jnp.einsum("bkrd,bksd->bkrs", qi, ki, preferred_element_type=f32)
        if bits:  # (B, block_q, keys), the same for every head of the group
            kept = jnp.tile(unpack_mask(bits[0], keys), (1, group, 1))[:, None]
        else:
            kept = first + row >= jnp.arange(keys)[None, :]
            if window:
                kept = kept & (first + row - jnp.arange(keys)[None, :] < window)
        # a finite mask and the softmax written out, normalised after the
        # second product: on the v5e `where(.., -inf)` + `jax.nn.softmax`
        # in float32 runs 16 x slower than this (49 ms against 3 ms a block
        # of 4096 x 8192 scores, PERF.md section 6, PR 27).  Every real row
        # keeps its own position, so its maximum is a real score; a row of
        # padding under a keep-mask keeps none and reads the values' mean,
        # cut off below
        s = jnp.where(kept, s, NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        total = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bkrs,bksd->bkrd", e.astype(cd), vi,
                       preferred_element_type=f32) / total
        return o if keep is None else (o, (m + jnp.log(total))[..., 0])

    out = by_run(jax.checkpoint(block, static_argnums=(0,)),
                 (qb,) if keep is None else (qb, blocked(keep, block_q)),
                 t, block_q, segments)
    # (blocks, B, Hkv, group * block_q, ...) -> (B, T, Hq, ...)
    heads = lambda x: unblocked(jnp.moveaxis(  # noqa: E731
        x.reshape(blocks, b, hkv, group, block_q, *x.shape[4:]), 4, 2),
        t).reshape(b, t, hq, *x.shape[4:])
    if keep is None:
        return heads(jnp.concatenate(out, axis=0))
    return tuple(heads(jnp.concatenate([x[i] for x in out], axis=0))
                 for i in range(2))
