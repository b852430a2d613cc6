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


def lowerable() -> bool:
    """``pallas_attention.lowerable``, imported when asked: ``ops/`` imports
    this module for every net, and Pallas takes 0.9 s that the image nets'
    set-up has no use for."""
    from sparknet_tpu.ops import pallas_attention

    return pallas_attention.lowerable()


def causal_gqa_attention(q, k, v, *, block_q: int = 512, segments: int = 4,
                         compute_dtype=None):
    """Causal softmax attention with grouped K/V heads.

    ``q``: ``(B, T, Hq, D)``; ``k``, ``v``: ``(B, T, Hkv, D)``, each K/V head
    serving ``Hq // Hkv`` query heads (never repeated in memory).  Scores
    and softmax are float32; the products take their operands in
    ``compute_dtype``, ``q`` scaled by ``D ** -0.5`` before it is cast.
    Returns ``(B, T, Hq, D)`` float32.

    Where Pallas lowers and ``pallas_attention.accepts`` the shapes, the
    K/V-blocked flash kernels (``ops/pallas_attention.py``): no score leaves
    VMEM, the key blocks above the diagonal are neither computed nor fetched
    (``(n + 1) / 2n`` of the score matrix at ``n`` key blocks), and the
    backward is the kernels' own.  Elsewhere ``_blockwise_gqa``, the same
    arithmetic in XLA, the fallback and the oracle the kernels are tested
    against; ``block_q`` and ``segments`` are its.  No switch picks between
    them: an ``obs`` instant names the path at each trace."""
    from sparknet_tpu.ops import pallas_attention  # see lowerable

    b, t, hq, d = q.shape
    hkv = k.shape[2]
    cd = jnp.dtype(compute_dtype or jnp.float32)
    backend = jax.default_backend()
    if not lowerable():
        why = f"no Pallas lowering on {backend}"
    elif not pallas_attention.accepts(hq, hkv, d, cd):
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
        block_k = min(KERNEL_BLOCK_K, t)
        rows = KERNEL_Q_BYTES // (hq // hkv * d * cd.itemsize)
        block_q = min(max(rows, pallas_attention.LANES), block_k)
        met = pallas_attention.blocks_met(t, t, block_q, block_k)
    obs.instant("attention_path", cat="kernel",
                path="xla" if why else "pallas", why=why, backend=backend,
                t=t, hq=hq, hkv=hkv, d=d, dtype=cd.name, block_q=block_q,
                block_k=block_k, blocks_computed=met[0], blocks_total=met[1])
    if why:
        return _blockwise_gqa(q, k, v, block_q, segments, cd)
    q = (q.astype(jnp.float32) * d ** -0.5).astype(cd)
    return pallas_attention.flash_attention(
        q, k.astype(cd), v.astype(cd), causal=True, block_q=block_q,
        block_k=block_k, scale=1.0, out_dtype=jnp.float32)


def _blockwise_gqa(q, k, v, block_q, segments, cd):
    """``causal_gqa_attention`` in XLA, in query blocks.  The query blocks
    run one after another (``lax.map`` over ``jax.checkpoint``ed blocks), so
    no more than ``block_q x T`` scores a head exist at once, forward or
    backward.  A ``lax.map`` needs one shape for all its blocks: the sequence
    is cut into ``segments`` runs of blocks, and a run meets only the keys up
    to its own end, a static slice, so with four runs 5/8 of the full score
    matrix is computed where causality needs 1/2."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    f32 = jnp.float32
    pad = (-t) % block_q  # padded keys lie after every real query: masked
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    blocks = (t + pad) // block_q
    # heads before positions, the layout batched products are made for: a
    # block of queries is (B, Hkv, group * block_q, D) against (B, Hkv, S, D)
    q = (q.astype(f32) * d ** -0.5).astype(cd)
    q = q.reshape(b, blocks, block_q, hkv, group, d).transpose(1, 0, 3, 4, 2, 5)
    q = q.reshape(blocks, b, hkv, group * block_q, d)
    k = k.astype(cd).transpose(0, 2, 1, 3)
    v = v.astype(cd).transpose(0, 2, 1, 3)
    row = jnp.tile(jnp.arange(block_q), group)[:, None]

    @jax.checkpoint
    def block(qi, first, ki, vi):
        s = jnp.einsum("bkrd,bksd->bkrs", qi, ki, preferred_element_type=f32)
        # a finite mask and the softmax written out, normalised after the
        # second product: on the v5e `where(.., -inf)` + `jax.nn.softmax`
        # in float32 runs 16 x slower than this (49 ms against 3 ms a block
        # of 4096 x 8192 scores, PERF.md section 6, PR 27).  Every row keeps
        # its own position, so its maximum is a real score
        s = jnp.where(first + row >= jnp.arange(ki.shape[2])[None, :], s,
                      -1e30)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.einsum("bkrs,bksd->bkrd", e.astype(cd), vi,
                       preferred_element_type=f32)
        return o / jnp.sum(e, axis=-1, keepdims=True)

    per_run = -(-blocks // segments)
    out = []
    for lo in range(0, blocks, per_run):
        hi = min(lo + per_run, blocks)
        ki, vi = k[:, :, :hi * block_q], v[:, :, :hi * block_q]
        out.append(jax.lax.map(
            lambda x: block(x[0], x[1], ki, vi),
            (q[lo:hi], jnp.arange(lo, hi) * block_q)))
    # (blocks, B, Hkv, group * block_q, D) -> (B, T, Hq, D)
    out = jnp.concatenate(out, axis=0)
    out = out.reshape(blocks, b, hkv, group, block_q, d)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, t + pad, hq, d)
    return out[:, :t]
