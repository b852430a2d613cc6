"""Ring attention: sequence parallelism over a mesh axis.

Long sequences shard along time across the ``sp`` mesh axis; each device
holds (B, T/N, H, D) of Q, K, V.  KV shards rotate around the ring with
``lax.ppermute`` (one ICI hop per step, overlapping compute with the next
transfer) while each device accumulates its queries' attention with the
online-softmax (flash) recurrence — so attention over a sequence N times
longer than one chip could hold costs N ring steps and O(T/N) memory per
chip.  This is the blockwise/ring-attention construction from the public
literature (Liu et al., "Ring Attention with Blockwise Transformers"),
expressed with XLA collectives.

Use inside ``shard_map`` (see ``ring_self_attention`` for the wrapped
form).  Exactness: matches single-device attention up to float
associativity — pinned by tests on the CPU mesh.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

def _ring_carry(x, axis_name, *like):
    """Type a freshly built loop carry as varying over the ring axis and
    over every mesh axis the operands ``like`` already vary on.  The scan
    that carries it requires equal varying-axes types in and out, and the
    loop body mixes the carry with q/k/v — which on a dp x sp mesh vary
    over both axes, not ``axis_name`` alone."""
    axes = {axis_name}.union(*(jax.typeof(a).vma for a in like))
    return lax.pcast(x, tuple(sorted(axes)), to="varying")


def _merge_partials(o1, lse1, o2, lse2):
    """Online-softmax combine of two partial attentions over disjoint
    key sets: ``(o, lse)`` each normalized within its own keys, lse the
    row logsumexp ( -inf == no visible keys).  Differentiable — every
    -inf/0 leg is guarded so no NaN survives into either the value or
    the cotangent path."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m == -jnp.inf, 0.0, m)
    w1 = jnp.exp(lse1 - m_safe)  # exp(-inf) = 0: absent side drops out
    w2 = jnp.exp(lse2 - m_safe)
    den = w1 + w2
    den_safe = jnp.maximum(den, 1e-30)
    o = (w1[..., None] * o1 + w2[..., None] * o2) / den_safe[..., None]
    lse = jnp.where(den > 0, m_safe + jnp.log(den_safe), -jnp.inf)
    return o, lse


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   use_flash=None):
    """Attention over ring-sharded KV. Call under shard_map; q/k/v are the
    local shards (B, T_local, H, D); returns the local output shard.

    ``use_flash``: the per-shard local attention of each ring step runs
    through the Pallas flash kernel (``ops.pallas_attention.
    flash_attention_step`` — absolute-position causal mask, (o, lse)
    merged with the online-softmax combine, exact gradients via the
    kernel's custom_vjp).  ``None`` takes the kernel wherever it lowers
    natively (``pallas_attention.lowerable()``); ``True`` forces it
    (interpreter mode off-TPU — the test/bench pin), ``False`` keeps
    the einsum path, which every backend but the TPU takes by default."""
    from sparknet_tpu.ops import pallas_attention

    if use_flash is None:
        use_flash = pallas_attention.lowerable()
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    perm = [(j, (j + 1) % n) for j in range(n)]

    if use_flash:
        def flash_step(i, o_acc, lse_acc, k_cur, v_cur):
            src = (idx - i) % n  # whose KV shard we hold at ring step i
            o_s, lse_s = pallas_attention.flash_attention_step(
                q, k_cur, v_cur,
                q_offset=idx * tq, k_offset=src * tk, causal=causal,
            )
            return _merge_partials(
                o_acc, lse_acc, o_s.astype(o_acc.dtype), lse_s
            )

        def flash_body(i, carry):
            o_acc, lse_acc, k_cur, v_cur = carry
            o_acc, lse_acc = flash_step(i, o_acc, lse_acc, k_cur, v_cur)
            k_next = lax.ppermute(k_cur, axis_name, perm)
            v_next = lax.ppermute(v_cur, axis_name, perm)
            return o_acc, lse_acc, k_next, v_next

        o_acc = _ring_carry(
            jnp.zeros((b, h, tq, d), jnp.float32), axis_name, q, k, v
        )
        lse_acc = _ring_carry(
            jnp.full((b, h, tq), -jnp.inf, jnp.float32), axis_name, q, k, v
        )
        o_acc, lse_acc, k_last, v_last = lax.fori_loop(
            0, n - 1, flash_body, (o_acc, lse_acc, k, v)
        )
        o_acc, _ = flash_step(n - 1, o_acc, lse_acc, k_last, v_last)
        return jnp.transpose(o_acc, (0, 2, 1, 3)).astype(q.dtype)

    q_pos = idx * tq + jnp.arange(tq)  # global query positions

    def accumulate(i, acc, m, l, k_cur, v_cur):
        src = (idx - i) % n  # whose KV shard we hold at ring step i
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur) * scale
        if causal:
            k_pos = src * tk + jnp.arange(tk)
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(jnp.where(m == -jnp.inf, 0.0, m - m_new))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur
        )
        return acc_new, m_new, l_new

    def body(i, carry):
        acc, m, l, k_cur, v_cur = carry
        acc, m, l = accumulate(i, acc, m, l, k_cur, v_cur)
        # rotate KV one hop around the ring (ICI neighbor exchange)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_next, v_next

    acc = _ring_carry(jnp.zeros((b, h, tq, d), q.dtype), axis_name, q, k, v)
    m = _ring_carry(
        jnp.full((b, h, tq), -jnp.inf, q.dtype), axis_name, q, k, v
    )
    l = _ring_carry(jnp.zeros((b, h, tq), q.dtype), axis_name, q, k, v)
    # n-1 rotate-and-accumulate steps, then the last shard accumulates
    # without the (discarded) final exchange
    acc, m, l, k_last, v_last = lax.fori_loop(
        0, n - 1, body, (acc, m, l, k, v)
    )
    acc, m, l = accumulate(n - 1, acc, m, l, k_last, v_last)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3))


def ring_self_attention(
    mesh: Mesh, axis: str = "sp", causal: bool = False, use_flash=None
):
    """Returns a fn (q, k, v) -> out with q/k/v (B, T, H, D) sharded
    along T over ``axis``; the driver-facing wrapper.  T must divide
    evenly by the axis size (the ring rotates equal shards) — a ragged
    T is rejected up front with the fix spelled out, instead of the
    shard_map partitioner's generic shape error."""
    from sparknet_tpu.ops import pallas_attention

    spec = P(None, axis, None, None)
    n = mesh.shape[axis]
    # Off the TPU a forced flash kernel runs in Pallas's HLO interpreter,
    # which slices each (sp-varying) block by its own unvarying grid
    # indices — a mix jax 0.9.0's varying-axes check rejects.  Every
    # operand here is sharded over ``axis`` alone, so nothing depends on
    # the inferred replication; compiled kernels keep the check.
    interpreted = bool(use_flash) and not pallas_attention.lowerable()

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not interpreted,
    )
    def inner(q, k, v):
        return ring_attention(q, k, v, axis, causal=causal,
                              use_flash=use_flash)

    def fn(q, k, v):
        for name, arr in (("q", q), ("k", k), ("v", v)):
            if arr.ndim != 4:
                raise ValueError(
                    f"ring_self_attention: {name} must be (B, T, H, D), "
                    f"got shape {tuple(arr.shape)}"
                )
            if arr.shape[1] % n:
                raise ValueError(
                    f"ring_self_attention: {name} has T={arr.shape[1]} "
                    f"which does not divide over the {n}-way {axis!r} "
                    "ring — pad the sequence or pick T a multiple of "
                    f"{n}"
                )
        return inner(q, k, v)

    return fn
