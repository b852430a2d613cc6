"""The gated delta rule, chunk-parallel — the recurrence of a Gated
DeltaNet linear-attention layer.

Per head, with a state ``S`` of shape ``(dk, dv)`` that starts at zero::

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T
    o_t = S^T q_t

``gated_delta_rule`` computes it over chunks of ``chunk`` tokens in the WY
form (Yang et al., "Gated Delta Networks", 2024): inside a chunk the
``d_t`` solve a unit lower-triangular system, ``(I + L) D = U - W S0``
with ``L_tj = beta_t exp(gamma_t - gamma_j) (k_t . k_j)`` for ``j < t``
and ``gamma`` the running sum of ``g`` inside the chunk; across chunks a
``lax.scan`` carries ``S``.  Everything that does not need ``S`` (the
triangular inverse, ``U``, ``W``, the masked ``q k^T``) is one stage over all
chunks: on the TPU, at shapes they take (``pallas_delta_rule.accepts``), two
Pallas kernels that keep a chunk's matrices in VMEM
(``ops/pallas_delta_rule.py``: forward, and a backward of its own that
differentiates the inverse by its identity); elsewhere ``_within_chunks``
below, the same arithmetic in XLA and the oracle the kernels are tested
against.  No switch picks between them.

Precision: ``g``, ``gamma``, every ``exp``, the triangular inverse (its
operands; its products at full precision in float32, at three bf16 passes
beside a lower ``compute_dtype``) and the carried state are float32 whatever
``compute_dtype`` is; the large
matrix products take their operands in ``compute_dtype`` and accumulate
in float32.  The scan's backward pass is jax's own: it keeps one ``S`` a
chunk (``T / chunk`` states a head); ``_within_chunks``' is too, through
every product of the inverse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.ops import pallas_delta_rule
from sparknet_tpu.ops.pallas_attention import lowerable

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(strict_lower, precision=HIGHEST):
    """``(I + L)^-1`` for ``L`` strictly lower triangular ``(..., C, C)``,
    ``C`` a power of two, by block forward substitution: the inverses of
    the diagonal blocks of size ``s`` give those of size ``2s`` as
    ``inv - inv @ P_s @ inv``, ``P_s`` the part of ``L`` in the lower-left
    quarter of each ``2s`` block.  ``log2(C)`` levels of two small matrix
    products on float32 operands: no power of ``L`` is ever formed, so
    correlated keys do not cancel catastrophically."""
    c = strict_lower.shape[-1]
    if c & (c - 1):
        raise ValueError(f"chunk={c}: the block inverse needs a power of two")
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    inv = jnp.broadcast_to(jnp.eye(c, dtype=F32), strict_lower.shape)
    s = 1
    while s < c:
        quarter = (
            (row // (2 * s) == col // (2 * s))
            & (row % (2 * s) >= s)
            & (col % (2 * s) < s)
        )
        p = jnp.where(quarter, strict_lower, 0.0)
        inv = inv - jnp.matmul(
            jnp.matmul(inv, p, precision=precision), inv, precision=precision
        )
        s *= 2
    return inv


def _within_chunks(q, k, v, g, beta, cd):
    """Everything that does not need the carried state, for all chunks at
    once.  ``q``, ``k``, ``v``: ``(..., C, d)``; ``g``, ``beta``:
    ``(..., C)`` float32.  Returns what the scan over chunks reads:
    ``u`` float32 (it is added), the rest in ``cd`` (the dtype their
    products take them in; the rounding is the same one, made once)."""
    chunk = q.shape[-2]

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(cd), y.astype(cd),
                          preferred_element_type=F32)

    gamma = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(gamma_i - gamma_j) for i >= j; the masked half is never exponentiated
    diff = jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(diff), 0.0)
    k_beta = k.astype(F32) * beta[..., None]
    strict = jnp.tril(mm("...id,...jd->...ij", k_beta, k) * decay, -1)
    # the inverse's consumers round it to ``cd``: beside bfloat16 three
    # bf16 passes (about 2^-16) are exact enough, and half the time of six
    inv = _unit_lower_inverse(
        strict, HIGHEST if jnp.dtype(cd) == F32 else jax.lax.Precision.HIGH)
    u = mm("...ij,...jd->...id", inv, v.astype(F32) * beta[..., None])
    w = mm("...ij,...jd->...id", inv, k_beta * jnp.exp(gamma)[..., None])
    qk = mm("...id,...jd->...ij", q, k) * decay
    q_in = q.astype(F32) * jnp.exp(gamma)[..., None]
    last = gamma[..., -1:]
    k_out = k.astype(F32) * jnp.exp(last - gamma)[..., None]
    return (u, w.astype(cd), qk.astype(cd), q_in.astype(cd),
            k_out.astype(cd), jnp.exp(last[..., 0]))


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                     compute_dtype=None):
    """``q``, ``k``: ``(B, T, H, dk)`` (normalised and scaled by the
    caller); ``v``: ``(B, T, H, dv)``; ``g`` (log decay, <= 0) and ``beta``:
    ``(B, T, H)``.  Returns ``o``: ``(B, T, H, dv)`` float32.  ``T`` need
    not divide by ``chunk``: the tail is padded with tokens that write
    nothing (``k = v = beta = g = 0``).  The work inside chunks is the
    Pallas kernels' where they lower and take the shapes, else
    ``_within_chunks``; an ``obs`` instant names the path at each trace."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    cd = jnp.dtype(compute_dtype or F32)
    backend = jax.default_backend()
    if not lowerable():
        why = f"no Pallas lowering on {backend}"
    elif not pallas_delta_rule.accepts(chunk, dk, dv):
        why = "chunk must tile 128 tokens by 16s, dk and dv whole lanes"
    else:
        why = ""
    obs.instant("delta_rule_path", cat="kernel",
                path="xla" if why else "pallas", why=why, backend=backend,
                chunk=chunk, dk=dk, dv=dv, dtype=cd.name)
    pad = (-t % chunk) if why else pallas_delta_rule.padded_length(t) - t
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, widths + ((0, 0),)) for x in (q, k, v))
        g, beta = jnp.pad(g, widths), jnp.pad(beta, widths)
    n = (t + pad) // chunk
    g, beta = g.astype(F32), beta.astype(F32)

    def blocks(x):  # (B, T, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    # chunk-major, as the scan reads them: u, w, qk, q_in, k_out, the decay
    if why:
        per_chunk = _within_chunks(
            *(blocks(x) for x in (q, k, v, g, beta)), cd)
    else:
        per_chunk = (
            *pallas_delta_rule.within_chunks(
                *(x.astype(F32) for x in (q, k, v)), g, beta, chunk, cd),
            jnp.exp(jnp.sum(blocks(g), axis=-1)))
    u = per_chunk[0]

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(cd), y.astype(cd),
                          preferred_element_type=F32)

    @jax.checkpoint  # backward keeps one S a chunk and recomputes the rest
    def step(s, xs):
        u_n, w_n, qk_n, q_n, k_n, decay_n = xs
        d = u_n - mm("bhid,bhde->bhie", w_n, s)
        o = mm("bhid,bhde->bhie", q_n, s) + mm("bhij,bhje->bhie", qk_n, d)
        s = s * decay_n[..., None, None] + mm("bhid,bhie->bhde", k_n, d)
        return s, o

    s0 = jnp.zeros((b, h, dk, dv), F32)
    # inside a shard_map the carry must vary over the axes the inputs do
    vma = tuple(sorted(jax.typeof(u).vma))
    if vma:
        s0 = jax.lax.pcast(s0, vma, to="varying")
    _, o = jax.lax.scan(step, s0, per_chunk)
    # (N, B, H, C, dv) -> (B, T, H, dv)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)
    return o[:, :t]
