"""Sparse mixture-of-experts pieces: a top-k router over ALL experts
(softmax or sigmoid scores, with or without a selection bias) and the part
of the result that the experts HELD HERE give.

An expert-parallel deployment divides a layer's experts over chips; each
chip routes every token over the whole published router width, and adds
only the terms of its own contiguous range ``lo .. lo + n - 1``.  What the
absent experts would add is left out (on the chips that hold them it is
their part); on one chip there is no exchange, and nothing here stands in
for one.

No token is dropped and no expert has a capacity.  The held assignments
are sorted by expert and go through one grouped matrix product per
projection, then a weighted scatter-add.  XLA needs a static row count: the
grouped path takes ``fast_rows`` rows, sized at ``slack`` times the expected
number of held assignments, and when a batch routes more than that to the
held experts the same grouped products run over chunks of tokens small
enough that whatever a chunk routes fits (``lax.cond``; exact, slower,
rare).  The products of the grouped path: on the TPU at bfloat16 and widths
of whole lanes the kernels of ``ops/pallas_grouped_matmul.py``, which do no
work on the rows past the held assignments and write them as zero;
elsewhere (float32, the CPU, ragged widths) ``jax.lax.ragged_dot``, which
multiplies every row, so there the rows past the held assignments ride in
the last expert's group with weight 0.  The chunked path keeps
``ragged_dot``.  An ``obs`` instant, ``grouped_matmul_path``, names the
choice at each trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sparknet_tpu import obs
from sparknet_tpu.ops.attention import lowerable  # Pallas, imported when asked

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# rows of the grouped path over the expected held assignments.  Seeded
# weights on Zipf tokens send the held experts 0.94 to 1.07 times the
# expectation, and 28 steps of training move a layer's share as far as 1.18
# times it (or to none of it) (the v5e, PERF.md section 6, PR 27).  The rest
# is room for a router that goes on drifting, since a step past the rows
# costs ``tokens * top_k / rows`` times the experts' work
ROWS_SLACK = 2.0


def route(x, w_router, top_k: int, *, scores: str = "softmax", bias=None,
          scale: float = 1.0, eps: float = 0.0):
    """``x``: ``(N, E)``; ``w_router``: ``(E, experts)``.  Float32 at full
    precision throughout: a top-k over rounded scores picks other experts.
    ``scores`` is ``softmax`` over the experts or ``sigmoid`` of each
    logit.  With a selection ``bias`` ``(experts,)`` the top-k is taken on
    ``scores + bias`` and the weights are gathered from the UNbiased scores;
    no gradient reaches the bias.  The weights are renormalised, ``w /
    (sum(w) + eps)``, then times ``scale``.  Returns the weights ``(N,
    top_k)`` and the expert ids ``(N, top_k)``."""
    logits = jnp.dot(x.astype(F32), w_router.astype(F32), precision=HIGHEST)
    if scores not in ("softmax", "sigmoid"):
        raise ValueError(f"router scores {scores!r}: softmax or sigmoid")
    s = (jax.nn.softmax(logits, axis=-1) if scores == "softmax"
         else jax.nn.sigmoid(logits))
    if bias is None:
        weights, ids = jax.lax.top_k(s, top_k)
    else:
        _, ids = jax.lax.top_k(
            s + jax.lax.stop_gradient(bias.astype(F32)), top_k)
        weights = jnp.take_along_axis(s, ids, axis=-1)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    # no operation is traced for a term at its identity (eps 0, scale 1):
    # a softmax router's program stays what it was
    weights = weights / (total + eps if eps else total)
    return (weights * scale if scale != 1.0 else weights), ids


def load(ids, experts: int):
    """Assignments each of the ``experts`` received: ``(experts,)`` float32
    from the ids ``(N, top_k)`` of one step's tokens."""
    return jnp.zeros((experts,), F32).at[ids.reshape(-1)].add(1.0)


def balance(bias, load, rate: float):
    """The selection bias after one step of the balancing rule that goes
    with it (auxiliary-loss-free balancing, arXiv:2408.15664): up by ``rate``
    where an expert received less than the mean load, down by ``rate`` where
    more.  No gradient and no optimizer state: the step's ``load`` decides."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def plan(ids, lo: int, n: int):
    """How the held assignments are laid out for the grouped products: the
    order that puts them first, by expert (stable, so by token inside one),
    and how many each held expert receives.  ``ids``: ``(N, top_k)``.
    Returns ``(order (N * top_k,), counts (n,))``, both int32."""
    local = ids.reshape(-1) - lo
    key = jnp.where((local >= 0) & (local < n), local, n)
    counts = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    return jnp.argsort(key, stable=True).astype(jnp.int32), counts


def fast_rows_for(tokens: int, top_k: int, experts: int, n: int,
                  slack: float = ROWS_SLACK, multiple: int = 256) -> int:
    """Rows of the grouped path: ``slack`` x the expected held assignments
    (``tokens * top_k * n / experts``), rounded up to ``multiple`` and never
    more than every assignment there is."""
    expected = tokens * top_k * n / experts
    rows = -(-int(slack * expected) // multiple) * multiple
    return max(min(top_k, n), min(rows, tokens * min(top_k, n)))


def gated_mlp(x, gate, up, down, compute_dtype=None):
    """``down(silu(gate(x)) * up(x))``, no bias."""
    cd = compute_dtype or F32
    dot = lambda a, w: jnp.dot(  # noqa: E731
        a.astype(cd), w.astype(cd), preferred_element_type=F32)
    return dot(jax.nn.silu(dot(x, gate)) * dot(x, up), down)


def _grouped_path(rows: int, width: int, inner: int, cd) -> str:
    """Why the grouped path's ``rows`` x ``width`` products with experts
    ``inner`` wide do NOT run in the Pallas kernels, ``""`` where they do;
    the ``grouped_matmul_path`` instant says which path a trace took."""
    from sparknet_tpu.ops import pallas_grouped_matmul  # see lowerable

    backend = jax.default_backend()
    if not lowerable():
        why = f"no Pallas lowering on {backend}"
    elif not pallas_grouped_matmul.accepts(rows, width, inner, cd):
        why = pallas_grouped_matmul.ACCEPTS
    else:
        why = ""
    obs.instant("grouped_matmul_path", cat="kernel",
                path="ragged_dot" if why else "pallas", rows=rows, why=why,
                backend=backend, width=width, inner=inner,
                dtype=jnp.dtype(cd).name)
    return why


def _grouped(x, weights, ids, order, counts, gate, up, down, rows, cd,
             kernel=False):
    """The held assignments' sum by grouped products over ``rows`` rows;
    needs ``sum(counts) <= rows``.  Rows beyond the held assignments carry
    weight 0: the ``kernel``s skip them, ``ragged_dot`` runs them in the
    last expert's group."""
    tokens, top_k = ids.shape
    n = gate.shape[0]
    total = jnp.sum(counts)
    first = order[:rows]
    token = first // top_k
    w = jnp.where(jnp.arange(rows) < total, weights.reshape(-1)[first], 0.0)
    if kernel:
        from sparknet_tpu.ops.pallas_grouped_matmul import grouped_matmul

        dot = lambda a, b: grouped_matmul(  # noqa: E731
            a.astype(cd), b.astype(cd), counts)
    else:
        sizes = counts.at[n - 1].add(rows - total)
        dot = lambda a, b: jax.lax.ragged_dot(  # noqa: E731
            a.astype(cd), b.astype(cd), sizes, preferred_element_type=F32)
    xs = x[token]
    y = dot(jax.nn.silu(dot(xs, gate)) * dot(xs, up), down)
    return jnp.zeros((tokens, x.shape[1]), F32).at[token].add(y * w[:, None])


def held_experts(x, weights, ids, order, counts, gate, up, down, *, lo: int,
                 fast_rows: int, compute_dtype=None):
    """``sum_k weights[:, k] * expert_{ids[:, k]}(x)`` over the assignments
    whose expert is held here.  ``x``: ``(N, E)``; ``order``, ``counts``:
    ``plan(ids, lo, n)``; ``gate``, ``up``: ``(n, E, F)``; ``down``:
    ``(n, F, E)``; expert ``e`` of the model is ``gate[e - lo]``.  Returns
    ``(N, E)`` float32.

    When the batch routes more than ``fast_rows`` assignments to the held
    experts, the same grouped products run over chunks of tokens small
    enough that every assignment a chunk could make fits in ``fast_rows``
    rows: exact, ``tokens * min(top_k, n) / fast_rows`` times the work,
    in ``ragged_dot`` (``_grouped_path`` decides the fast path's products)."""
    cd = compute_dtype or F32
    tokens, top_k = ids.shape
    n = gate.shape[0]
    most = min(top_k, n)  # held assignments one token can make
    rows = min(max(fast_rows, most), tokens * most)
    kernel = not _grouped_path(rows, x.shape[1], gate.shape[2], cd)
    if rows == tokens * most:
        return _grouped(x, weights, ids, order, counts, gate, up, down,
                        rows, cd, kernel)
    chunk = max(c for c in range(1, rows // most + 1) if tokens % c == 0)

    def fast(_):
        return _grouped(x, weights, ids, order, counts, gate, up, down,
                        rows, cd, kernel)

    @jax.checkpoint
    def one_chunk(args):
        xc, wc, ic = args
        return _grouped(xc, wc, ic, *plan(ic, lo, n), gate, up, down,
                        chunk * most, cd)

    def chunked(_):
        split = lambda a: a.reshape(tokens // chunk, chunk, a.shape[1])  # noqa: E731
        out = jax.lax.map(one_chunk, (split(x), split(weights), split(ids)))
        return out.reshape(tokens, x.shape[1])

    return jax.lax.cond(jnp.sum(counts) <= rows, fast, chunked, None)
