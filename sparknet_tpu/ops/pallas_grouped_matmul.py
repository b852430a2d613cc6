"""The held experts' grouped matrix products as Pallas kernels that stop
where the groups end: what ``jax.lax.ragged_dot`` computes for
``ops/moe.held_experts``' fast path, without the rows past the held
assignments.

The fast path holds ``moe.ROWS_SLACK`` (2) times the expected held
assignments, so at the expected load half of its rows are past the groups'
sum.  ``ragged_dot`` multiplies every row it is given (the rows past the
total ride in the last group with weight 0); these kernels take the group
sizes as they are, ``sum(sizes) <= rows``, and do no work on a row past the
total: they write it as zero.

Three kernels, each ONE ``jax.jit`` at module scope whose only static input
is the configuration (``_Shape``: tiles, the transpose, the output's dtype):

- ``_forward``: ``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group
  ``g``, ``(rows, K) x (n, K, N) -> (rows, N)`` float32;
- ``_dlhs``: ``dout[r] @ rhs[g]^T``, the same kernel reading ``rhs`` as it
  lies and contracting its last axis, ``(rows, N) x (n, K, N) -> (rows, K)``;
- ``_drhs``: ``lhs[rows of g]^T @ dout[rows of g]`` for every group,
  ``(rows, K) x (rows, N) -> (n, K, N)``, an empty group's zero;

the two backward ones in the operands' dtype, which is what ``ragged_dot``'s
transpose rounds its float32 products to: no float32 ``(rows, K)`` or ``(n,
K, N)`` array is written.  ``grouped_matmul`` is their ``jax.custom_vjp``.

The walk (megablox's ``gmm`` / ``tgmm`` pattern).  Rows go in tiles of
``block_rows``.  The grid's last axis walks VISITS, (row tile, group) pairs
computed in XLA from the sizes (``_visits``) and handed in as scalar prefetch
with the groups' offsets: a tile that straddles a group boundary is visited
once for each group it holds, and each visit writes its own group's rows.
The number of visits is static, ``row tiles + groups - 1``, what the most
ragged sizes need; the visits the sizes do not need are padding, which
computes and writes nothing, and every block index of a visit that computes
nothing stays on the last computing visit's, so no block is fetched for it.
In ``_forward`` / ``_dlhs`` (tile-major) a row tile that starts at or past
the total gets one visit that writes its zeros: no product and no load of
``rhs``; rows past the total inside a straddling tile are zeroed by the
first visit of the tile.  In ``_drhs`` (group-major, a float32 accumulator a
block of ``(K, N)``) an empty group gets one visit that writes its zeros.

Tiles: ``block_rows`` the largest of 512, 256, ... that divides ``rows``
(the benchmark's 16,384-32,768 rows are whole 512s); in ``_forward`` /
``_dlhs`` the whole contraction and the output's width in the widest
whole-lane block up to 2,048 that divides it; in ``_drhs`` ``K`` and ``N``
in such blocks.  On the v5e at half of 24,576 / 16,384 rows held, by
projection (hidden 2,048 x experts 768 / 1,536) forward + ``dlhs`` +
``drhs``: 1.22 / 1.15 ms for 2,048 -> F and 1.35 / 1.18 for F -> 2,048,
where blocks of 1,024 took 1.33 / 1.36 and 1.43 / 1.40 and ``ragged_dot``
over all the rows 3.53 / 3.04 and 3.69 / 2.93 (PERF.md section 6, PR 39).
VMEM, double-buffered blocks + float32 temporaries, at most ~46 MiB
(``_drhs`` at 2,048 x 1,536), under the flash kernels' scoped-VMEM ceiling,
which these take too (``pallas_attention.VMEM_LIMIT_BYTES``: every program
that runs these runs those).

Built once per program.  A kernel is traced when its ``jax.jit`` misses its
cache, once per distinct operand shapes in a process: every layer, branch and
rematerialised copy that calls it again binds the same traced program, and
``jax`` lowers an equation it has lowered in the module before from its
cache, so the Mosaic kernel is lowered once a program.  A ``custom_vjp``
traces its primal under the caller's mesh context and its forward under the
equation's, which names the empty mesh where the caller named none; the calls
set the current mesh explicitly (``_bind``) so that both hit one entry.
``TRACES`` counts the kernels' bodies as they are traced, by (kernel, the
two operands' shapes).

Arithmetic: operands in the compute dtype, bfloat16, products accumulated in
float32 (``ragged_dot``'s ``preferred_element_type=float32``); the backward
reads the float32 cotangent as it lies and rounds each block to bfloat16 in
VMEM (a product of float32 and bfloat16 at default precision does the same
on the v5e), so no bfloat16 copy of it is written either.  A row's value is
one product of its own group, never a sum across groups.

Off the TPU the kernels run in interpreter mode (tests only:
``moe.held_experts`` takes ``ragged_dot`` there).
"""

from __future__ import annotations

import collections
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.ops.pallas_attention import (
    F32,
    LANES,
    _NN,
    _NT,
    _compiler_params,
    _mm,
    _out_struct,
    lowerable,
)

_TN = (((0,), (0,)), ((), ()))  # a.T @ b without materializing a.T

# kernel bodies traced, by (kernel, the two operands' shapes)
TRACES: collections.Counter = collections.Counter()

# the largest blocks: of rows; of the output's width in ``_forward`` /
# ``_dlhs``; of ``K`` and ``N`` in ``_drhs``
BLOCK_ROWS = 512
BLOCK_OUT = 2048
BLOCK_KN = 2048
LEAST_ROWS = 16  # a bfloat16 register tile

# what a visit does
COMPUTE, ZERO, PAD = 0, 1, 2

ACCEPTS = ("bfloat16 operands, both widths whole lanes, rows whole tiles of "
           f"{LEAST_ROWS}")


def accepts(rows: int, k: int, n: int, dtype) -> bool:
    """What the kernels are worth taking for (``ACCEPTS``): a bfloat16
    compute dtype, ``K`` and ``N`` of whole lanes, ``rows`` of whole bfloat16
    register tiles.  Anything else keeps ``ragged_dot``."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and k % LANES == 0 and n % LANES == 0
            and rows % LEAST_ROWS == 0)


class _Shape(NamedTuple):
    """What a kernel is specialised on besides its operands' shapes."""
    block_rows: int
    block_k: int  # of the contraction (``_forward`` / ``_dlhs``: all of it)
    block_n: int  # of the output's width
    transpose: bool  # ``_dlhs``: ``rhs`` contracted over its last axis
    out_dtype: np.dtype
    interpret: bool


def _lane_block(width: int, cap: int) -> int:
    """The widest block of whole lanes, up to ``cap``, that divides
    ``width``."""
    return max(b for b in range(LANES, min(cap, width) + 1, LANES)
               if width % b == 0)


def _row_block(rows: int) -> int:
    """The largest of ``BLOCK_ROWS``, half of it, ... that divides ``rows``."""
    block = BLOCK_ROWS
    while rows % block:
        block //= 2
    return block


# -- the walk -----------------------------------------------------------------
def _visits(sizes, rows: int, block_rows: int, by_group: bool):
    """The grid's visits for group sizes ``(n,)`` over ``rows`` rows in tiles
    of ``block_rows``: ``(offsets (n + 1,), tile, group, kind, load_tile,
    load_group)``, int32, each but the first ``(row tiles + n - 1,)``.
    Tile-major (``by_group`` False): every (tile, group) pair a group's rows
    meet, in order, then a ``ZERO`` visit for every tile past the total.
    Group-major: the same pairs group by group, an empty group's one
    ``ZERO`` visit among them.  ``PAD`` after that; a visit that computes
    nothing keeps the last computing visit's ``load_*``."""
    n = sizes.shape[0]
    tiles = rows // block_rows
    v = jnp.arange(tiles + n - 1, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // block_rows
    count = jnp.where(sizes > 0, (ends - 1) // block_rows - first + 1, 0)
    if by_group:
        count = jnp.maximum(count, 1)
    cum = jnp.cumsum(count)
    active = cum[-1]
    group = jnp.minimum(
        jnp.searchsorted(cum, v, side="right", method="compare_all"), n - 1)
    group = group.astype(jnp.int32)
    tile = first[group] + v - (cum - count)[group]
    kind = jnp.where(sizes[group] > 0, COMPUTE, ZERO)
    real = active
    if not by_group:  # then one visit for every tile past the total
        done = -(-ends[-1] // block_rows)
        tile = jnp.where(v < active, tile, done + v - active)
        kind = jnp.where(v < active, kind, ZERO)
        real = active + tiles - done
    tile = jnp.minimum(tile, tiles - 1)
    kind = jnp.where(v < real, kind, PAD)
    tile = jnp.where(v < real, tile, tile[real - 1])
    group = jnp.where(v < real, group, group[real - 1])
    load = jnp.maximum(jax.lax.cummax(jnp.where(kind == COMPUTE, v, -1)), 0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, tile, group, kind.astype(jnp.int32), tile[load],
            group[load])


def _held(offsets_ref, group_ref, tile_ref, v, c: _Shape, shape):
    """The rows of the visit's tile that its group holds, ``shape`` with the
    rows first, and whether the group holds the whole tile."""
    g = group_ref[v]
    lo, hi = offsets_ref[g], offsets_ref[g + 1]
    top = tile_ref[v] * c.block_rows
    row = top + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi), (lo <= top) & (top + c.block_rows <= hi)


# -- the kernels --------------------------------------------------------------
def _gmm_kernel(offsets_ref, tile_ref, group_ref, kind_ref, load_tile_ref,
                load_group_ref, lhs_ref, rhs_ref, out_ref, *, c: _Shape, key):
    del load_tile_ref, load_group_ref  # the index maps' alone
    TRACES[key] += 1
    v = pl.program_id(1)
    kind = kind_ref[v]

    @pl.when(kind == COMPUTE)
    def _():
        prod = _mm(lhs_ref[...].astype(rhs_ref.dtype), rhs_ref[...],
                   _NT if c.transpose else _NN)
        held, whole = _held(offsets_ref, group_ref, tile_ref, v, c,
                            out_ref.shape)

        @pl.when(whole)
        def _():
            out_ref[...] = prod.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():  # a tile's first visit zeroes the rows no group holds
            fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile_ref[v])
            kept = jnp.where(fresh, 0.0, out_ref[...].astype(F32))
            out_ref[...] = jnp.where(held, prod, kept).astype(out_ref.dtype)

    @pl.when(kind == ZERO)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _tgmm_kernel(offsets_ref, tile_ref, group_ref, kind_ref, load_tile_ref,
                 load_group_ref, lhs_ref, dout_ref, out_ref, acc_ref, *,
                 c: _Shape, key):
    del load_tile_ref, load_group_ref
    TRACES[key] += 1
    v, nv = pl.program_id(2), pl.num_programs(2)
    kind, g = kind_ref[v], group_ref[v]

    @pl.when(kind == COMPUTE)
    def _():
        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        held, whole = _held(offsets_ref, group_ref, tile_ref, v, c,
                            lhs_ref.shape)

        dout = dout_ref[...].astype(lhs_ref.dtype)

        @pl.when(whole)
        def _():
            acc_ref[...] += _mm(lhs_ref[...], dout, _TN)

        @pl.when(jnp.logical_not(whole))
        def _():
            x = lhs_ref[...]
            x = jnp.where(held, x.astype(F32), 0.0).astype(x.dtype)
            acc_ref[...] += _mm(x, dout, _TN)

        after = jnp.minimum(v + 1, nv - 1)

        @pl.when((v == nv - 1) | (group_ref[after] != g)
                 | (kind_ref[after] == PAD))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @pl.when(kind == ZERO)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _gmm(name, c: _Shape, lhs, rhs, sizes):
    rows, k = lhs.shape
    n = rhs.shape[1] if c.transpose else rhs.shape[2]
    meta = _visits(sizes, rows, c.block_rows, by_group=False)
    rhs_block = (None, c.block_n, k) if c.transpose else (None, k, c.block_n)
    return pl.pallas_call(
        partial(_gmm_kernel, c=c, key=(name, lhs.shape, rhs.shape)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(n // c.block_n, meta[1].shape[0]),
            in_specs=[
                pl.BlockSpec((c.block_rows, k),
                             lambda j, v, o, t, g, kd, lt, lg: (lt[v], 0)),
                pl.BlockSpec(rhs_block, (
                    (lambda j, v, o, t, g, kd, lt, lg: (lg[v], j, 0))
                    if c.transpose else
                    (lambda j, v, o, t, g, kd, lt, lg: (lg[v], 0, j)))),
            ],
            out_specs=pl.BlockSpec(
                (c.block_rows, c.block_n),
                lambda j, v, o, t, g, kd, lt, lg: (t[v], j)),
        ),
        out_shape=_out_struct((rows, n), c.out_dtype, lhs, rhs, sizes),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=c.interpret,
        name=name,
    )(*meta, lhs, rhs)


def _tgmm(name, c: _Shape, lhs, dout, sizes):
    rows, k = lhs.shape
    n = dout.shape[1]
    groups = sizes.shape[0]
    meta = _visits(sizes, rows, c.block_rows, by_group=True)
    return pl.pallas_call(
        partial(_tgmm_kernel, c=c, key=(name, lhs.shape, dout.shape)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(k // c.block_k, n // c.block_n, meta[1].shape[0]),
            in_specs=[
                pl.BlockSpec((c.block_rows, c.block_k),
                             lambda i, j, v, o, t, g, kd, lt, lg: (lt[v], i)),
                pl.BlockSpec((c.block_rows, c.block_n),
                             lambda i, j, v, o, t, g, kd, lt, lg: (lt[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, c.block_k, c.block_n),
                lambda i, j, v, o, t, g, kd, lt, lg: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((c.block_k, c.block_n), F32)],
        ),
        out_shape=_out_struct((groups, k, n), c.out_dtype, lhs, dout, sizes),
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=c.interpret,
        name=name,
    )(*meta, lhs, dout)


@partial(jax.jit, static_argnums=(0,))
def _forward(c: _Shape, lhs, rhs, sizes):
    return _gmm("grouped_matmul", c, lhs, rhs, sizes)


@partial(jax.jit, static_argnums=(0,))
def _dlhs(c: _Shape, dout, rhs, sizes):
    return _gmm("grouped_matmul_dlhs", c, dout, rhs, sizes)


@partial(jax.jit, static_argnums=(0,))
def _drhs(c: _Shape, lhs, dout, sizes):
    return _tgmm("grouped_matmul_drhs", c, lhs, dout, sizes)


def _bind(kernel, c: _Shape, *operands):
    """``kernel`` called under the current mesh named explicitly: see the
    module docstring."""
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return kernel(c, *operands)


def _gmm_shape(rows, width, out_dtype, transpose, interpret) -> _Shape:
    return _Shape(_row_block(rows), 0, _lane_block(width, BLOCK_OUT),
                  transpose, np.dtype(out_dtype), interpret)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, sizes, interpret):
    return _grouped_fwd(lhs, rhs, sizes, interpret)[0]


def _grouped_fwd(lhs, rhs, sizes, interpret):
    c = _gmm_shape(lhs.shape[0], rhs.shape[2], F32, False, interpret)
    return _bind(_forward, c, lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(interpret, res, dout):
    lhs, rhs, sizes = res
    rows, k = lhs.shape
    dlhs = _bind(_dlhs, _gmm_shape(rows, k, lhs.dtype, True, interpret),
                 dout, rhs, sizes)
    c = _Shape(_row_block(rows), _lane_block(k, BLOCK_KN),
               _lane_block(rhs.shape[2], BLOCK_KN),
               False, np.dtype(rhs.dtype), interpret)
    return dlhs, _bind(_drhs, c, lhs, dout, sizes), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, *, interpret=None):
    """``lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``, groups in
    order from row 0: ``lhs`` ``(rows, K)`` and ``rhs`` ``(n, K, N)`` in one
    dtype, ``sizes`` ``(n,)`` with ``sum(sizes) <= rows``.  Returns ``(rows,
    N)`` float32, zero past ``sum(sizes)``, with the kernels' own backward:
    cotangents in the operands' dtype, zero past the total.  ``accepts``
    says which shapes the kernels take."""
    if interpret is None:
        interpret = not lowerable()
    return _grouped(lhs, rhs, sizes.astype(jnp.int32), bool(interpret))
