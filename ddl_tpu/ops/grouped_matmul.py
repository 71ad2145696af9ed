"""Grouped matrix product over rows sorted by group (Pallas, TPU).

A dropless expert layer sorts its token-choices by expert and multiplies
each expert's run of rows by that expert's matrix.  The runs are ragged
and known only at run time, so no dense einsum computes this without
padding every expert to the worst case.  Here the caller lays the rows
out so that **every run starts on a row-tile boundary and has at least
one tile** (``align_groups``): a row tile then belongs to exactly one
group, the kernels need no masks, and a group with no rows still gets
its (zero) weight gradient written.  The buffer is sized for the worst
case; the tiles really filled are the first ``n_active`` and the kernels
do work only for those:

    out[r] = x[r] @ w[group of r's tile]            ``moe_gmm_fwd``
    dx[r]  = dy[r] @ w[group of r's tile]^T         ``moe_gmm_dx``
    dw[g]  = x[rows of g]^T @ dy[rows of g]         ``moe_gmm_dw``

A grid step past ``n_active`` maps every block to the last active
tile's (no new DMA) and computes nothing, so time follows the rows
really routed (plus a third of a microsecond a skipped step).  Rows of
such tiles are **never written**, and what reads the result never reads
them: the row kernels of ``ops/moe_rows`` (``moe_rows_combine`` the
forward's result, ``moe_rows_gather_bwd`` ``dx``) walk only the pairs of
row tiles that hold a routed row, as these kernels' own backward walks
only active tiles of ``x`` and ``dy``.  Rows that pad a group inside an
active tile must be zero in ``x`` (forward) and in ``dy`` (backward):
``moe_rows_gather`` and ``moe_rows_combine_bwd`` write them so; then
they add nothing to ``dw`` and come out as zeros.

The design follows ``jax.experimental.pallas.ops.tpu.megablox`` in using
scalar-prefetched group metadata to index the weight bank; aligning the
groups is what lets it drop that kernel's masks and revisited tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = [
    "ROW_TILE", "align_groups", "buffer_rows", "grouped_matmul",
    "grouped_matmul_reference",
]

# Rows a tile; also the alignment of a group's run.  256 rows against a
# (2048, 512) weight block is 5.4 us of MXU work a step at the v5e's
# peak, so the 0.35 us a grid step costs stays near 6%; half of a tile
# a group is padding on average (ISSUE 27's cell: 512 rows an expert,
# so a quarter more rows than routed).
ROW_TILE = 256
# Column block of a weight matrix (and of dw's two dims) for a width that
# divides by it.
_COL_TILE = 512
# For a width over 512 that 512 does not divide (896 = 7 x 128, 2304 = 18 x
# 128): the widest multiple of 128 lanes that divides it, up to this many.
_COL_TILE_128 = 1152


def buffer_rows(choices: int, groups: int, tile: int = ROW_TILE) -> int:
    """Rows the sorted buffer needs so that no routing can overflow it:
    every choice, each group's run rounded up to a tile, and one tile
    for a group that got nothing."""
    return (-(-choices // tile) + groups) * tile


def align_groups(counts, num_tiles: int, tile: int = ROW_TILE):
    """Where each group's run lies once runs start on tile boundaries.

    ``counts`` (G,) int32 rows a group.  Returns ``(start, tile_group,
    tile_src, n_active)``: ``start`` (G,) the first row of each run;
    ``tile_group`` (num_tiles,) the group of each row tile, ``tile_src``
    (num_tiles,) the tile whose blocks a grid step maps (itself when
    active, the last active one beyond), ``n_active`` (1,) tiles in
    use."""
    counts = counts.astype(jnp.int32)
    aligned = jnp.maximum(tile, -(-counts // tile) * tile)
    end = jnp.cumsum(aligned)
    n_active = end[-1] // tile
    t = jnp.arange(num_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(end // tile, t, side="right").astype(jnp.int32),
        counts.shape[0] - 1,
    )
    tile_src = jnp.minimum(t, n_active - 1)
    return end - aligned, tile_group, tile_src, n_active.reshape(1)


def _col_tile(n: int) -> int:
    if n <= _COL_TILE:
        return n
    if n % _COL_TILE == 0:
        return _COL_TILE
    if n % 128:
        raise ValueError(
            f"a grouped product's width {n} must be a multiple of 128 lanes "
            f"(a width over {_COL_TILE} goes through in column blocks)"
        )
    return max(t for t in range(128, _COL_TILE_128 + 1, 128) if n % t == 0)


def _gmm_kernel(tg_ref, ts_ref, na_ref, x_ref, w_ref, o_ref, *, transpose_w):
    del tg_ref, ts_ref

    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        dims = (((1,), (1 if transpose_w else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def _gmm(x, w, tile_group, tile_src, n_active, *, tile, transpose_w,
         interpret, name):
    """``x`` (R, K) times ``w[g]`` ((K, N), or (N, K) transposed) for the
    group of each row tile -> (R, N) in ``x``'s type."""
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tn = _col_tile(n)
    num_tiles = rows // tile
    if transpose_w:
        w_spec = pl.BlockSpec((1, tn, k), lambda j, i, tg, ts, na: (tg[i], j, 0))
    else:
        w_spec = pl.BlockSpec((1, k, tn), lambda j, i, tg, ts, na: (tg[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # row tiles innermost: a group's weight block is fetched once
            # a column block, its rows stream past it
            grid=(n // tn, num_tiles),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, tg, ts, na: (ts[i], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tile, tn), lambda j, i, tg, ts, na: (ts[i], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
        metadata=_tiles_metadata(num_tiles * (n // tn), w.shape[0] * (n // tn),
                                 **_col_metadata(n, tn)),
    )(tile_group, tile_src, n_active, x, w)


def _tgmm_kernel(tg_ref, ts_ref, na_ref, x_ref, dy_ref, o_ref):
    del ts_ref
    i = pl.program_id(2)
    active = i < na_ref[0]
    first = (i == 0) | (tg_ref[i] != tg_ref[jnp.maximum(i - 1, 0)])

    @pl.when(active & first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(active)
    def _():
        o_ref[0] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _tgmm(x, dy, tile_group, tile_src, n_active, *, groups, tile, interpret):
    """``dw[g] = x[rows of g]^T @ dy[rows of g]`` -> (G, K, N) float32.
    Every group has a tile, so every block of the result is written."""
    rows, k = x.shape
    n = dy.shape[1]
    tk, tn = _col_tile(k), _col_tile(n)
    num_tiles = rows // tile
    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, n // tn, num_tiles),
            in_specs=[
                pl.BlockSpec((tile, tk), lambda a, b, i, tg, ts, na: (ts[i], a)),
                pl.BlockSpec((tile, tn), lambda a, b, i, tg, ts, na: (ts[i], b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, tg, ts, na: (tg[i], a, b)
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="moe_gmm_dw",
        metadata=_tiles_metadata(
            num_tiles * (k // tk) * (n // tn), groups * (k // tk) * (n // tn),
            **_col_metadata(k, tk), **_col_metadata(n, tn),
        ),
    )(tile_group, tile_src, n_active, x, dy)


def _tiles_metadata(total: int, floor: int, **more: int) -> dict:
    """What ``obs/scope.kernel_tiles`` sums out of the compiled step: the
    grid steps the buffer's worst case gives the call and the fewest any
    routing leaves it (one row tile a group).  The steps really computed
    are the routing's and are not known to the program's text."""
    return {"tiles_total": str(total), "tiles_floor": str(floor),
            **{f"tiles_{k}": str(v) for k, v in more.items()}}


def _col_metadata(n: int, block: int) -> dict:
    """The column block chosen for a width the 128-lane rule cut
    (``col<width>``, which ``kernel_tiles`` keeps and does not sum: a
    width has one block in every call); a width whose block is the
    constant's says nothing new and keeps the text it had."""
    return {f"col{n}": block} if n > _COL_TILE and n % _COL_TILE else {}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, w, tile_group, tile_src, n_active, tile, interpret):
    return _forward(x, w, tile_group, tile_src, n_active, tile, interpret)


def _forward(x, w, tile_group, tile_src, n_active, tile, interpret):
    return _gmm(x, w.astype(x.dtype), tile_group, tile_src, n_active, tile=tile,
                transpose_w=False, interpret=interpret, name="moe_gmm_fwd")


def _grouped_fwd(x, w, tile_group, tile_src, n_active, tile, interpret):
    out = _forward(x, w, tile_group, tile_src, n_active, tile, interpret)
    return out, (x, w, tile_group, tile_src, n_active)


def _grouped_bwd(tile, interpret, res, dy):
    x, w, tile_group, tile_src, n_active = res
    dy = dy.astype(x.dtype)
    dx = _gmm(dy, w.astype(x.dtype), tile_group, tile_src, n_active, tile=tile,
              transpose_w=True, interpret=interpret, name="moe_gmm_dx")
    dw = _tgmm(x, dy, tile_group, tile_src, n_active, groups=w.shape[0],
               tile=tile, interpret=interpret)
    return dx, dw.astype(w.dtype), None, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, tile_group, tile_src, n_active, *,
                   tile: int = ROW_TILE, interpret: bool | None = None):
    """``out[r] = x[r] @ w[tile_group[r // tile]]`` for the rows of the
    first ``n_active`` tiles; the other rows are left unwritten.

    ``x`` (R, K) in the compute type, R a multiple of ``tile``; ``w``
    (G, K, N), the master weights in their own type: they are cast to
    ``x``'s type for the products and their gradient comes back in
    theirs, accumulated in float32 (no round trip through the compute
    type).  The three index arrays are ``align_groups``'s."""
    if x.shape[0] % tile:
        raise ValueError(f"{x.shape[0]} rows do not divide into tiles of {tile}")
    if interpret is None:
        interpret = interpret_default()
    return _grouped(x, w, tile_group, tile_src, n_active, tile, interpret)


def grouped_matmul_reference(x, w, tile_group, n_active, *, tile: int = ROW_TILE):
    """The same product as a loop over groups in ``jax.numpy`` (float32,
    highest precision); rows of tiles past ``n_active`` read 0."""
    rows = x.shape[0]
    row_tile = jnp.arange(rows) // tile
    group = jnp.where(row_tile < n_active[0], tile_group[row_tile], -1)
    out = jnp.zeros((rows, w.shape[2]), jnp.float32)
    for g in range(w.shape[0]):
        y = jnp.dot(x.astype(jnp.float32), w[g].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        out = jnp.where((group == g)[:, None], y, out)
    return out
