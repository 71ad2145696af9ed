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

The gated pair of a SwiGLU expert (``grouped_swiglu``) keeps those names:
its forward takes both products of an ``x`` tile and writes ``silu(x wg)
* (x wi)`` beside the two products (the backward's residuals), and its
``moe_gmm_dx`` forms both products' cotangents from ``dh`` and those
residuals and sums ``dgate wg^T + dup wi^T`` in one float32 accumulator,
so nothing between the products walks the worst-case buffer.  Each takes
the widest block of both banks whose VMEM reckoning fits (``_gated_tile``:
whole at every cell's widths), so a row tile is read, and its activation
or cotangents formed, once.

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
    "grouped_matmul_reference", "grouped_swiglu",
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
# What the gated pair may hold in VMEM (``_gated_vmem``): both banks'
# blocks, the row tiles in and out, each twice (the pipeline's buffers),
# and the float32 temporaries of a row tile.  LFM2's dx takes both
# 2048 x 1792 blocks whole at about 54 MB, past Mosaic's 16 MB default
# (a v5e core has 128 MiB).
_GATED_VMEM_LIMIT = 64 * 1024 * 1024


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
                                 products=1, **_col_metadata(n, tn)),
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
            products=1, **_col_metadata(k, tk), **_col_metadata(n, tn),
        ),
    )(tile_group, tile_src, n_active, x, dy)


def _gated_vmem(block: int, k: int, tile: int, itemsize: int, *, dx: bool) -> int:
    """Bytes of VMEM the gated pair holds at a block of ``block`` of the
    blocked width against the whole other width ``k``: both banks'
    blocks and the row tiles, double-buffered, and the float32
    temporaries of a row tile.  Forward: ``x`` (tile, k) in; ``h``,
    ``gate``, ``up`` (tile, block) out and in float32.  dx: ``dh``,
    ``gate``, ``up`` (tile, k) in, ``dgate``, ``dup`` (tile, k) and ``dx``
    (tile, block) out; in float32 the three inputs, the sigmoid, both
    cotangents and the (tile, block) accumulator."""
    if dx:
        rows, f32 = 5 * tile * k + tile * block, 6 * tile * k + tile * block
    else:
        rows, f32 = tile * k + 3 * tile * block, 3 * tile * block
    return 2 * (2 * k * block + rows) * itemsize + 4 * f32


def _gated_tile(n: int, k: int, tile: int, itemsize: int, *, dx: bool) -> int:
    """The gated pair's block of a width ``n`` against the whole other
    width ``k``: the widest of ``n`` itself and the multiples of 128 lanes
    that divide it whose ``_gated_vmem`` fits ``_GATED_VMEM_LIMIT``; where
    none fits, the narrowest, and the compiler judges."""
    blocks = [b for b in range(n, 0, -128) if n % b == 0]
    return next((b for b in blocks
                 if _gated_vmem(b, k, tile, itemsize, dx=dx) <= _GATED_VMEM_LIMIT),
                blocks[-1])


def _gated_fwd_kernel(tg_ref, ts_ref, na_ref, x_ref, wg_ref, wi_ref,
                      h_ref, g_ref, u_ref):
    del tg_ref, ts_ref

    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        g_ref[...] = gate.astype(g_ref.dtype)
        u_ref[...] = up.astype(u_ref.dtype)
        h_ref[...] = (gate * jax.nn.sigmoid(gate) * up).astype(h_ref.dtype)


def _gated_dx_kernel(tg_ref, ts_ref, na_ref, dh_ref, g_ref, u_ref, wg_ref,
                     wi_ref, dx_ref, dg_ref, du_ref):
    del tg_ref, ts_ref

    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        dh = dh_ref[...].astype(jnp.float32)
        gate = g_ref[...].astype(jnp.float32)
        up = u_ref[...].astype(jnp.float32)
        sig = jax.nn.sigmoid(gate)
        # both cotangents leave in the compute type before the MXU: a
        # float32 operand would be three products (PERF.md section 6, PR 34)
        dgate = (dh * up * sig * (1.0 + gate * (1.0 - sig))).astype(dg_ref.dtype)
        dup = (dh * gate * sig).astype(du_ref.dtype)
        dg_ref[...] = dgate
        du_ref[...] = dup
        dims = (((1,), (1,)), ((), ()))
        acc = jax.lax.dot_general(dgate, wg_ref[0], dims,
                                  preferred_element_type=jnp.float32)
        acc += jax.lax.dot_general(dup, wi_ref[0], dims,
                                   preferred_element_type=jnp.float32)
        dx_ref[...] = acc.astype(dx_ref.dtype)


def _gated_gmm(x, wg, wi, tile_group, tile_src, n_active, *, tile, interpret):
    """``h = silu(x @ wg[g]) * (x @ wi[g])`` and the two products (the
    backward's residuals), each (R, F) in ``x``'s type, for the group of
    each active row tile; an ``x`` tile is read once for both."""
    rows, k = x.shape
    n = wg.shape[2]
    tn = _gated_tile(n, k, tile, x.dtype.itemsize, dx=False)
    num_tiles = rows // tile
    w_spec = pl.BlockSpec((1, k, tn), lambda j, i, tg, ts, na: (tg[i], 0, j))
    o_spec = pl.BlockSpec((tile, tn), lambda j, i, tg, ts, na: (ts[i], j))
    out = jax.ShapeDtypeStruct((rows, n), x.dtype)
    return pl.pallas_call(
        _gated_fwd_kernel,
        out_shape=(out, out, out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, num_tiles),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, tg, ts, na: (ts[i], 0)),
                w_spec, w_spec,
            ],
            out_specs=[o_spec, o_spec, o_spec],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GATED_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_gmm_fwd",
        metadata=_tiles_metadata(num_tiles * (n // tn), wg.shape[0] * (n // tn),
                                 products=2, **_col_metadata(n, tn)),
    )(tile_group, tile_src, n_active, x, wg, wi)


def _gated_gmm_dx(dh, gate, up, wg, wi, tile_group, tile_src, n_active, *,
                  tile, interpret):
    """``dx = dgate @ wg[g]^T + dup @ wi[g]^T`` (R, K) from ``dh`` and the
    forward's two products, and ``dgate``, ``dup`` (R, F) for the weight
    gradients, all in ``dh``'s type; inactive row tiles stay unwritten.
    At every cell's widths K is one block, so a row tile's cotangents are
    formed and written once; where ``_gated_tile`` cuts K narrower, each
    block recomputes them and writes the same ``dgate`` and ``dup``."""
    rows, n = dh.shape
    k = wg.shape[1]
    tk = _gated_tile(k, n, tile, dh.dtype.itemsize, dx=True)
    num_tiles = rows // tile
    w_spec = pl.BlockSpec((1, tk, n), lambda j, i, tg, ts, na: (tg[i], j, 0))
    row_spec = pl.BlockSpec((tile, n), lambda j, i, tg, ts, na: (ts[i], 0))
    cot = jax.ShapeDtypeStruct((rows, n), dh.dtype)
    return pl.pallas_call(
        _gated_dx_kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, k), dh.dtype), cot, cot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, num_tiles),
            in_specs=[row_spec, row_spec, row_spec, w_spec, w_spec],
            out_specs=[
                pl.BlockSpec((tile, tk), lambda j, i, tg, ts, na: (ts[i], j)),
                row_spec, row_spec,
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GATED_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_gmm_dx",
        metadata=_tiles_metadata(num_tiles * (k // tk), wg.shape[0] * (k // tk),
                                 products=2, **_col_metadata(k, tk)),
    )(tile_group, tile_src, n_active, dh, gate, up, wg, wi)


def _tiles_metadata(total: int, floor: int, **more: int) -> dict:
    """What ``obs/scope.kernel_tiles`` sums out of the compiled step: the
    grid steps the buffer's worst case gives the call and the fewest any
    routing leaves it (one row tile a group).  The steps really computed
    are the routing's and are not known to the program's text."""
    return {"tiles_total": str(total), "tiles_floor": str(floor),
            **{f"tiles_{k}": str(v) for k, v in more.items()}}


def _col_metadata(n: int, block: int) -> dict:
    """The column block chosen for a width over 512 that the 128-lane rule
    cut or the gated pair's reckoning chose (``col<width>``, which
    ``kernel_tiles`` keeps and does not sum: a width has one block in
    every call); a width whose block is the constant's says nothing new
    and keeps the text it had."""
    return {f"col{n}": block} if n > _COL_TILE and block != _COL_TILE else {}


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _swiglu(x, wg, wi, tile_group, tile_src, n_active, tile, interpret):
    return _swiglu_fwd(x, wg, wi, tile_group, tile_src, n_active, tile,
                       interpret)[0]


def _swiglu_fwd(x, wg, wi, tile_group, tile_src, n_active, tile, interpret):
    h, gate, up = _gated_gmm(
        x, wg.astype(x.dtype), wi.astype(x.dtype), tile_group, tile_src,
        n_active, tile=tile, interpret=interpret,
    )
    return h, (x, wg, wi, gate, up, tile_group, tile_src, n_active)


def _swiglu_bwd(tile, interpret, res, dh):
    x, wg, wi, gate, up, tile_group, tile_src, n_active = res
    dx, dgate, dup = _gated_gmm_dx(
        dh.astype(x.dtype), gate, up, wg.astype(x.dtype), wi.astype(x.dtype),
        tile_group, tile_src, n_active, tile=tile, interpret=interpret,
    )
    dwg, dwi = (
        _tgmm(x, d, tile_group, tile_src, n_active, groups=wg.shape[0],
              tile=tile, interpret=interpret)
        for d in (dgate, dup)
    )
    return dx, dwg.astype(wg.dtype), dwi.astype(wi.dtype), None, None, None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def grouped_swiglu(x, wg, wi, tile_group, tile_src, n_active, *,
                   tile: int = ROW_TILE, interpret: bool | None = None):
    """``h[r] = silu(x[r] @ wg[g]) * (x[r] @ wi[g])``, ``g`` the group of
    r's tile, for the rows of the first ``n_active`` tiles; the other rows
    are left unwritten.  One kernel takes both products of a row tile and
    the activation in float32 (``moe_gmm_fwd``), one the cotangent of
    ``x`` in one float32 accumulator (``moe_gmm_dx``); the weights'
    gradients are ``grouped_matmul``'s (``moe_gmm_dw``).  Arguments and
    types as ``grouped_matmul``'s, ``wg`` and ``wi`` (G, K, F) alike."""
    if x.shape[0] % tile:
        raise ValueError(f"{x.shape[0]} rows do not divide into tiles of {tile}")
    if interpret is None:
        interpret = interpret_default()
    return _swiglu(x, wg, wi, tile_group, tile_src, n_active, tile, interpret)


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
