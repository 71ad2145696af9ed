"""The dropless shuffle's gathers, bounded by the rows really routed
(Pallas, TPU).

A dropless expert layer moves rows twice: each token-choice's input into
the buffer sorted by expert (``ops/grouped_matmul``), and each choice's
output back to its token.  The buffer is sized for the worst routing, and
a gather written in ``jax.numpy`` walks all of it whatever was routed.
These kernels take their bound from the plan's own counts instead, the
way the grouped products take ``n_active``.

Mosaic refuses a copy of one row of a tiled matrix (a slice's second-minor
extent must be a multiple of 8), so a row is not fetched: it is **selected
by the MXU**.  A row tile of the buffer belongs to one expert and holds its
choices in token order, so the tokens it draws on lie in a few *token
tiles*; ``pair_plan`` lists every (row tile, token tile) pair that shares a
row, and a kernel multiplies, pair by pair, a 0/1 selection matrix built
from the row tile's token numbers with the other side's block:

    row side     out[rows of i] += sel(i, j)^T @ src[tokens of j]
    token side   out[tokens of j] += sel(i, j) @ src[rows of i]

A 0/1 selection of bf16 rows is exact: one MXU product a pair.  Float32
never rides through the selection: a float32 operand is split into three
bf16 addends (three products a pair, the rows exact again), and a float32
weight is applied beside the selection, on the vector unit (the routing
weight a token after the token side's product, the row's scale at the row
side's close).  What is summed is the float32 product and the sums are
float32 (the same mathematics as a gather and a sum over the choices).
Pairs number at most ``groups * (token tiles - 1) + row tiles`` and, in
use, about one a held expert a token tile plus one a filled row tile: the
grid is sized for the most, a step past the pairs in use maps every block
to the last pair's (no new DMA) and computes nothing.  Rows of row tiles
past ``n_active`` are in no pair: never read, and never written (what the
grouped products leave unwritten stays so); rows that pad a group inside
an active tile select nothing and are written as zeros.

    ``moe_rows_gather``        xs[r] = x[token of r]
    ``moe_rows_gather_bwd``    dx[t] = sum over t's held choices of g[row]
    ``moe_rows_combine``       y[t]  = sum of w[t, k] * o[row of (t, k)]
                               (+ the shared experts' row, where there is
                               one), leaving in the compute type
    ``moe_rows_combine_bwd``   do[r] = g[token of r] * w[r], and
                               <o[r], g[token of r]> a row (the weights'
                               gradient, gathered by the caller)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.grouped_matmul import _tiles_metadata
from ddl_tpu.ops.interpret import interpret_default

__all__ = ["pair_plan", "pairs_bound", "rows_combine", "rows_gather", "token_tile"]

# A pair holds two (256, D) blocks twice (the pipeline's buffers), a float32
# sum and, for float32 operands, their bf16 addends: 20 MB at D = 2048,
# past Mosaic's 16 MB default (a v5e core has 128 MiB).
_VMEM_LIMIT = 64 * 1024 * 1024


def token_tile(tokens: int, tile: int) -> int:
    """Tokens a token tile: a row tile's worth, or all of them where that
    does not divide them."""
    return tile if tokens % tile == 0 else tokens


def pairs_bound(row_tiles: int, token_tiles: int, groups: int) -> int:
    """The most (row tile, token tile) pairs any routing gives: along a
    group's run the token tiles only advance, so its row tiles' spans
    overlap at their ends only."""
    return groups * (token_tiles - 1) + row_tiles


def pair_plan(row_token, row_valid, n_active, *, tokens: int, groups: int,
              tile: int) -> dict:
    """The pairs of a sorted buffer (``models/transformer.dropless_plan``).

    ``row_token`` (R,) the token whose choice a buffer row holds,
    ``row_valid`` (R,) whether it holds one (a prefix of each row tile,
    tokens ascending along a group's run), ``n_active`` (1,) row tiles in
    use.  Returns ``tok`` (row tiles, 1, tile) the token of each row, -1
    where none; ``by_row`` = (row tile, token tile, pairs in use) sorted by
    row tile, every active row tile in at least one pair (so that its
    block is written); ``by_token`` the same pairs sorted by token tile,
    each token tile's run opened by one entry of its own (the step that
    zeroes its sum, which maps the next entry's row tile).  Entries past
    those in use repeat the last."""
    rows = row_token.shape[0]
    row_tiles = rows // tile
    tt = token_tile(tokens, tile)
    token_tiles = tokens // tt
    max_pairs = pairs_bound(row_tiles, token_tiles, groups)
    tok = jnp.where(row_valid, row_token, -1).astype(jnp.int32).reshape(row_tiles, tile)
    n_valid = row_valid.reshape(row_tiles, tile).sum(1, dtype=jnp.int32)
    some = n_valid > 0
    first = jnp.where(some, tok[:, 0] // tt, 0)
    last = jnp.take_along_axis(tok, jnp.maximum(n_valid - 1, 0)[:, None], 1)[:, 0] // tt
    active = jnp.arange(row_tiles, dtype=jnp.int32) < n_active[0]
    span = jnp.where(some, last - first + 1, active.astype(jnp.int32))
    end = jnp.cumsum(span)
    n_pairs = end[-1]
    p = jnp.minimum(jnp.arange(max_pairs, dtype=jnp.int32), n_pairs - 1)
    pi = jnp.minimum(
        jnp.searchsorted(end, p, side="right").astype(jnp.int32), row_tiles - 1
    )
    pj = first[pi] + p - (end[pi] - span[pi])

    # by token tile: sort (token tile, row tile + 1); key (j, 0) opens j's run
    stride = row_tiles + 1
    in_use = jnp.arange(max_pairs, dtype=jnp.int32) < n_pairs
    keys = jnp.sort(jnp.concatenate([
        jnp.arange(token_tiles, dtype=jnp.int32) * stride,
        jnp.where(in_use, pj * stride + pi + 1, token_tiles * stride),
    ]))
    n_entries = n_pairs + token_tiles
    keys = keys[jnp.minimum(jnp.arange(keys.shape[0], dtype=jnp.int32), n_entries - 1)]
    qj, qi = keys // stride, keys % stride - 1
    qi = jnp.where(qi < 0, jnp.maximum(jnp.roll(qi, -1), 0), qi)
    return {
        "tok": tok.reshape(row_tiles, 1, tile),
        "by_row": (pi, pj, n_pairs.reshape(1)),
        "by_token": (qi, qj, n_entries.reshape(1)),
    }


def _select(tok_ref, j, tt: int):
    """(tt, tile) bool: is token ``j * tt + t`` the one buffer row ``r``
    holds.  A row with no choice (-1) and a token of another tile match
    nothing."""
    local = tok_ref[0] - j * tt
    return local == jax.lax.broadcasted_iota(jnp.int32, (tt, local.shape[1]), 0)


def _passes(dtype) -> int:
    """MXU products a pair for a selected operand of this type: one for
    bf16, three for a float32's addends (``tiles_passes`` in a call's
    metadata)."""
    return 1 if dtype == jnp.bfloat16 else 3


def _addends(x):
    """bf16 arrays that sum to ``x``: itself, or a float32's three (8 + 8
    + 8 bits of significand; each remainder is exact in float32, and so is
    the sum of the three selected, taken in this order)."""
    # the operand's type is the layer's compute type: one program a model
    if _passes(x.dtype) == 1:  # ddl-lint: disable=recompile-shape-branch
        return [x]
    x = x.astype(jnp.float32)
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


def _run_ends(idx_ref, n_ref, step):
    """Is ``step`` the first / the last of the run of equal ``idx``."""
    here = idx_ref[step]
    first = (step == 0) | (here != idx_ref[jnp.maximum(step - 1, 0)])
    last = (step == n_ref[0] - 1) | (
        here != idx_ref[jnp.minimum(step + 1, idx_ref.shape[0] - 1)]
    )
    return first, last


def _gather_kernel(pi_ref, pj_ref, n_ref, tok_ref, *refs, tt, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    src_ref = refs.pop(0)
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    (acc,) = refs
    p = pl.program_id(0)

    @pl.when(p < n_ref[0])
    def _():
        first, last = _run_ends(pi_ref, n_ref, p)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        sel = jnp.where(_select(tok_ref, pj_ref[p], tt), 1.0, 0.0).astype(jnp.bfloat16)
        for part in _addends(src_ref[...]):
            acc[...] += jax.lax.dot_general(
                sel, part, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(last)
        def _():
            rows = acc[...]
            if scaled or dotted:
                tile = rows.shape[0]
                diag = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                        == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
            if dotted:
                # a row's dot product stands down a column; the diagonal
                # lays it along the lanes, as the result is stored
                col = jnp.sum(other_ref[...].astype(jnp.float32) * rows,
                              axis=1, keepdims=True)
                dot_ref[0] = jnp.sum(jnp.where(diag, col, 0.0), axis=0, keepdims=True)
            if scaled:
                rows = rows * jnp.sum(
                    jnp.where(diag, scale_ref[0], 0.0), axis=1, keepdims=True
                )
            out_ref[...] = rows.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("groups", "out_dtype", "interpret", "name")
)
def _gather(pi, pj, n, tok, scale, src, other, *, groups, out_dtype,
            interpret, name):
    row_tiles, _, tile = tok.shape
    tokens, d = src.shape
    tt = token_tile(tokens, tile)
    steps = pi.shape[0]
    rows_spec = pl.BlockSpec((tile, d), lambda p, pi, pj, n: (pi[p], 0))
    row_spec = pl.BlockSpec((1, 1, tile), lambda p, pi, pj, n: (pi[p], 0, 0))
    in_specs, args = [row_spec], [tok]
    if scale is not None:
        in_specs.append(row_spec)
        args.append(scale)
    in_specs.append(pl.BlockSpec((tt, d), lambda p, pi, pj, n: (pj[p], 0)))
    args.append(src)
    out_shape = [jax.ShapeDtypeStruct((row_tiles * tile, d), out_dtype)]
    out_specs = [rows_spec]
    if other is not None:
        in_specs.append(rows_spec)
        args.append(other)
        out_shape.append(jax.ShapeDtypeStruct((row_tiles, 1, tile), jnp.float32))
        out_specs.append(row_spec)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tt=tt, scaled=scale is not None,
                          dotted=other is not None),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
        metadata=_tiles_metadata(steps, groups, passes=_passes(src.dtype)),
    )(pi, pj, n, *args)
    return out[0] if other is None else tuple(out)


def _combine_kernel(qi_ref, qj_ref, n_ref, tok_ref, *refs, tt, weighted, added):
    del qi_ref
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    src_ref = refs.pop(0)
    add_ref = refs.pop(0) if added else None
    out_ref, acc = refs
    q = pl.program_id(0)

    @pl.when(q < n_ref[0])
    def _():
        first, last = _run_ends(qj_ref, n_ref, q)

        # a token tile's run opens with the entry that has no rows of its own
        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(jnp.logical_not(first))
        def _():
            sel = _select(tok_ref, qj_ref[q], tt)
            picked = jnp.where(sel, 1.0, 0.0).astype(jnp.bfloat16)
            rows = functools.reduce(jnp.add, [
                jnp.dot(picked, part, preferred_element_type=jnp.float32)
                for part in _addends(src_ref[...])
            ])
            if weighted:
                # a token holds one row of the tile at most: its weight is
                # the one non-zero of its line of the selection
                rows = rows * jnp.sum(jnp.where(sel, w_ref[0], 0.0), axis=1, keepdims=True)
            acc[...] += rows

        @pl.when(last)
        def _():
            total = acc[...]
            if added:
                total = total + add_ref[...].astype(jnp.float32)
            out_ref[...] = total.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tokens", "groups", "out_dtype", "interpret", "name")
)
def _combine(qi, qj, n, tok, w, src, add, *, tokens, groups, out_dtype, interpret, name):
    _, _, tile = tok.shape
    d = src.shape[1]
    tt = token_tile(tokens, tile)
    steps = qi.shape[0]
    row_spec = pl.BlockSpec((1, 1, tile), lambda q, qi, qj, n: (qi[q], 0, 0))
    tokens_spec = pl.BlockSpec((tt, d), lambda q, qi, qj, n: (qj[q], 0))
    in_specs, args = [row_spec], [tok]
    if w is not None:
        in_specs.append(row_spec)
        args.append(w)
    in_specs.append(pl.BlockSpec((tile, d), lambda q, qi, qj, n: (qi[q], 0)))
    args.append(src)
    if add is not None:
        in_specs.append(tokens_spec)
        args.append(add)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt, weighted=w is not None,
                          added=add is not None),
        out_shape=jax.ShapeDtypeStruct((tokens, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=tokens_spec,
            scratch_shapes=[pltpu.VMEM((tt, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
        metadata=_tiles_metadata(steps, tokens // tt + groups,
                                 passes=_passes(src.dtype)),
    )(qi, qj, n, *args)


def rows_gather(src, plan: dict, *, groups: int, out_dtype=None, scale=None,
                dot_with=None, name: str = "moe_rows_gather",
                interpret: bool | None = None):
    """``out[r] = src[token of r]`` for every buffer row that holds a
    choice, 0 for a row that pads a group in an active tile; rows of
    other tiles are left unwritten.

    ``src`` (tokens, D); ``plan`` is ``pair_plan``'s.  With ``scale``
    (row tiles, 1, tile) float32 the row is multiplied by its entry, in
    float32, before the cast to ``out_dtype``.  With ``dot_with`` (R, D),
    also returns (row tiles, 1, tile) float32: each row's dot product
    with the row gathered for it (before the scale), 0 where none."""
    if interpret is None:
        interpret = interpret_default()
    return _gather(
        *plan["by_row"], plan["tok"], scale, src, dot_with, groups=groups,
        out_dtype=out_dtype or src.dtype, interpret=interpret, name=name,
    )


def rows_combine(src, plan: dict, *, tokens: int, groups: int, out_dtype,
                 weights=None, add=None, name: str = "moe_rows_combine",
                 interpret: bool | None = None):
    """``out[t] = sum over the buffer rows r that hold a choice of token t
    of weights[r] * src[r]`` (of ``src[r]`` without ``weights``), summed
    in float32, plus ``add[t]`` (tokens, D) where given, in float32, then
    cast to ``out_dtype`` once; without ``add``, 0 for a token with none.

    ``src`` (R, D), read only in row tiles that hold such a row;
    ``weights`` (row tiles, 1, tile) float32.  The weighted form rests on
    **a token holding at most one row of a row tile**: the rows are
    selected first and the token's weight, read off its line of the
    selection, multiplies what was selected (each product of a weight and
    a row in float32, rounded once).  A plan of ``dropless_plan`` has it:
    a row tile belongs to one expert and ``top_k`` gives a token each
    expert once.  The unweighted form sums whatever a tile holds."""
    if interpret is None:
        interpret = interpret_default()
    return _combine(
        *plan["by_token"], plan["tok"], weights, src, add, tokens=tokens,
        groups=groups, out_dtype=out_dtype, interpret=interpret, name=name,
    )
