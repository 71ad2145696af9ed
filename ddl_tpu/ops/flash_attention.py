"""Pallas TPU flash attention: tiled online-softmax attention, fwd + bwd.

The hot op of the transformer family (``models/transformer.py``).  XLA's
default lowering materialises the (T x T) score matrix in HBM; this kernel
never sees more than one (block_q x block_k) tile at a time: the grid's
innermost dimension walks K/V blocks against a resident Q block while
running row-max / row-sum statistics live in VMEM scratch across grid steps
(the same online softmax the ring schedule uses *across* devices, here
applied *within* one device's block loop).  The forward's per-program
VMEM is O(block_q x head_dim + block_k x head_dim) regardless of sequence
length, and every matmul lands on the MXU at (block, head_dim) granularity.

The forward keeps those statistics a lane tile wide, ``(block_q, 128)``
float32 each.  A row's running max is held equal along its lanes, so
``s - m`` and ``acc * corr`` take it by repeating the tile (or a slice of
it, where V's head is narrower than 128), never by a broadcast out of one
lane; its running sum is held as 128 per-lane partial sums, to which a K
step adds the probabilities' lane tiles with vector adds.  A K step then
costs whole-vreg loads, stores and vector ops and one reduction across
lanes, the max; the sum across lanes happens once a Q block, at its
close, which also turns the finished ``m + log l`` from rows to lanes by
reading each 128-row square's diagonal down its sublanes (``lse`` leaves
as ``(bh, 1, t)``: the backward and the ring merge read it so).  As
``(block_q, 1)`` columns the same bookkeeping was one-lane masked loads
and stores, two reductions, two lane broadcasts a K step and a strided
store a row at the close, and the forward's time followed the (row, K
step) pairs a call makes, not its area (``flash_tile_plan``'s
``row_steps``; PERF.md section 6, PR 26 and 32).

The backward pass is one kernel over the same band, with a saved per-row
logsumexp: each run of sub-tiles recomputes its probabilities from the
residuals instead of storing them (rematerialisation in kernel form) and
makes dS once, from which dQ, dK and dV all accumulate (five matmuls a
sub-tile; the two-kernel decomposition computes S and dP twice, seven).
Its grid runs at K/V-head granularity: dQ accumulates over the innermost
K/V walk in a block-sized scratch as the forward's output does, and the
head's dK and dV stay resident in VMEM for the whole sequence
(``2 * T * head_dim`` float32) across every Q block of every query head in
the group, written once; no partial sum goes through HBM.

A causal (or windowed) call does the band's work and little more, at two
granularities.  The grid skips whole (block_q x block_k) tiles outside the
band via predicated execution (``pl.when``), the block-level analog of the
ring schedule masking future blocks; that alone halves the causal FLOPs
only at long T, and never engages while ``T <= block_k`` (one K block:
every tile touches the band).  So inside a live tile both kernels walk Q
sub-blocks and compute, for each, only the run of K sub-blocks it can
see, in one pass, masking only the sub-blocks the band's edge crosses
(``_visible``, ``_scores``).  With the defaults at T=1024 that is 10 of
the square's 16 sub-tiles of 256 x 256 (62.5%), 4 of them masked;
``flash_tile_plan`` counts it for any call, and every kernel carries its
count into the compiled program (``obs hbm`` prints it).

Layout: (B, T, H, D) public API; internally heads fold into the grid's
leading dimension so each program works on one (head, Q-block, K-block)
cell.  On the CPU backend the kernels run interpreted so the same tests
run on the simulated mesh.

Grouped-query attention is native: with ``Hkv < H`` K/V heads
(``H % Hkv == 0``), the K/V BlockSpecs index the shared K/V head for each
query head's grid row directly — K/V are never materialised at H heads, so
the K/V tensors (and the dK/dV gradients, which the backward accumulates at
Hkv granularity over every query head in the group) stay ``H/Hkv`` times
smaller in HBM than a repeat-then-attend lowering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["flash_attention", "flash_attention_with_lse", "flash_tile_plan"]

_NEG_INF = -1e30
# default grid blocks (``flash_attention``'s docstring has the sweep)
_BLOCK_Q = 1024
_BLOCK_K = 1024


def _pick_block(t: int, requested: int) -> int:
    block = min(requested, t)
    while t % block:
        block //= 2
    return max(block, 1)


def _band(r0, nr, k0, nk, causal, window):
    """The visible band against one tile, at any granularity: rows
    ``[r0, r0 + nr)`` and key positions ``[k0, k0 + nk)`` (a grid tile, a
    sub-tile, one (row, key) pair).  Row q attends keys in
    ``(q - window, q]``; ``window = 0`` means unbounded history (plain
    causal).  Returns ``(live, full)``: the tile holds a visible pair /
    every pair of it is visible.  The one statement of the rule: the grid's
    tile skip, the run of sub-tiles a kernel walks, which of them take a
    mask and ``flash_tile_plan`` all ask it.  Works on Python ints, numpy
    arrays and traced scalars alike."""
    if not causal:
        return True, True
    live = k0 <= r0 + nr - 1
    full = k0 + nk - 1 <= r0
    if window:
        live = live & (k0 + nk - 1 > r0 - window)
        full = full & (k0 > r0 + nr - 1 - window)
    return live, full


def _qk_live(i, j, bq, bk, causal, window, kv_offset=0):
    """Whether the (q block i, k block j) tile intersects the visible band
    (the block-skip predicate; window extends causal's future-skip with a
    past-skip).  ``kv_offset`` shifts the K/V coordinates ``kv_offset``
    positions EARLIER than the queries (the ring schedule's off-diagonal
    hops, where the K/V block originated ``hop * T_local`` positions
    back)."""
    return _band(i * bq, bq, j * bk - kv_offset, bk, causal, window)[0]


def _band_mask(r0, k0, s, window):
    """Mask the (rows from r0) x (keys from k0) score tile ``s`` to the
    band: the two iotas, compare and select that only a sub-tile the
    band's edge crosses needs.  Masked scores are ``-inf`` against row
    maxima floored at ``_NEG_INF``: their probability is exactly 0 even in
    a row whose whole visible set is masked (possible in a live tile when
    ``kv_offset`` or a window pushes the band off the row), so such a
    row's output is 0 and its lse stays at the floor, not mean-of-V
    garbage, with no second select on the probabilities."""
    q_pos = r0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = k_pos <= q_pos
    if window:
        keep &= k_pos > q_pos - window
    return jnp.where(keep, s, -jnp.inf)


# Edges of the square sub-tile a causal call's resident tile is walked in:
# the finer one where the band is a large share of the square and its
# branches stay few, the coarser one elsewhere (``_sub_tile``).
_SUB_TILE = 256
_SUB_TILE_LONG = 512
_SHORT_T = 1024


def _sub_tile(t, block_q, block_k, causal, window):
    """The (rows, keys) sub-tile each kernel walks its resident
    (block_q x block_k) tile in: the one place the rule is stated, a
    function of what the call shows (v5e, head_dim 64, bf16; the sweep is
    in PERF.md section 6, PR 26).

    * A non-causal call has no band, and takes the whole tile: exactly
      the pre-walk computation.
    * A causal call takes squares.  Finer squares follow the band more
      closely (T=1024: 62.5% of the square at 256, 75% at 512) and feed
      the MXU shorter runs.  Up to ``_SHORT_T`` the band is most of what
      is computed and 256 wins (forward + backward 2.73 ms against 2.87
      at b16 h12 T1024); beyond it most tiles lie wholly inside the band,
      and 512 wins (T=8192: 9.33 ms against 10.86).
    * A window adds a branch for every run that starts inside the block:
      at 256 their code outgrows what the core holds (T=8192 W=1024
      backward: 11.5 ms against 3.8), so a windowed call takes 512.
    """
    if not causal:
        return block_q, block_k
    edge = _SUB_TILE if t <= _SHORT_T and not window else _SUB_TILE_LONG
    return _pick_block(block_q, edge), _pick_block(block_k, edge)


_KERNELS = ("flash_fwd", "flash_bwd_dkv")


@functools.lru_cache(maxsize=256)
def _grid_runs(t, block_q, block_k, sub_q, sub_k, window, kv_offset):
    """Every run a causal call's grid reaches, as ``(a, c_lo, c_hi,
    diagonal)``: for each (Q block, K block) tile and each Q sub-block
    ``a`` of it that sees any of the K block, the run ``[c_lo, c_hi)`` of
    K sub-blocks it sees and whether the run's end takes the mask (it
    ends inside the block, cut short by the diagonal, or the diagonal
    crosses one of its sub-blocks).  ``_band`` on Python ints, in grid
    order, one entry a meeting: ``flash_tile_plan`` sums them, and the
    kernels take their branches from the distinct ones, so a run no tile
    of this grid can reach costs no code."""
    n = block_k // sub_k
    runs = []
    for i in range(t // block_q):
        for a in range(block_q // sub_q):
            r0 = i * block_q + a * sub_q
            for j in range(t // block_k):
                keys = [j * block_k - kv_offset + c * sub_k for c in range(n)]
                lives = [_band(r0, sub_q, k, sub_k, True, window)[0] for k in keys]
                if not any(lives):
                    continue
                c_lo = lives.index(True)
                c_hi = c_lo + sum(lives)
                crossed = any(
                    live and not full for live, full in
                    (_band(r0, sub_q, k, sub_k, True, 0) for k in keys)
                )
                runs.append((a, c_lo, c_hi, c_hi < n or crossed))
    return tuple(runs)


def flash_tile_plan(
    t: int,
    block_q: int = _BLOCK_Q,
    block_k: int = _BLOCK_K,
    causal: bool = False,
    window: int = 0,
    kv_offset: int = 0,
) -> dict:
    """What one (batch, head) row of a ``flash_attention`` call at these
    arguments computes of its T x T square, per kernel: sub-tiles in the
    square (``total``), sub-tiles the kernel computes (``computed``: those
    ``_band`` calls live, the runs it walks) and how many of those it
    masks (``masked``: those the band's edge crosses, and where sub-tiles
    and band are not aligned the neighbour an edge can reach), with the
    sub-tile's shape, and ``row_steps``: the (row, K step) pairs it makes,
    ``sub_q`` rows for every run it walks (a K step is one pass of the
    online softmax over a run: a row's statistics are read, updated and
    written once, whatever the run's width; it is what the forward's time
    followed before its statistics were lane-dense, PERF.md section 6, PR
    26 and 32).  Pure arithmetic on the arguments, by the predicate
    and the mask rule the kernels' walk uses; each ``pallas_call`` carries
    its kernel's numbers (times its rows) as ``metadata``, which the
    compiled step's ``hbm_plan`` record sums
    (``obs/scope.kernel_tiles``)."""
    bq, bk = _pick_block(t, block_q), _pick_block(t, block_k)
    sub_q, sub_k = _sub_tile(t, bq, bk, causal, window)
    tiles = {"total": (t // sub_q) * (t // sub_k), "computed": 0, "masked": 0,
             "row_steps": 0}
    if not causal:
        tiles["computed"] = tiles["total"]
        tiles["row_steps"] = tiles["total"] * sub_q
    else:
        reach = _edge_reach(sub_q, sub_k, bq, bk, kv_offset, window)
        for _, c_lo, c_hi, diagonal in _grid_runs(
            t, bq, bk, sub_q, sub_k, window, kv_offset
        ):
            tiles["computed"] += c_hi - c_lo
            tiles["masked"] += len(_masked(c_hi - c_lo, reach, diagonal))
            tiles["row_steps"] += sub_q
    # both kernels walk the same sub-tiles today; the record is per
    # kernel so that a kernel with a walk of its own can say so
    return {"sub_tile": [sub_q, sub_k], **{name: dict(tiles) for name in _KERNELS}}


def _walk(name, rows, t, block_q, block_k, causal, window, kv_offset):
    """What a launcher hands its ``pallas_call`` for one call's band: the
    kernel's static walk arguments, and its ``metadata=``, kernel
    ``name``'s tile plan over ``rows`` (batch, head) rows as strings."""
    sub_q, sub_k = _sub_tile(t, block_q, block_k, causal, window)
    walk = dict(sub_q=sub_q, sub_k=sub_k, runs=None, reach=(0, 0))
    if causal:
        walk.update(
            runs=_grid_runs(t, block_q, block_k, sub_q, sub_k, window, kv_offset),
            reach=_edge_reach(sub_q, sub_k, block_q, block_k, kv_offset, window),
        )
    plan = flash_tile_plan(t, block_q, block_k, causal, window, kv_offset)
    metadata = {f"tiles_{key}": str(rows * n) for key, n in plan[name].items()}
    return walk, metadata


def _run(flags):
    """``(first, end)`` of the single run of true flags in a short static
    list of traced scalars (``(n, n)`` when none is true)."""
    first = n_true = 0
    seen = False
    for f in flags:
        seen = seen | f
        first = first + jnp.where(seen, 0, 1)
        n_true = n_true + jnp.where(f, 1, 0)
    return first, first + n_true


def _visible_run(r0, sub_q, k0, sub_k, n, window):
    """Which of the resident K block's ``n`` sub-blocks rows
    ``[r0, r0 + sub_q)`` of a causal call see: ``(lo, hi, future)``, the
    one run ``[lo, hi)`` of sub-blocks that hold a visible pair, and
    whether the band's future edge (the diagonal) crosses any of them.
    Counted from ``_band`` on program-id scalars."""
    bands = [_band(r0, sub_q, k0 + c * sub_k, sub_k, True, 0)
             for c in range(n)]
    future = functools.reduce(
        lambda x, y: x | y, [live & ~full for live, full in bands]
    )
    if window:
        bands = [_band(r0, sub_q, k0 + c * sub_k, sub_k, True, window)
                 for c in range(n)]
    lo, hi = _run([live for live, _ in bands])
    return lo, hi, future


def _edge_reach(sub_q, sub_k, block_q, block_k, kv_offset, window):
    """``(past, future)``: the most sub-blocks either edge of the band
    crosses in one Q sub-block's run, from how row and key sub-blocks can
    be aligned (their starts differ by ``kv_offset`` plus multiples of
    what divides every block): 1 for the diagonal of aligned squares, 2
    for a window that is no multiple of the sub-tile."""
    g = math.gcd(sub_q, block_q, block_k)
    phases = {(kv_offset + m * g) % sub_k for m in range(sub_k // math.gcd(g, sub_k))}

    def crossed(shift):  # the edge ``k == q - shift`` against rows [0, sub_q)
        return max(
            sum(
                1 for c in range(-2 - shift // sub_k, sub_q // sub_k + 2)
                if c * sub_k - phase + shift <= sub_q - 1
                and c * sub_k - phase + shift + sub_k - 1 > 0
            )
            for phase in phases
        )

    # the past edge is the diagonal moved ``window`` keys back
    return (crossed(window) if window else 0), crossed(0)


def _masked(n_run, reach, diagonal):
    """Which of a run's ``n_run`` sub-blocks take the mask: the first
    ``reach[0]`` (the past edge; none without a window) and, where the
    diagonal crosses the run, the last ``reach[1]``."""
    past, future = reach
    return sorted(
        set(range(min(past, n_run)))
        | (set(range(max(n_run - future, 0), n_run)) if diagonal else set())
    )


def _visible(step, branches, r0, sub_q, k0, sub_k, n, window):
    """Run ``step(c_lo, c_hi, diagonal)`` once, on the run ``[c_lo, c_hi)``
    of the resident K block's sub-blocks that rows ``[r0, r0 + sub_q)``
    can see, in ONE pass over the whole run (a K step has a price a row
    beside its area's: PERF.md section 6, PR 26 and 32), and not at all
    when they see none.  The run's bounds are program-id
    arithmetic and a score tile's shape is static, so each run in
    ``branches`` (the distinct runs this Q sub-block meets anywhere in
    the grid, ``_grid_runs``) is its own ``pl.when`` branch, of which at
    most one executes.  ``diagonal`` says whether the run's end takes the
    mask: always when it ends inside the block (what cut it short is the
    diagonal), and by ``_visible_run`` when it reaches the block's end (a
    block wholly in the past has a branch without).  ``branches=None``: a
    non-causal call, which has no band and takes the whole tile,
    unmasked: the pre-walk computation."""
    if branches is None:
        return step(0, n, False)
    lo, hi, future = _visible_run(r0, sub_q, k0, sub_k, n, window)
    diagonal = (hi < n) | future
    for c_lo, c_hi, masked_end in branches:
        pl.when(
            (lo == c_lo) & (hi == c_hi)
            & (diagonal if masked_end else ~diagonal)
        )(functools.partial(step, c_lo, c_hi, masked_end))


def _branches(runs, a):
    """Q sub-block ``a``'s distinct runs out of ``_grid_runs``' (None
    stays None: a non-causal call)."""
    if runs is None:
        return None
    return sorted({run[1:] for run in runs if run[0] == a})


def _scores(q, k_blk, r0, k_first, sub_k, masked, window):
    """``q k^T`` over a run of K sub-blocks (keys from ``k_first``), with
    the band's mask on the sub-blocks ``masked`` names and on no other."""
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
    if not masked:
        return s
    parts = [
        s[:, e * sub_k:(e + 1) * sub_k] for e in range(s.shape[1] // sub_k)
    ]
    for e in masked:
        parts[e] = _band_mask(r0, k_first + e * sub_k, parts[e], window)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


# A vreg's lanes: the width the forward keeps a row's running max and sum at
_LANES = 128


def _lanes(x, n):
    """``x`` (rows, 128), equal along a row's lanes, at ``n`` lanes: whole
    lane tiles by repetition and a slice of the tile for the rest, so
    nothing is broadcast from one lane."""
    reps, rest = divmod(n, _LANES)
    parts = []
    if reps:
        parts.append(pltpu.repeat(x, reps, axis=1) if reps > 1 else x)
    if rest:
        parts.append(x[:, :rest])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _lane_sums(p):
    """``p`` (rows, n) as 128 partial sums a row: its lane tiles added up
    by vector adds, no reduction across lanes (a ragged last tile is
    padded with zeros)."""
    rows, n = p.shape
    if n % _LANES:
        p = jnp.concatenate(
            [p, jnp.zeros((rows, -n % _LANES), p.dtype)], axis=1
        )
    return sum(p[:, c:c + _LANES] for c in range(0, p.shape[1], _LANES))


def _rows_to_lanes(x):
    """``x`` (rows, 128), equal along a row's lanes, as (1, rows): row r on
    lane r, the layout lse leaves in.  Each square of 128 rows keeps its
    diagonal and is summed down its sublanes (one value and zeros:
    exact): vector work on 16 vregs a square, where relaying a (rows, 1)
    column out to lanes costs a strided store and a rotate a row."""
    out = []
    for r0 in range(0, x.shape[0], _LANES):
        square = x[r0:r0 + _LANES]
        diagonal = (
            lax.broadcasted_iota(jnp.int32, square.shape, 0)
            == lax.broadcasted_iota(jnp.int32, square.shape, 1)
        )
        row = jnp.where(diagonal, square, 0.0).sum(axis=0, keepdims=True)
        out.append(row[:, :square.shape[0]])
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc, *, scale,
    causal, window=0, kv_offset=0, sub_q, sub_k, runs, reach,
):
    # m_sc: a row's running max, equal along its 128 lanes; l_sc: its
    # running sum as 128 per-lane partial sums, summed at the close (the
    # module's text says why)
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # K/V blocks outside the visible band contribute nothing — skip
    live = _qk_live(i, j, bq, bk, causal, window, kv_offset)

    @pl.when(live)
    def _():
        k0 = j * bk - kv_offset
        for a in range(bq // sub_q):  # each Q sub-block has its own band
            rows = slice(a * sub_q, (a + 1) * sub_q)
            r0 = i * bq + a * sub_q

            def step(c_lo, c_hi, diagonal, rows=rows, r0=r0):
                keys = slice(c_lo * sub_k, c_hi * sub_k)
                masked = _masked(c_hi - c_lo, reach, diagonal)
                q = q_ref[0, rows, :].astype(jnp.float32) * scale
                k_blk = k_ref[0, keys, :].astype(jnp.float32)
                v_blk = v_ref[0, keys, :].astype(jnp.float32)
                s = _scores(q, k_blk, r0, k0 + c_lo * sub_k, sub_k, masked,
                            window)
                m = m_sc[rows]
                new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - _lanes(new_m, s.shape[1]))
                corr = jnp.exp(m - new_m)
                l_sc[rows] = l_sc[rows] * corr + _lane_sums(p)
                acc_sc[rows] = acc_sc[rows] * _lanes(
                    corr, acc_sc.shape[1]
                ) + jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
                m_sc[rows] = new_m

            _visible(step, _branches(runs, a), r0, sub_q, k0, sub_k,
                     bk // sub_k, window)

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_sc[:].sum(axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc_sc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = _rows_to_lanes(m_sc[:] + jnp.log(l))


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_sc, dk_sc, dv_sc, *, scale, causal, window=0, kv_offset=0, q_blocks=1,
    sub_q, sub_k, runs, reach,
):
    # grid: (b*kv_heads, group*q_blocks, k_blocks).  The innermost
    # dimension walks K/V blocks against a resident Q block, so dQ
    # accumulates in a block-sized scratch as the forward's output does;
    # the middle one walks every (query head in the group, Q block) pair
    # of the K/V head, whose dK and dV stay resident for all of it in
    # whole-sequence scratch, one (block_k, d) slab a K block, and leave
    # once, at the head's last step.
    iz, j = pl.program_id(1), pl.program_id(2)
    nz, nk = pl.num_programs(1), dk_sc.shape[0]
    i = iz % q_blocks  # Q-block index within the current group member
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when((iz == 0) & (j == 0))
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(j == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    # K/V blocks outside the Q block's visible band contribute nothing
    live = _qk_live(i, j, bq, bk, causal, window, kv_offset)

    @pl.when(live)
    def _():
        k0 = j * bk - kv_offset
        # the forward's walk (the K sub-blocks each Q sub-block sees): S,
        # P, dP and dS of a run are computed once and feed all three sums
        for a in range(bq // sub_q):
            rows = slice(a * sub_q, (a + 1) * sub_q)
            r0 = i * bq + a * sub_q

            def step(c_lo, c_hi, diagonal, rows=rows, r0=r0):
                keys = slice(c_lo * sub_k, c_hi * sub_k)
                masked = _masked(c_hi - c_lo, reach, diagonal)
                q_blk = q_ref[0, rows, :].astype(jnp.float32) * scale
                k_blk = k_ref[0, keys, :].astype(jnp.float32)
                v_blk = v_ref[0, keys, :].astype(jnp.float32)
                do_blk = do_ref[0, rows, :].astype(jnp.float32)
                s = _scores(q_blk, k_blk, r0, k0 + c_lo * sub_k, sub_k,
                            masked, window)
                p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
                dv_sc[j, keys, :] += jnp.dot(
                    p.T, do_blk, preferred_element_type=jnp.float32
                )
                dp = jnp.dot(
                    do_blk, v_blk.T, preferred_element_type=jnp.float32
                )
                ds = p * (dp - delta_ref[0, 0, rows][:, None])
                dk_sc[j, keys, :] += jnp.dot(
                    ds.T, q_blk, preferred_element_type=jnp.float32
                )
                dq_sc[rows] += jnp.dot(
                    ds, k_blk, preferred_element_type=jnp.float32
                )

            _visible(step, _branches(runs, a), r0, sub_q, k0, sub_k,
                     bk // sub_k, window)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)

    @pl.when((iz == nz - 1) & (j == nk - 1))
    def _():
        for jj in range(nk):
            blk = slice(jj * bk, (jj + 1) * bk)
            dk_ref[0, blk, :] = dk_sc[jj].astype(dk_ref.dtype)  # scale folded into q_blk
            dv_ref[0, blk, :] = dv_sc[jj].astype(dv_ref.dtype)


def _kv_row(b, q_heads, kv_heads):
    """Folded K/V row serving folded Q/grid row ``b``: same batch, the
    group's shared K/V head (identity when q_heads == kv_heads)."""
    g = q_heads // kv_heads
    return (b // q_heads) * kv_heads + (b % q_heads) // g


# The two launchers are jitted so that a program with many attention layers
# traces and lowers each kernel once, not once a layer: the walk's branches
# are several times the pre-walk body, and a 12-layer step paid for them once
# a kernel a layer at every start, cached executable or not (PERF.md section
# 6, PR 26).
@functools.partial(jax.jit, static_argnums=tuple(range(3, 11)))
def _flash_fwd_impl(
    q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
    q_heads, kv_heads,
):
    bh, t, d = q.shape
    dv = v.shape[-1]  # V's head may be wider than Q's and K's
    scale = 1.0 / (d ** 0.5)
    kv_idx = lambda b, i, j: (_kv_row(b, q_heads, kv_heads), j, 0)
    walk, metadata = _walk(
        "flash_fwd", bh, t, block_q, block_k, causal, window, kv_offset
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, kv_offset=kv_offset, **walk),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            # row stats ride in a (bh, 1, t) layout: the (1, 1, block_q)
            # block then satisfies Mosaic's tiling rule (second-to-last
            # block dim == array dim; last dim a 128-multiple or == t)
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, dv), kv_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        metadata=metadata,
    )(q, k, v)
    return out, lse


# What the backward kernel may take of a core's 128 MiB of VMEM (Mosaic's
# default scope is 16 MiB), and what of that is left for a K/V head's
# resident gradients once the working tiles are counted: the Q, K, V and dO
# blocks twice (the pipeline's buffers), dQ's scratch and block, and a
# sub-tile run's float32 scores, probabilities and their two transposes
# (about 14 MiB at 1024 x 1024 blocks walked in 512-row runs, head_dim 128).
_BWD_VMEM_LIMIT = 96 * 1024 * 1024
_BWD_RESIDENT_LIMIT = _BWD_VMEM_LIMIT - 24 * 1024 * 1024


@functools.partial(jax.jit, static_argnums=tuple(range(7, 15)))
def _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal, window,
                    kv_offset, block_q, block_k, interpret, q_heads,
                    kv_heads):
    """Shared backward: one kernel, one walk of the band, with
    ``ds = p * (dp - (delta - dlse))`` feeding dQ, dK and dV alike.

    With ``dlse=None`` this is the classic flash backward (cotangent on the
    output only).  A nonzero ``dlse`` (cotangent on the per-row logsumexp,
    layout (bh, 1, t)) arises when the caller consumes lse — the ring
    schedule's cross-block combination does — and enters the kernel purely
    through the delta term: d lse_i/d s_ij = p_ij, so the correction folds
    into the same ``p * (...)`` product the kernel already computes.

    The grid runs at K/V-head granularity, ``(bkv, g * q_blocks,
    k_blocks)``: the middle dimension walks every (group member, Q block)
    pair of the head, the innermost the K/V blocks against it.  dQ leaves
    a Q block at a time; the head's dK and dV (the whole group's
    contribution, one (bkv, t, d) gradient) stay in VMEM until its last
    step, ``2 * t * d`` float32 beside their output blocks.  A
    sequence too long for that is refused from the shapes: it belongs on
    the ring schedule, which hands this kernel ``T_local``.
    """
    bh, t, d = q.shape
    dv = v.shape[-1]
    bkv = k.shape[0]
    g = q_heads // kv_heads
    scale = 1.0 / (d ** 0.5)
    # VMEM rows are whole 128-lane tiles
    lanes = -(-d // 128) * 128 + -(-dv // 128) * 128
    resident = t * lanes * (4 + 2 * k.dtype.itemsize)
    if resident > _BWD_RESIDENT_LIMIT:
        raise ValueError(
            f"flash backward keeps a K/V head's dK and dV resident: T={t}, "
            f"head_dim={d} need {resident} bytes of VMEM, over "
            f"{_BWD_RESIDENT_LIMIT}; shard the sequence (attn_impl='ring')"
        )
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, t) — same row-stat layout as lse
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    nq, nk = t // block_q, t // block_k

    def q_row(b, iz):
        return (b // kv_heads) * q_heads + (b % kv_heads) * g + iz // nq

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda b, iz, j: (q_row(b, iz), iz % nq, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, block_q), lambda b, iz, j: (q_row(b, iz), 0, iz % nq)
    )
    do_spec = pl.BlockSpec(
        (1, block_q, dv), lambda b, iz, j: (q_row(b, iz), iz % nq, 0)
    )
    kv_spec = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_k, width), lambda b, iz, j: (b, j, 0))
    head_spec = lambda width: pl.BlockSpec(  # noqa: E731
        (1, t, width), lambda b, iz, j: (b, 0, 0))
    # the kernel is named for its grid, the K/V head's; the plan counts
    # (batch, query head) rows like the forward's
    walk, metadata = _walk(
        "flash_bwd_dkv", bh, t, block_q, block_k, causal, window, kv_offset
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, window=window,
            kv_offset=kv_offset, q_blocks=nq, **walk,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, t, dv), v.dtype),
        ),
        grid=(bkv, g * nq, nk),
        in_specs=[q_spec, kv_spec(d), kv_spec(dv), do_spec, row_spec, row_spec],
        out_specs=(q_spec, head_spec(d), head_spec(dv)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((nk, block_k, d), jnp.float32),
            pltpu.VMEM((nk, block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_VMEM_LIMIT
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
        metadata=metadata,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(
    q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
    q_heads, kv_heads,
):
    return _flash_fwd_impl(
        q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
        q_heads, kv_heads,
    )


def _flash_lse_vjp_fwd(
    q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
    q_heads, kv_heads,
):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
        q_heads, kv_heads,
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(
    causal, window, kv_offset, block_q, block_k, interpret, q_heads,
    kv_heads, residuals, cts,
):
    do, dlse = cts
    q, k, v, out, lse = residuals
    return _flash_bwd_impl(
        q, k, v, out, lse, do, dlse, causal, window, kv_offset, block_q,
        block_k, interpret, q_heads, kv_heads,
    )


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _fold_heads(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _validate_flash_args(q, k, v, causal, window, kv_offset=0):
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("window > 0 requires causal=True (sliding causal window)")
    if kv_offset < 0:
        raise ValueError(f"kv_offset must be >= 0, got {kv_offset}")
    if kv_offset and not causal:
        raise ValueError(
            "kv_offset shifts the causal/window band; it requires causal=True"
        )
    h, hkv = q.shape[2], k.shape[2]
    if v.shape[2] != hkv:
        raise ValueError(f"k has {hkv} heads but v has {v.shape[2]}")
    if h % hkv:
        raise ValueError(f"q heads {h} must divide by kv heads {hkv}")
    return h, hkv


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    window: int = 0,
    block_q: int = _BLOCK_Q,
    block_k: int = _BLOCK_K,
    interpret: bool | None = None,
    kv_offset: int = 0,
):
    """Flash attention. q: (B, T, H, D), k: (B, T, Hkv, D), v: (B, T, Hkv,
    Dv) -> (B, T, H, Dv); Dv is D unless V's head is wider than Q's and K's
    (differential attention's ``[v1, v2]``).

    Grouped-query attention is native: ``Hkv < H`` (``H % Hkv == 0``) makes
    each K/V head serve ``H/Hkv`` query heads via BlockSpec indexing — the
    K/V tensors and their gradients stay at Hkv heads end to end.

    ``window > 0`` (requires ``causal``) restricts each row to the last
    ``window`` positions — sliding-window attention, with blocks fully
    outside the band skipped like causal's future blocks, so compute drops
    from O(T^2) toward O(T * window).

    Differentiable (custom VJP, flash backward).  Block sizes are clamped to
    the sequence length and halved until they divide it; pick powers of two.
    Defaults (1024x1024) come from a v5e device-only sweep of these
    kernels (PR 26's own, JAX 0.9.0; ``bench/kernels.py`` slope method;
    D=64, causal, bf16; forward / forward+backward ms a call).  At B=16,
    H=12, T=1024 (the benchmark cell's shape) ``block_q=1024`` gives 0.92 /
    2.73 against 1.20 / 3.36 at 512: one grid step a head where there were
    two (the pre-walk kernels gained the same in the forward, 0.85 against
    1.16).  At B=2, H=8, T=8192 it gives 2.41 / 9.33 against 2.48 / 10.25,
    and with a window of 1024 1.19 / 4.97 (pre-walk, 512x1024: 1.32 /
    7.03); ``block_q=2048`` loses (16.3).  ``block_k`` was not swept
    again: the older sweep that chose 1024 (PERF_HISTORY.md) found 512
    slower at every T.  The T^2 score tile stays out of HBM either way.
    Those backward figures are the two kernels' of that PR; the one
    kernel since PR 30 takes 1.15 ms a call at the cell's shape where
    they took 1.55 (kernel events of a device trace, v5e), and 1.65
    against 1.93 at B=1, H=12, T=8192, window 1024.  Those forward
    figures are the ``(block_q, 1)`` statistics' of that PR; lane-dense
    (PR 32's sweep, same method, forward ms a call before / after) the
    cell's shape takes 0.931 / 0.534 and its non-causal square 1.020 /
    0.741, so what does not depend on area fell from 0.78 ms to 0.19;
    B=2, T=4096, H=32 on 4 K/V heads of 128: 2.77 / 2.15 at window 2048
    and 2.98 / 2.38 full; B=2, T=4096, H=40 on 20 K heads of 64 under V
    heads of 128: 2.83 / 2.09 at window 512 and 3.99 / 3.36 full.  Block
    sizes were not swept again under it.
    ``interpret=None`` interprets on the CPU backend (tests on the
    simulated mesh) and compiles on a TPU (``ops/interpret.py``).
    """
    h, hkv = _validate_flash_args(q, k, v, causal, window, kv_offset)
    if interpret is None:
        interpret = interpret_default()
    b, t, _, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(t, block_k)
    # one custom_vjp for both public entry points: dropping lse here hands
    # its backward a zero cotangent, which the shared kernels fold away
    out, _ = _flash_lse(
        _fold_heads(q), _fold_heads(k), _fold_heads(v), causal, window,
        kv_offset, bq, bk, interpret, h, hkv,
    )
    return out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q,
    k,
    v,
    causal: bool = False,
    window: int = 0,
    block_q: int = _BLOCK_Q,
    block_k: int = _BLOCK_K,
    interpret: bool | None = None,
    kv_offset: int = 0,
):
    """Flash attention that also returns the per-row logsumexp.

    q: (B, T, H, D), k/v: (B, T, Hkv, D) -> (out (B, T, H, D),
    lse (B, H, T) float32) with
    ``lse = log sum_j exp(q_i . k_j / sqrt(D))`` over the visible keys.
    Two partial attentions over disjoint key sets combine exactly as
    ``lse = logaddexp(lse1, lse2); out = out1*exp(lse1-lse) +
    out2*exp(lse2-lse)`` — the blockwise composition the ring schedule
    uses to run this kernel per K/V ring hop
    (``parallel/ring_attention.py``).  Differentiable in out AND lse
    (shared backward kernels; the lse cotangent folds into delta).
    Grouped-query K/V (Hkv < H) supported as in ``flash_attention``."""
    h, hkv = _validate_flash_args(q, k, v, causal, window, kv_offset)
    if interpret is None:
        interpret = interpret_default()
    b, t, _, d = q.shape
    bq = _pick_block(t, block_q)
    bk = _pick_block(t, block_k)
    out, lse = _flash_lse(
        _fold_heads(q), _fold_heads(k), _fold_heads(v), causal, window,
        kv_offset, bq, bk, interpret, h, hkv,
    )
    return (
        out.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3),
        lse.reshape(b, h, t),
    )
