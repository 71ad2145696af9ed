"""Pallas TPU flash attention: tiled online-softmax attention, fwd + bwd.

The hot op of the transformer family (``models/transformer.py``).  XLA's
default lowering materialises the (T x T) score matrix in HBM; this kernel
never sees more than one (block_q x block_k) tile at a time: the grid's
innermost dimension walks K/V blocks against a resident Q block while
running row-max / row-sum statistics live in VMEM scratch across grid steps
(the same online softmax the ring schedule uses *across* devices, here
applied *within* one device's block loop).  Per-program VMEM is
O(block_q x head_dim + block_k x head_dim) regardless of sequence length,
and every matmul lands on the MXU at (block, head_dim) granularity.

The backward pass is the standard two-kernel flash decomposition with a
saved per-row logsumexp: one grid accumulates dQ over K/V blocks, one
accumulates dK/dV over Q blocks, both recomputing probabilities from the
residuals instead of storing them (rematerialisation in kernel form).

Causal masking skips the compute of strictly-future blocks via predicated
execution (``pl.when``), halving the causal FLOPs — the block-level analog
of the ring schedule masking future blocks.

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

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["flash_attention", "flash_attention_with_lse"]

_NEG_INF = -1e30


def _pick_block(t: int, requested: int) -> int:
    block = min(requested, t)
    while t % block:
        block //= 2
    return max(block, 1)


def _causal_mask(i, j, bq, bk, s, window=0, kv_offset=0):
    """Causal (and, with ``window > 0``, sliding-window) score mask: row
    q attends keys in ``(q - window, q]`` — ``window = 0`` means
    unbounded history (plain causal).  ``kv_offset`` shifts the K/V
    coordinates ``kv_offset`` positions EARLIER than the queries (the
    ring schedule's off-diagonal hops, where the K/V block originated
    ``hop * T_local`` positions back)."""
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk - kv_offset + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = k_pos <= q_pos
    if window:
        keep &= k_pos > q_pos - window
    return jnp.where(keep, s, _NEG_INF)


def _qk_live(i, j, bq, bk, causal, window, kv_offset=0):
    """Whether the (q block i, k block j) tile intersects the visible band
    (the block-skip predicate; window extends causal's future-skip with a
    past-skip; ``kv_offset`` as in ``_causal_mask``)."""
    if not causal:
        return True
    live = j * bk - kv_offset <= i * bq + bq - 1
    if window:
        live &= j * bk + bk - 1 - kv_offset > i * bq - window
    return live


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc, *, scale,
    causal, window=0, kv_offset=0,
):
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
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(i, j, bq, bk, s, window, kv_offset)
        m = m_sc[:]
        blk_max = s.max(axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m)
        # rows whose whole visible set is masked (possible in a live tile
        # when kv_offset pushes the band off the row): new_m == mask value
        # makes p = exp(0) = 1 — zero those entries so the row's output is
        # 0 and its lse stays at the -inf floor, not mean-of-V garbage
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m - new_m)
        l_sc[:] = l_sc[:] * corr + p.sum(axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        m_sc[:] = new_m

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_sc[:], 1e-30)
        o_ref[0] = (acc_sc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[:] + jnp.log(l))[:, 0]


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, scale,
    causal, window=0, kv_offset=0,
):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    live = _qk_live(i, j, bq, bk, causal, window, kv_offset)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(i, j, bq, bk, s, window, kv_offset)
        p = jnp.exp(s - lse)
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)  # empty-band rows (fwd note)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_sc, dv_sc, *, scale, causal, window=0, kv_offset=0, q_blocks=1,
):
    # grid: (b*kv_heads, k_blocks, group*q_blocks) — the innermost
    # dimension walks every (query head in the group, Q block) pair, so
    # dK/dV accumulate over the whole query-head group at Hkv granularity
    j, iz = pl.program_id(1), pl.program_id(2)
    nz = pl.num_programs(2)
    i = iz % q_blocks  # Q-block index within the current group member
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(iz == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    # Q blocks outside this K/V block's visible band contribute nothing
    live = _qk_live(i, j, bq, bk, causal, window, kv_offset)

    @pl.when(live)
    def _():
        q_blk = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[0, 0][:, None]
        delta_blk = delta_ref[0, 0][:, None]
        s = jnp.dot(q_blk, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(i, j, bq, bk, s, window, kv_offset)
        p = jnp.exp(s - lse_blk)
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)  # empty-band rows (fwd note)
        dv_sc[:] = dv_sc[:] + jnp.dot(
            p.T, do_blk, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do_blk, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk_sc[:] = dk_sc[:] + jnp.dot(
            ds.T, q_blk, preferred_element_type=jnp.float32
        )

    @pl.when(iz == nz - 1)
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)  # scale folded into q_blk
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)




def _kv_row(b, q_heads, kv_heads):
    """Folded K/V row serving folded Q/grid row ``b``: same batch, the
    group's shared K/V head (identity when q_heads == kv_heads)."""
    g = q_heads // kv_heads
    return (b // q_heads) * kv_heads + (b % q_heads) // g


def _flash_fwd_impl(
    q, k, v, causal, window, kv_offset, block_q, block_k, interpret,
    q_heads, kv_heads,
):
    bh, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    kv_idx = lambda b, i, j: (_kv_row(b, q_heads, kv_heads), j, 0)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, kv_offset=kv_offset),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            # row stats ride in a (bh, 1, t) layout: the (1, 1, block_q)
            # block then satisfies Mosaic's tiling rule (second-to-last
            # block dim == array dim; last dim a 128-multiple or == t)
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


def _flash_bwd_kernels(q, k, v, out, lse, do, dlse, causal, window,
                       kv_offset, block_q, block_k, interpret, q_heads,
                       kv_heads):
    """Shared backward: the two flash kernels with
    ``ds = p * (dp - (delta - dlse))``.

    With ``dlse=None`` this is the classic flash backward (cotangent on the
    output only).  A nonzero ``dlse`` (cotangent on the per-row logsumexp,
    layout (bh, 1, t)) arises when the caller consumes lse — the ring
    schedule's cross-block combination does — and enters the kernels purely
    through the delta term: d lse_i/d s_ij = p_ij, so the correction folds
    into the same ``p * (...)`` product the kernels already compute.

    Grouped K/V: dQ reads the group's shared K/V row per query head; the
    dK/dV grid runs at K/V-head granularity with its innermost dimension
    extended over every (group member, Q block) pair, accumulating the
    whole group's contribution into one (bkv, t, d) gradient.
    """
    bh, t, d = q.shape
    bkv = k.shape[0]
    g = q_heads // kv_heads
    scale = 1.0 / (d ** 0.5)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, t) — same row-stat layout as lse
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    kv_idx = lambda b, i, j: (_kv_row(b, q_heads, kv_heads), j, 0)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), kv_idx)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, kv_offset=kv_offset),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # grid (bkv, k_blocks, g * q_blocks): outermost at K/V-head
    # granularity, innermost walking every (group member, Q block) pair
    nq = t // block_q

    def q_row(b, iz):
        return (b // kv_heads) * q_heads + (b % kv_heads) * g + iz // nq

    q_spec_t = pl.BlockSpec(
        (1, block_q, d), lambda b, j, iz: (q_row(b, iz), iz % nq, 0)
    )
    kv_spec_t = pl.BlockSpec((1, block_k, d), lambda b, j, iz: (b, j, 0))
    row_spec_t = pl.BlockSpec(
        (1, 1, block_q), lambda b, j, iz: (q_row(b, iz), 0, iz % nq)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkdv_kernel, scale=scale, causal=causal, window=window,
            kv_offset=kv_offset, q_blocks=nq,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, t, d), v.dtype),
        ),
        grid=(bkv, t // block_k, g * nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t, row_spec_t],
        out_specs=(kv_spec_t, kv_spec_t),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


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
    return _flash_bwd_kernels(
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
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool | None = None,
    kv_offset: int = 0,
):
    """Flash attention. q: (B, T, H, D), k/v: (B, T, Hkv, D) -> (B, T, H, D).

    Grouped-query attention is native: ``Hkv < H`` (``H % Hkv == 0``) makes
    each K/V head serve ``H/Hkv`` query heads via BlockSpec indexing — the
    K/V tensors and their gradients stay at Hkv heads end to end.

    ``window > 0`` (requires ``causal``) restricts each row to the last
    ``window`` positions — sliding-window attention, with blocks fully
    outside the band skipped like causal's future blocks, so compute drops
    from O(T^2) toward O(T * window).

    Differentiable (custom VJP, flash backward).  Block sizes are clamped to
    the sequence length and halved until they divide it; pick powers of two.
    Defaults (512x1024) come from a v5e device-only sweep
    (``bench/kernels.py`` slope method; B=2, H=8, D=64, causal, bf16):
    ``block_k=1024`` beats 512 in both directions at every measured T —
    fwd 2.59 vs 4.14 ms and bwd 10.9 vs 13.3 at T=8192 (dense lowering:
    8.77 / 28.7) — and also with a sliding window (W=1024: fwd 1.32 vs
    1.46, bwd 7.01 vs 7.97), while keeping the T^2 score tile out of HBM.
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
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q,
    k,
    v,
    causal: bool = False,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
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
        out.reshape(b, h, t, d).transpose(0, 2, 1, 3),
        lse.reshape(b, h, t),
    )
