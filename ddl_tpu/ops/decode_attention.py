"""Pallas decode-attention kernel: one-pass cached attention for T=1.

Why a kernel, when XLA fuses attention fine at training shapes: the
decode step's cache works AGAINST XLA's layout assignment.  The score
einsum wants the cache's sequence dim in the 128-lane position (softmax
over lanes), so layout assignment makes the whole cache L-minor — and a
single-token ``dynamic_update_slice`` into an L-minor buffer lowers to a
full-cache rewrite, ~20 us/step per buffer at B=32/L=768 (measured: the
24 cache updates were the plurality of decode step time,
``bench/profile_decode.py``, PERF.md round 5).  A Pallas consumer breaks
the conflict: ``pallas_call`` operands use the default (feature-minor)
layout, so the cache write is genuinely in place, and the kernel does
the L-major contraction in VMEM where layout is free.  Measured effect
at B=32, GQA 12q/4kv, window 1024: 21.8k -> 35.3k tok/s bf16, 40.2k
with int8 cache+weights.

Structure: grid (B, L/block_l), sequential over the L tiles with a
flash-style online softmax (running max / denom / output accumulators in
VMEM scratch, finalised at the last tile) — VMEM holds one (block_l,
Hkv*Dh) K and V tile at a time, so cache capacity is unbounded.  Per
L tile, each K/V head's grouped scores and value contraction run as
small (G, block_l) dots in f32; the int8 variant folds the per-(token,
head) scales into the scores/probs so the cache is never dequantized to
a materialised buffer.

Masking is an additive f32 bias row (0 = attend, -1e30 = masked) built
by the caller — the same mask math as the XLA path (ring-slot positions
or linear positions), so rolling and full-cache decode share the kernel.

Used by every cache kind (``infer/kv_cache.py``, ``serve/kv_pool.PagedKV``)
for T=1 decode over the full cache where ``decode_attention_path`` says
``"kernel"``: one TPU device (multi-device decode keeps the einsum path —
GSPMD cannot partition a custom call; on the CPU the caches take the
einsum too, and ``tests/test_decode_attention.py`` runs the kernels
interpreted against it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["decode_attention", "pick_block_l", "quant_decode_attention"]

# Per-stage VMEM budget for one K or V tile.  Mosaic double-buffers both
# tiles and the kernel also materialises f32 per-head slices, so the
# working set is several times this; 3.5 MB with rows costed at bf16
# width (int8 tiles spend the difference on their f32 dequant slices)
# keeps the largest auto-picked case (bl 2048 at fused width 768) inside
# the ~16 MB scoped limit — compile-probed: bl>2048 at that width fails.
# Measured at B=32/L=6144 MHA bf16: 745 GB/s at bl=2048 vs 666 at 1024.
_TILE_BYTES = 3_500_000
_MIN_BLOCK_L = 512
_MAX_AUTO_BLOCK_L = 2048


def _finalize(o_ref, acc_sc, l_sc, j, nl):
    @pl.when(j == nl - 1)
    def _():
        o_ref[0] = (acc_sc[:] / jnp.maximum(l_sc[:], 1e-30)).astype(
            o_ref.dtype
        )


def _kernel(
    q_ref, k_ref, v_ref, bias_ref, o_ref, acc_sc, m_sc, l_sc,
    *, hkv: int, scale: float,
):
    j, nl = pl.program_id(1), pl.num_programs(1)
    h, d = q_ref.shape[1], q_ref.shape[2]
    g = h // hkv

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, -1e30)
        l_sc[:] = jnp.zeros_like(l_sc)

    bias = bias_ref[0, 0].astype(jnp.float32)  # (block_l,)
    for i in range(hkv):
        rows = slice(i * g, (i + 1) * g)
        qh = q_ref[0, rows, :].astype(jnp.float32)  # (G, D)
        kh = k_ref[0, :, i * d:(i + 1) * d].astype(jnp.float32)  # (bl, D)
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias[None, :]  # (G, bl)
        m = m_sc[rows, :]
        new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        p = jnp.where(s > -1e29, p, 0.0)  # fully-masked tile rows
        corr = jnp.exp(m - new_m)
        l_sc[rows, :] = l_sc[rows, :] * corr + p.sum(-1, keepdims=True)
        vh = v_ref[0, :, i * d:(i + 1) * d].astype(jnp.float32)
        acc_sc[rows, :] = acc_sc[rows, :] * corr + jax.lax.dot_general(
            p, vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[rows, :] = new_m
    _finalize(o_ref, acc_sc, l_sc, j, nl)


def _quant_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, o_ref,
    acc_sc, m_sc, l_sc, *, hkv: int, scale: float,
):
    j, nl = pl.program_id(1), pl.num_programs(1)
    h, d = q_ref.shape[1], q_ref.shape[2]
    g = h // hkv

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, -1e30)
        l_sc[:] = jnp.zeros_like(l_sc)

    bias = bias_ref[0, 0].astype(jnp.float32)
    for i in range(hkv):
        rows = slice(i * g, (i + 1) * g)
        qh = q_ref[0, rows, :].astype(jnp.float32)
        kh = k_ref[0, :, i * d:(i + 1) * d].astype(jnp.float32)
        # per-key scale folds into the (G, bl) scores: q.(kq*s) = (q.kq)*s
        ksr = ks_ref[0, i, :].astype(jnp.float32)  # (bl,)
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (ksr * scale)[None, :] + bias[None, :]
        m = m_sc[rows, :]
        new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        p = jnp.where(s > -1e29, p, 0.0)
        corr = jnp.exp(m - new_m)
        l_sc[rows, :] = l_sc[rows, :] * corr + p.sum(-1, keepdims=True)
        # value scale folds into the probs before the contraction
        p = p * vs_ref[0, i, :].astype(jnp.float32)[None, :]
        vh = v_ref[0, :, i * d:(i + 1) * d].astype(jnp.float32)
        acc_sc[rows, :] = acc_sc[rows, :] * corr + jax.lax.dot_general(
            p, vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[rows, :] = new_m
    _finalize(o_ref, acc_sc, l_sc, j, nl)


def _bias_spec(bias, b: int, bl: int) -> pl.BlockSpec:
    """BlockSpec for the additive mask, passed as (rows, 1, L): one
    shared row broadcast to every batch program, or B per-lane rows
    tiled along the batch grid dimension (the serving engine's
    continuous decode batch, where each lane's visible length differs).
    The unit middle axis is what lets one row be a block: Mosaic wants a
    block's second-to-last dim 8-divisible or equal to the array's, and
    a (1, bl) block of a (B, L) bias is neither."""
    # bounded two-program dispatch (shared vs per-lane bias), both
    # variants precompiled by the serve engine's program grid — not an
    # unbounded per-shape specialization
    if bias.shape[0] == 1:  # ddl-lint: disable=recompile-shape-branch
        return pl.BlockSpec((1, 1, bl), lambda i, j: (0, 0, j))
    if bias.shape[0] != b:
        raise ValueError(
            f"bias batch dim {bias.shape[0]} must be 1 (shared) or match "
            f"the query batch {b} (per-lane)"
        )
    return pl.BlockSpec((1, 1, bl), lambda i, j: (i, 0, j))


def pick_block_l(L: int, fused: int) -> int | None:
    """Legal sequence tile for a cache of L rows and ``fused`` feature
    width, or None when the kernel cannot tile this shape.

    A tile must be a 128-multiple divisor of L (Mosaic lane/sublane
    alignment — a partial block's dims must be aligned unless they equal
    the full array dims), sized so the K/V tile fits the per-stage VMEM
    budget; rows are costed at bf16 width regardless of cache dtype
    (the int8 kernel's f32 dequant slices eat the byte savings — an
    unclamped int8 tile both neared the compile-probed scoped-VMEM
    boundary and measured SLOWER).  When no aligned divisor exists
    (e.g. L=3000), a single full-L tile is always alignment-legal and
    is used if it fits _TILE_BYTES — the same per-tile envelope the
    probe validated; Mosaic double-buffers both K and V tiles plus the
    f32 per-head slices, so admitting a larger "relaxed" tile here can
    blow the ~16 MB scoped VMEM and fail at runtime.  Above the budget,
    return None and the caller keeps the XLA einsum path."""
    limit = min(
        _MAX_AUTO_BLOCK_L,
        max(_MIN_BLOCK_L, (_TILE_BYTES // max(fused * 2, 1) // 512) * 512),
    )
    if L <= limit:
        return L  # single tile: block dims == array dims, always legal
    for bl in range(limit - limit % 128, 0, -128):
        if L % bl == 0:
            return bl
    if L * fused * 2 <= _TILE_BYTES:
        return L
    return None


def _block_l(
    L: int, block_l: int | None, fused: int, itemsize: int,
    interpret: bool = False,
) -> int:
    del itemsize  # rows costed at bf16 width (see pick_block_l)
    if block_l is not None:
        if block_l >= L:
            return L  # full array: block dims == array dims, always legal
        if interpret:
            # the interpreter has no alignment rules; tests use tiny
            # tiles to exercise the multi-tile accumulator path
            bl = block_l
            while L % bl:
                bl -= 1
            return bl
        # partial tiles must be 128-multiple divisors of L (the Mosaic
        # lane/sublane alignment rule the module docstring states) —
        # step down in 128s rather than hand Mosaic an unaligned tile
        # (e.g. L=1000, block_l=512 must not land on 500)
        for bl in range(block_l - block_l % 128, 0, -128):
            if L % bl == 0:
                return bl
        raise ValueError(
            f"block_l={block_l} has no 128-multiple divisor of L={L} at "
            "or below it; pass a 128-multiple divisor of L, block_l >= L "
            "(single tile), or block_l=None to auto-pick"
        )
    bl = pick_block_l(L, fused)
    if bl is None:
        raise ValueError(
            f"no legal sequence tile for L={L}, fused width {fused}; "
            "gate on pick_block_l() before selecting the kernel, or "
            "pass block_l explicitly"
        )
    return bl


@functools.partial(
    jax.jit, static_argnames=("hkv", "block_l", "interpret")
)
def decode_attention(q, ck, cv, bias, *, hkv: int, block_l=None,
                     interpret=None):
    """q: (B, 1, H, D); ck/cv: (B, L, Hkv*Dh) bf16 fused cache;
    bias: (1, L) f32 additive mask shared across the batch, or (B, L)
    per-lane — continuous-batching decode (``ddl_tpu/serve/``) attends a
    gathered block-table cache where every lane sits at its own length,
    so each batch row carries its own mask.  Returns (B, 1, H, D)."""
    b, _, h, d = q.shape
    L = ck.shape[1]
    if interpret is None:
        interpret = interpret_default()
    bl = _block_l(L, block_l, hkv * d, ck.dtype.itemsize, interpret)
    out = pl.pallas_call(
        functools.partial(_kernel, hkv=hkv, scale=1.0 / (d ** 0.5)),
        grid=(b, L // bl),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bl, hkv * d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bl, hkv * d), lambda i, j: (i, j, 0)),
            _bias_spec(bias, b, bl),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attn",
    )(q[:, 0], ck, cv, bias[:, None])
    return out[:, None]


@functools.partial(
    jax.jit, static_argnames=("hkv", "block_l", "interpret")
)
def quant_decode_attention(q, ck, ks, cv, vs, bias, *, hkv: int,
                           block_l=None, interpret=None):
    """q: (B, 1, H, D); ck/cv: (B, L, Hkv*Dh) int8 fused cache;
    ks/vs: (B, Hkv, L) f32 per-(token, head) scales (L minor, so the
    kernel reads an aligned (block_l,) lane vector per head);
    bias: (1, L) f32 additive mask, or (B, L) per-lane (see
    ``decode_attention``)."""
    b, _, h, d = q.shape
    L = ck.shape[1]
    if interpret is None:
        interpret = interpret_default()
    bl = _block_l(L, block_l, hkv * d, ck.dtype.itemsize, interpret)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, hkv=hkv, scale=1.0 / (d ** 0.5)),
        grid=(b, L // bl),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bl, hkv * d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bl, hkv * d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, hkv, bl), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, hkv, bl), lambda i, j: (i, 0, j)),
            _bias_spec(bias, b, bl),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attn_int8",
    )(q[:, 0], ck, cv, ks, vs, bias[:, None])
    return out[:, None]
