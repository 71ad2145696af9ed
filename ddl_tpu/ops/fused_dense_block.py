"""Pallas kernel: a whole DenseNet dense block, VMEM-resident — trainable.

The round-4 packed rewrite (models/densenet.py) removed the O(L^2)
concat copies; the profile's remaining architecture-mandated traffic is
the **conv input re-reads** — every dense layer re-reads the whole
feature prefix from HBM for its 1x1 conv.  This kernel is the named
next lever (PERF.md rounds 4-6): hold the growing feature map in VMEM
SCRATCH across all L layers of a block, so HBM sees exactly one block
input read, one streamed pass over the layer weights, and one block
output write.

Round 5 built the eval-mode forward and measured it (2.0x standalone,
2.9x on denseblock1, 8.9x on denseblock4 — PERF.md round 5, go verdict);
round 6 makes it trainable:

* **Train-mode BN, two-phase**: batch statistics need a cross-image
  reduction between layers, which a per-image kernel cannot do in one
  pass.  So the train forward runs a *batch-stats pass* first (plain
  JAX, computes every per-strip / per-bottleneck mean+var once per
  block), folds those stats into the same per-layer affine vectors the
  kernel already consumes (``pack_affines``), and then runs the
  per-image kernel.  The kernel stays per-image; BN stays batch-correct.
* **Backward, ``jax.custom_vjp``**: the forward's output IS the block's
  full concatenated feature map, so every layer input is a prefix slice
  of it.  The backward kernel (``_bwd_kernel``) mirrors the forward's
  grid-(B, L) structure with the layer axis reversed: it holds the
  feature-map cotangent in VMEM scratch per image, *recomputes* each
  layer's intermediates (hid, y1, h2) from the resident feature map,
  runs the 3x3 transpose as nine shifted matmuls over a zero halo, and
  accumulates the per-layer weight/affine gradients across images in
  VMEM-resident output blocks (constant index maps — one flush at grid
  end).  The custom-VJP boundary is the *folded affines*: gradients
  through the batch statistics themselves flow through the (plain-JAX,
  differentiable) stats pass + fold outside the kernel, so train-mode
  BN gradients are exact by the chain rule — see
  ``models/densenet.FusedDenseBlock``.

Layout: grid (B, L), L sequential ("arbitrary"); scratch X (H*W, P)
holds the feature map.  Mosaic requires lane-dim stores at 128-aligned
offsets, so the column layout is pack-aligned: the block input sits
FRONT-PADDED to the lane width ([0:pad0] zeros, then C0 channels —
padding done outside the kernel), each growth strip lands in an
open-pack scratch at a STATIC phase offset (`pl.when` on layer%phase),
and full packs flush to X at 128-aligned offsets.  Unwritten columns
are zero and the per-layer affine/kernel tensors are zero-padded to the
same layout, so full-width compute is exact — trading ~2x 1x1-conv MXU
FLOPs (the step has headroom) for the HBM re-reads (it does not).  The
3x3 conv runs as 9 shifted (H*W, bn) @ (bn, growth) matmuls over a zero
halo (jnp.pad — scatter has no Mosaic lowering).

Parity: tests/test_fused_dense_block.py pins forward AND gradients
against the textbook concat / packed XLA forms in interpreter mode and
under jit (the kernel's own growth/pack geometry at growth 32 / pack
128 is exercised on-chip by the PERF.md experiments).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.interpret import interpret_default

__all__ = [
    "block_pad",
    "fused_dense_block",
    "fused_dense_block_eval",
    "pack_affines",
    "pack_block_params",
]

_BN_EPS = 1e-5
_LANE = 128
# The backward keeps a block's whole strip resident in f32 next to the
# recomputed intermediates: DenseNet121 block 1 at 56x56 asks for 42 MB
# of scoped VMEM, past Mosaic's 16 MB default (a v5e core has 128 MiB).
_BWD_VMEM_LIMIT = 96 * 1024 * 1024


def pack_affines(layer_params, norm1_stats, norm2_stats, c0: int,
                 growth: int):
    """Fold per-layer BN params + (mean, var) stats into affine vectors
    and pad every per-layer tensor to the kernel's pack-aligned column
    layout ([0:pad0] zeros, then the features).

    ``layer_params[i]`` is the denselayer{i+1} param subtree (norm1/
    conv1/norm2/conv2); ``norm1_stats[i]`` is the ``(mean, var)`` pair
    for its full ``c0 + i*growth``-channel input, ``norm2_stats[i]`` the
    pair for its bottleneck.  The stats may be running averages (eval)
    or batch statistics from the cross-image stats pass (train) — the
    fold is plain traced JAX either way, so gradients flow through it.
    Returns a dict of arrays with leading layer dim."""
    L = len(layer_params)
    pad0, p_total = block_pad(c0, L, growth)
    a1 = jnp.zeros((L, p_total), jnp.float32)
    b1 = jnp.zeros((L, p_total), jnp.float32)
    w1_list, a2, b2, w2_list = [], [], [], []
    for i, p in enumerate(layer_params):
        lo, hi = pad0, pad0 + c0 + i * growth
        n1, n2 = p["norm1"], p["norm2"]
        mu1, var1 = norm1_stats[i]
        s1 = jax.lax.rsqrt(var1 + _BN_EPS) * n1["scale"]
        a1 = a1.at[i, lo:hi].set(s1)
        b1 = b1.at[i, lo:hi].set(n1["bias"] - mu1 * s1)
        w1 = p["conv1"]["kernel"][0, 0]  # (c_in, bn)
        w1_list.append(
            jnp.zeros((p_total, w1.shape[1]), jnp.float32)
            .at[lo:hi].set(w1)
        )
        mu2, var2 = norm2_stats[i]
        s2 = jax.lax.rsqrt(var2 + _BN_EPS) * n2["scale"]
        a2.append(s2)
        b2.append(n2["bias"] - mu2 * s2)
        w2_list.append(
            p["conv2"]["kernel"].reshape(9, w1.shape[1], growth)
        )
    # unit middle axis: Mosaic needs a block's second-to-last dim to be
    # 8-divisible OR the full array dim; (1, C) blocks of (L, C) are not
    return {
        "a1": a1[:, None],
        "b1": b1[:, None],
        "w1": jnp.stack(w1_list),
        "a2": jnp.stack(a2)[:, None],
        "b2": jnp.stack(b2)[:, None],
        "w2": jnp.stack(w2_list),
    }


def pack_block_params(layer_params, layer_stats, c0: int, growth: int):
    """Eval-mode fold: affines from the layers' *running* stats
    (``layer_stats[i]`` is the denselayer{i+1} batch_stats subtree)."""
    norm1 = [
        (st["norm1"]["mean"], st["norm1"]["var"]) for st in layer_stats
    ]
    norm2 = [
        (st["norm2"]["mean"], st["norm2"]["var"]) for st in layer_stats
    ]
    return pack_affines(layer_params, norm1, norm2, c0, growth)


def block_pad(c0: int, n_layers: int, growth: int) -> tuple[int, int]:
    """(pad0, p_total) of the kernel's pack-aligned column layout —
    static ints derived from the block geometry (shared by
    pack_affines, the kernel wrappers, and callers slicing the padded
    output)."""
    pad0 = (-c0) % _LANE
    p_total = pad0 + c0 + n_layers * growth
    p_total += (-p_total) % _LANE
    return pad0, p_total


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _kernel(
    x0_ref, a1_ref, b1_ref, w1_ref, a2_ref, b2_ref, w2_ref, o_ref,
    x_sc, pack_sc,
    *, h: int, w: int, c0: int, growth: int, pad0: int, dtype,
):
    li = pl.program_id(1)
    nl = pl.num_programs(1)
    s = h * w
    per_pack = _LANE // growth  # strips per lane pack

    @pl.when(li == 0)
    def _():
        x_sc[:] = jnp.zeros_like(x_sc)
        # block input, front-padded to the lane width by the caller
        x_sc[:, : pad0 + c0] = (
            x0_ref[0].reshape(s, pad0 + c0).astype(x_sc.dtype)
        )

    phase = li % per_pack

    @pl.when(phase == 0)
    def _():
        pack_sc[:] = jnp.zeros_like(pack_sc)

    x = x_sc[:].astype(jnp.float32)  # (S, P); cols past prefix are 0
    hid = jnp.maximum(x * a1_ref[0] + b1_ref[0], 0.0)
    y1 = jax.lax.dot_general(
        hid.astype(dtype), w1_ref[0].astype(dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (S, bn)
    h2 = jnp.maximum(y1 * a2_ref[0] + b2_ref[0], 0.0)
    h2 = h2.astype(dtype)
    bn = h2.shape[1]
    # 3x3 conv, padding 1: nine shifted matmuls over a zero halo
    hp = jnp.pad(h2.reshape(h, w, bn), ((1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((s, growth), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            win = hp[dy:dy + h, dx:dx + w].reshape(s, bn)
            acc = acc + jax.lax.dot_general(
                win, w2_ref[0, dy * 3 + dx].astype(dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    # strip -> open pack at a STATIC lane offset (one branch per phase)
    for k in range(per_pack):
        @pl.when(phase == k)
        def _(k=k):
            pack_sc[:, k * growth:(k + 1) * growth] = acc.astype(
                pack_sc.dtype
            )
    # flush the open pack EVERY layer (the next layer reads x_sc, which
    # must include this strip) — a 128-aligned VMEM store, cheap
    pack_idx = (pad0 + c0) // _LANE + li // per_pack
    x_sc[:, pl.dslice(pack_idx * _LANE, _LANE)] = pack_sc[:]

    @pl.when(li == nl - 1)
    def _():
        o_ref[0] = x_sc[:].reshape(h, w, x_sc.shape[1]).astype(o_ref.dtype)


def _forward_call(x0p, a1, b1, w1, a2, b2, w2, *, c0, growth, interpret):
    """The forward pallas_call over pre-padded input and folded affines."""
    b, h, w, _ = x0p.shape
    L = a1.shape[0]
    pad0, p_total = block_pad(c0, L, growth)
    bn = w1.shape[2]
    dtype = x0p.dtype
    kern = functools.partial(
        _kernel, h=h, w=w, c0=c0, growth=growth, pad0=pad0, dtype=dtype,
    )
    return pl.pallas_call(
        kern,
        grid=(b, L),
        in_specs=[
            pl.BlockSpec((1, h, w, pad0 + c0), lambda i, l: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, p_total), lambda i, l: (l, 0, 0)),
            pl.BlockSpec((1, 1, p_total), lambda i, l: (l, 0, 0)),
            pl.BlockSpec((1, p_total, bn), lambda i, l: (l, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, l: (l, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, l: (l, 0, 0)),
            pl.BlockSpec((1, 9, bn, growth), lambda i, l: (l, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, w, p_total), lambda i, l: (i, 0, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w, p_total), dtype),
        scratch_shapes=[
            pltpu.VMEM((h * w, p_total), dtype),
            pltpu.VMEM((h * w, _LANE), dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dense_block_fwd",
    )(x0p, a1, b1, w1, a2, b2, w2)


# ---------------------------------------------------------------------------
# Backward kernel: grid (B, L) with the layer axis REVERSED.
#
# The forward output X is the block's full concatenated feature map, so
# every layer's input is a prefix of it — nothing else needs saving.
# Per image the cotangent dX lives in VMEM scratch; at reverse-layer li
# the accumulated dX at strip li's columns is complete (all consumers of
# that strip — layers > li — were processed first), so the kernel reads
# the strip cotangent, recomputes the layer's intermediates from the
# resident X (full-width with zero-padded affines, exactly like the
# forward: columns past the prefix have a1 == b1 == 0, so hid and dz1
# vanish there), and accumulates:
#   dW2[li]  += shifted(h2)^T @ dstrip           (nine taps)
#   dh2       = nine shifted dstrip @ W2[tap]^T  (the 3x3 transpose)
#   dz2       = dh2 * (z2 > 0);  dA2/dB2 reductions;  dy1 = dz2 * a2
#   dW1[li]  += hid^T @ dy1;  dhid = dy1 @ W1^T
#   dz1       = dhid * (z1 > 0);  dA1/dB1 reductions
#   dX       += dz1 * a1    (zero past the prefix by construction)
# Weight/affine gradients accumulate across images in VMEM-resident
# output blocks (constant index maps: the block is the whole array and
# is flushed once, at grid end).  dX0 flushes per image at li == 0.
# ---------------------------------------------------------------------------


def _bwd_kernel(
    x_ref, g_ref, a1_ref, b1_ref, w1_ref, a2_ref, b2_ref, w2_ref,
    dx0_ref, da1_ref, db1_ref, dw1_ref, da2_ref, db2_ref, dw2_ref,
    dx_sc, strip_sc,
    *, h: int, w: int, c0: int, growth: int, pad0: int, dtype,
):
    i = pl.program_id(0)
    l = pl.program_id(1)
    nl = pl.num_programs(1)
    li = nl - 1 - l  # the layer this grid step differentiates
    s = h * w
    per_pack = _LANE // growth

    @pl.when(jnp.logical_and(i == 0, l == 0))
    def _():  # zero the cross-image parameter-grad accumulators once
        da1_ref[...] = jnp.zeros_like(da1_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        da2_ref[...] = jnp.zeros_like(da2_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)

    @pl.when(l == 0)
    def _():  # this image's output cotangent seeds dX
        dx_sc[:] = g_ref[0].reshape(s, dx_sc.shape[1]).astype(dx_sc.dtype)

    # recompute layer li's intermediates from the resident feature map;
    # full-width is exact: a1/b1/w1 rows past the prefix are zero, so
    # later strips present in X contribute nothing
    x = x_ref[0].reshape(s, dx_sc.shape[1]).astype(jnp.float32)
    a1 = a1_ref[0]
    z1 = x * a1 + b1_ref[0]
    hid = jnp.maximum(z1, 0.0)
    y1 = jax.lax.dot_general(
        hid.astype(dtype), w1_ref[0].astype(dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (S, bn)
    a2 = a2_ref[0]
    z2 = y1 * a2 + b2_ref[0]
    h2 = jnp.maximum(z2, 0.0).astype(dtype)
    bn = h2.shape[1]

    # strip li's accumulated cotangent: complete at this grid step
    pack_idx = (pad0 + c0) // _LANE + li // per_pack
    phase = li % per_pack
    gpack = dx_sc[:, pl.dslice(pack_idx * _LANE, _LANE)]
    for k in range(per_pack):
        @pl.when(phase == k)
        def _(k=k):
            strip_sc[:] = gpack[:, k * growth:(k + 1) * growth].astype(
                strip_sc.dtype
            )
    dstrip = strip_sc[:].astype(jnp.float32)  # (S, growth)

    # 3x3 transpose: nine shifted matmuls over zero halos
    # the halo and its windows stay f32 until after the reshape: Mosaic
    # has no (7, 7, 32) <-> (49, 32) shape cast for a packed bf16 strip
    dsp = jnp.pad(dstrip.reshape(h, w, growth), ((1, 1), (1, 1), (0, 0)))
    h2p = jnp.pad(h2.reshape(h, w, bn), ((1, 1), (1, 1), (0, 0)))
    dh2 = jnp.zeros((s, bn), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            # dL/dh2 gathers each tap's dstrip against the transposed tap
            win_g = dsp[dy:dy + h, dx:dx + w].reshape(s, growth)
            dh2 = dh2 + jax.lax.dot_general(
                win_g.astype(dtype),
                w2_ref[0, (2 - dy) * 3 + (2 - dx)].astype(dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # dW2[tap] += shifted(h2)^T @ dstrip — accumulated straight
            # into the resident output block, one tap per store (an
            # .at[tap].set on a value is a scatter, which Mosaic has no
            # lowering for)
            win_h = h2p[dy:dy + h, dx:dx + w].reshape(s, bn)
            tap = (pl.dslice(li, 1), pl.dslice(dy * 3 + dx, 1))
            dw2_ref[tap] = dw2_ref[tap] + jax.lax.dot_general(
                win_h, dstrip.astype(dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )[None, None]

    dz2 = jnp.where(z2 > 0.0, dh2, 0.0)  # (S, bn)
    da2_ref[pl.dslice(li, 1)] = da2_ref[pl.dslice(li, 1)] + jnp.sum(
        dz2 * y1, axis=0, keepdims=True
    )[None]
    db2_ref[pl.dslice(li, 1)] = db2_ref[pl.dslice(li, 1)] + jnp.sum(
        dz2, axis=0, keepdims=True
    )[None]
    dy1 = dz2 * a2

    cur1 = dw1_ref[pl.dslice(li, 1)]
    dw1_ref[pl.dslice(li, 1)] = cur1 + jax.lax.dot_general(
        hid.astype(dtype), dy1.astype(dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )[None]
    dhid = jax.lax.dot_general(
        dy1.astype(dtype), w1_ref[0].astype(dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (S, P)
    dz1 = jnp.where(z1 > 0.0, dhid, 0.0)  # zero past the prefix (z1==0)
    da1_ref[pl.dslice(li, 1)] = da1_ref[pl.dslice(li, 1)] + jnp.sum(
        dz1 * x, axis=0, keepdims=True
    )[None]
    db1_ref[pl.dslice(li, 1)] = db1_ref[pl.dslice(li, 1)] + jnp.sum(
        dz1, axis=0, keepdims=True
    )[None]
    dx_sc[:] = dx_sc[:] + (dz1 * a1).astype(dx_sc.dtype)

    @pl.when(l == nl - 1)
    def _():  # all layers processed: flush this image's input gradient
        dx0_ref[0] = (
            dx_sc[:, : pad0 + c0]
            .reshape(h, w, pad0 + c0)
            .astype(dx0_ref.dtype)
        )


def _backward_call(out, g, a1, b1, w1, a2, b2, w2, *, c0, growth,
                   interpret):
    b, h, w, p_total = out.shape
    L = a1.shape[0]
    pad0, _ = block_pad(c0, L, growth)
    bn = w1.shape[2]
    dtype = out.dtype
    nl = L
    kern = functools.partial(
        _bwd_kernel, h=h, w=w, c0=c0, growth=growth, pad0=pad0,
        dtype=dtype,
    )
    f32 = jnp.float32
    return pl.pallas_call(
        kern,
        grid=(b, L),
        in_specs=[
            pl.BlockSpec((1, h, w, p_total), lambda i, l: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, w, p_total), lambda i, l: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, p_total), lambda i, l: (nl - 1 - l, 0, 0)),
            pl.BlockSpec((1, 1, p_total), lambda i, l: (nl - 1 - l, 0, 0)),
            pl.BlockSpec(
                (1, p_total, bn), lambda i, l: (nl - 1 - l, 0, 0)
            ),
            pl.BlockSpec((1, 1, bn), lambda i, l: (nl - 1 - l, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, l: (nl - 1 - l, 0, 0)),
            pl.BlockSpec(
                (1, 9, bn, growth), lambda i, l: (nl - 1 - l, 0, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, h, w, pad0 + c0), lambda i, l: (i, 0, 0, 0)
            ),
            pl.BlockSpec((L, 1, p_total), lambda i, l: (0, 0, 0)),
            pl.BlockSpec((L, 1, p_total), lambda i, l: (0, 0, 0)),
            pl.BlockSpec((L, p_total, bn), lambda i, l: (0, 0, 0)),
            pl.BlockSpec((L, 1, bn), lambda i, l: (0, 0, 0)),
            pl.BlockSpec((L, 1, bn), lambda i, l: (0, 0, 0)),
            pl.BlockSpec(
                (L, 9, bn, growth), lambda i, l: (0, 0, 0, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, w, pad0 + c0), dtype),
            jax.ShapeDtypeStruct((L, 1, p_total), f32),
            jax.ShapeDtypeStruct((L, 1, p_total), f32),
            jax.ShapeDtypeStruct((L, p_total, bn), f32),
            jax.ShapeDtypeStruct((L, 1, bn), f32),
            jax.ShapeDtypeStruct((L, 1, bn), f32),
            jax.ShapeDtypeStruct((L, 9, bn, growth), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h * w, p_total), f32),
            pltpu.VMEM((h * w, growth), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="dense_block_bwd",
    )(out, g, a1, b1, w1, a2, b2, w2)


@functools.cache
def _diff_block_fn(c0: int, growth: int, interpret: bool):
    """Per-static-config differentiable block function over the padded
    input and the folded affine tensors.  The custom-VJP boundary treats
    the affines as independent inputs — gradients through the batch
    statistics they were folded from flow through the (plain-JAX) stats
    pass and fold at the caller, so the composition's total gradient is
    exact."""

    @jax.custom_vjp
    def f(x0p, a1, b1, w1, a2, b2, w2):
        return _forward_call(
            x0p, a1, b1, w1, a2, b2, w2,
            c0=c0, growth=growth, interpret=interpret,
        )

    def f_fwd(x0p, a1, b1, w1, a2, b2, w2):
        out = _forward_call(
            x0p, a1, b1, w1, a2, b2, w2,
            c0=c0, growth=growth, interpret=interpret,
        )
        # the output is the full feature map: it alone (plus the folded
        # params) reconstructs every layer input in the backward
        return out, (out, a1, b1, w1, a2, b2, w2)

    def f_bwd(res, g):
        out, a1, b1, w1, a2, b2, w2 = res
        dx0p, da1, db1, dw1, da2, db2, dw2 = _backward_call(
            out, g, a1, b1, w1, a2, b2, w2,
            c0=c0, growth=growth, interpret=interpret,
        )
        return dx0p, da1, db1, dw1, da2, db2, dw2

    f.defvjp(f_fwd, f_bwd)
    return f


def fused_dense_block(x0, packed, *, c0: int, growth: int,
                      interpret=None):
    """Differentiable fused dense block (train or eval affines).

    ``x0``: (B, H, W, C0) block input; ``packed`` from ``pack_affines``
    (batch stats — train) or ``pack_block_params`` (running stats —
    eval).  Returns (B, H, W, pad0 + Cmax [+ tail pad]) — the caller
    slices ``[..., pad0 : pad0 + Cmax]`` for the dense concatenated
    features (kept padded here so every kernel store stays
    lane-aligned).  Differentiable wrt ``x0`` and every packed tensor
    via the paired forward/backward Pallas kernels."""
    L = packed["a1"].shape[0]
    pad0, _ = block_pad(c0, L, growth)
    if _LANE % growth:
        raise ValueError(f"growth {growth} must divide the lane width")
    if interpret is None:
        interpret = interpret_default()
    x0p = jnp.pad(x0, ((0, 0), (0, 0), (0, 0), (pad0, 0)))
    f = _diff_block_fn(c0, growth, bool(interpret))
    return f(
        x0p, packed["a1"], packed["b1"], packed["w1"], packed["a2"],
        packed["b2"], packed["w2"],
    )


@functools.partial(jax.jit, static_argnames=("c0", "growth", "interpret"))
def fused_dense_block_eval(x0, packed, *, c0: int, growth: int,
                           interpret=None):
    """Jitted eval-forward entry point (round-5 experiment surface —
    kept for the standalone benches and parity tests; the in-model path
    is ``fused_dense_block``)."""
    return fused_dense_block(
        x0, packed, c0=c0, growth=growth, interpret=interpret
    )
