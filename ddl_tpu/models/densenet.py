"""DenseNet in Flax Linen, built as a sequence of pipeline-splittable stages.

TPU-native re-design of the reference model — torchvision ``densenet121`` with
its 1000-way classifier swapped for a 5-class head (reference
``single.py:297-299``).  Architecture (Huang et al. 2017, densenet121 config):
stem Conv7x7/2 + BN + ReLU + MaxPool3x3/2; four dense blocks of (6,12,24,16)
bottleneck layers (BN-ReLU-Conv1x1(4k) -> BN-ReLU-Conv3x3(k), growth k=32)
with channel-halving transitions between them; final BN-ReLU, global average
pool, linear head.  Layout is NHWC (TPU-native; channels-last feeds the MXU's
128-lane dimension), params are float32 with a configurable compute dtype
(bfloat16 on TPU).

Pipeline staging: instead of FX-tracing and splitting a monolithic module the
way ``torch.distributed.pipelining`` does (reference ``pp.py:380-386``), the
model is *constructed* as N ``DenseNetStage`` modules cut at dense-block
boundaries.  The reference's split spec "features.denseblock3.denselayer1
BEGINNING" (``pp.py:384``) is ``split_blocks=(2,)``.  Block-boundary splits are
also what the reference found to be the only safe cut points — mid-block
splits break on DenseNet's concatenative skip connections (``debug.py:9-18``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddl_tpu.config import ModelConfig

__all__ = [
    "DenseNetStage",
    "FusedDenseBlock",
    "StageSpec",
    "build_stages",
    "init_stages",
    "apply_stage",
    "forward_stages",
    "stage_boundary_shapes",
    "count_params",
]

# torch BatchNorm2d defaults: momentum=0.1 (EMA keep-rate 0.9), eps=1e-5.
_BN_MOMENTUM = 0.9
_BN_EPS = 1e-5

# torchvision DenseNet initialises convs with kaiming_normal_ (he-normal).
_conv_init = nn.initializers.he_normal()

# Feature-pack width for dense_block_impl="packed": the TPU lane width.
# bf16 tensors tile as (sublane, 128-lane) in HBM, so a 32-channel growth
# strip stored alone wastes 3/4 of every tile; packing strips into
# 128-channel groups keeps every stored feature tensor lane-aligned.
_PACK = 128


def _batch_stats(x) -> tuple[jax.Array, jax.Array]:
    """Per-channel batch mean/var, Flax-BatchNorm style: float32, fast
    variance (E[x^2] - E[x]^2), clipped at zero."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=tuple(range(x.ndim - 1)))
    var = jnp.maximum(
        jnp.mean(xf * xf, axis=tuple(range(x.ndim - 1))) - mu * mu, 0.0
    )
    return mu, var


def _affine_relu(x, mu, var, scale, bias, dtype):
    """BatchNorm-then-ReLU with precomputed stats, folded to one affine:
    relu((x - mu) * rsqrt(var+eps) * scale + bias) in f32, cast to dtype
    (the same promotion/cast order as Flax ``_normalize``)."""
    a = jax.lax.rsqrt(var + _BN_EPS) * scale
    b = bias - mu * a
    return nn.relu(x.astype(jnp.float32) * a + b).astype(dtype)


class _BNParams(nn.Module):
    """Declares exactly Flax ``BatchNorm``'s param/variable tree (scale,
    bias params; batch_stats mean/var) without applying it — the packed
    dense block computes statistics once per feature pack and applies the
    normalization as per-pack affines, but must keep the checkpoint tree
    bit-identical to the concat form's ``nn.BatchNorm``."""

    features: int

    @nn.compact
    def __call__(self):
        scale = self.param(
            "scale", nn.initializers.ones_init(), (self.features,),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            jnp.float32,
        )
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), (self.features,),
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), (self.features,),
        )
        return scale, bias, ra_mean, ra_var


class _ConvKernel(nn.Module):
    """Declares exactly ``nn.Conv``'s 1x1 kernel (same name, shape, init
    stream) without applying it; the packed path contracts slices of it
    against individual feature packs."""

    in_features: int
    out_features: int

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", _conv_init,
            (1, 1, self.in_features, self.out_features), jnp.float32,
        )


class _Conv3x3Kernel(nn.Module):
    """Declares exactly ``nn.Conv``'s 3x3 kernel (same name, shape, init
    stream) without applying it; the fused block's Pallas kernel runs the
    conv itself as nine shifted matmuls."""

    in_features: int
    out_features: int

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", _conv_init,
            (3, 3, self.in_features, self.out_features), jnp.float32,
        )


def _packed_norm_relu_conv1x1(
    module, packs, pack_stats, train, scale, bias, ra_mean, ra_var,
    kernel, dtype,
):
    """The packed-block hot path: BN+ReLU+Conv1x1 over an implicit concat.

    Instead of materialising ``concatenate(packs)`` (the O(L^2)
    channel-copies the profile shows costing ~20% of the headline step),
    contract each lane-aligned pack against its slice of the 1x1 kernel
    and sum the partial products in f32 — algebraically the same matmul,
    zero concat traffic.  Batch statistics are *shared*: the batch
    mean/var of a pack is the same for every consuming layer, so stats
    are computed once at pack creation (``pack_stats``) and each consumer
    only applies its own affine (in eval mode, its own running stats).
    Running averages update from the concatenated pack stats — the exact
    values the concat form would compute.
    """
    if train:
        mu_all = jnp.concatenate([s[0] for s in pack_stats])
        var_all = jnp.concatenate([s[1] for s in pack_stats])
        if not module.is_initializing():
            ra_mean.value = (
                _BN_MOMENTUM * ra_mean.value + (1 - _BN_MOMENTUM) * mu_all
            )
            ra_var.value = (
                _BN_MOMENTUM * ra_var.value + (1 - _BN_MOMENTUM) * var_all
            )
    y = None
    off = 0
    for i, p in enumerate(packs):
        w = p.shape[-1]
        if train:
            mu_p, var_p = pack_stats[i]
        else:
            mu_p = ra_mean.value[off:off + w]
            var_p = ra_var.value[off:off + w]
        xn = _affine_relu(
            p, mu_p, var_p, scale[off:off + w], bias[off:off + w], dtype
        )
        # partial sums accumulate across packs in f32 when computing in
        # f32, in the compute dtype otherwise (a bf16 partial write is
        # half the HBM traffic; each pack's own contraction still
        # accumulates in f32 inside the MXU)
        part = jnp.einsum(
            "bhwc,co->bhwo", xn, kernel[0, 0, off:off + w].astype(dtype),
            preferred_element_type=jnp.promote_types(dtype, jnp.bfloat16),
        )
        y = part if y is None else y + part
        off += w
    return y.astype(dtype)


def _append_pack(packs, stats, h, h_stats):
    """Append a growth strip to the pack list, merging into the open
    (sub-128-lane) tail pack so every closed pack stays lane-aligned."""
    if packs and packs[-1].shape[-1] + h.shape[-1] <= _PACK:
        packs = packs[:-1] + [jnp.concatenate([packs[-1], h], axis=-1)]
        if stats is not None:
            m, v = stats[-1]
            stats = stats[:-1] + [
                (jnp.concatenate([m, h_stats[0]]),
                 jnp.concatenate([v, h_stats[1]]))
            ]
        return packs, stats
    packs = packs + [h]
    if stats is not None:
        stats = stats + [h_stats]
    return packs, stats


def _split_packs(x, train):
    """Split a dense (B,H,W,C) tensor into lane-width packs (+ stats)."""
    c = x.shape[-1]
    packs = [
        jax.lax.slice_in_dim(x, o, min(o + _PACK, c), axis=3)
        for o in range(0, c, _PACK)
    ]
    stats = [_batch_stats(p) for p in packs] if train else None
    return packs, stats


class PackedDenseLayer(nn.Module):
    """Bottleneck layer over an implicit-concat pack list.  Identical
    parameter/batch-stats tree to ``DenseLayer`` (norm1/conv1/norm2/conv2);
    returns only the new ``growth_rate`` strip."""

    growth_rate: int
    bn_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, packs, pack_stats, train: bool):
        c_in = sum(p.shape[-1] for p in packs)
        scale, bias, ra_mean, ra_var = _BNParams(c_in, name="norm1")()
        kernel = _ConvKernel(
            c_in, self.bn_size * self.growth_rate, name="conv1"
        )()
        h = _packed_norm_relu_conv1x1(
            self, packs, pack_stats, train, scale, bias, ra_mean, ra_var,
            kernel, self.dtype,
        )
        h = _bn(self.dtype, "norm2")(h, use_running_average=not train)
        h = nn.relu(h)
        h = nn.Conv(
            self.growth_rate,
            (3, 3),
            padding=1,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=_conv_init,
            name="conv2",
        )(h)
        return h


class PackedDenseBlock(nn.Module):
    """Dense block over lane-aligned feature packs (impl="packed"):
    no per-layer concat, per-pack stats computed once.  Takes and
    returns (packs, stats) so transitions can stay in packed form."""

    num_layers: int
    growth_rate: int
    bn_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, packs, stats, train: bool):
        for i in range(self.num_layers):
            h = PackedDenseLayer(
                self.growth_rate, self.bn_size, self.dtype,
                name=f"denselayer{i + 1}",
            )(packs, stats, train)
            h_stats = _batch_stats(h) if train else None
            packs, stats = _append_pack(packs, stats, h, h_stats)
        return packs, stats


class PackedTransition(nn.Module):
    """Transition over packs: the BN-ReLU-Conv1x1 decomposes per pack the
    same way, so the block's full concat never materialises; the halved
    output is dense (and re-split by the next block)."""

    num_output_features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, packs, stats, train: bool):
        c_in = sum(p.shape[-1] for p in packs)
        scale, bias, ra_mean, ra_var = _BNParams(c_in, name="norm")()
        kernel = _ConvKernel(
            c_in, self.num_output_features, name="conv"
        )()
        x = _packed_norm_relu_conv1x1(
            self, packs, stats, train, scale, bias, ra_mean, ra_var,
            kernel, self.dtype,
        )
        return nn.avg_pool(x, (2, 2), strides=(2, 2))


class _FusedLayerDecl(nn.Module):
    """Declares one dense layer's full param/variable tree (norm1/conv1/
    norm2/conv2 — bit-identical names, shapes, and init streams to
    ``DenseLayer``/``PackedDenseLayer``) without applying anything; the
    fused block folds and runs them through the Pallas kernel."""

    c_in: int
    bn_features: int
    growth_rate: int

    @nn.compact
    def __call__(self):
        s1, b1, ra1m, ra1v = _BNParams(self.c_in, name="norm1")()
        k1 = _ConvKernel(self.c_in, self.bn_features, name="conv1")()
        s2, b2, ra2m, ra2v = _BNParams(self.bn_features, name="norm2")()
        k2 = _Conv3x3Kernel(
            self.bn_features, self.growth_rate, name="conv2"
        )()
        params = {
            "norm1": {"scale": s1, "bias": b1},
            "conv1": {"kernel": k1},
            "norm2": {"scale": s2, "bias": b2},
            "conv2": {"kernel": k2},
        }
        return params, (ra1m, ra1v), (ra2m, ra2v)


def _fused_stats_pass(x, layer_params, growth: int, dtype):
    """Phase one of the fused block's two-phase train-mode BN: the
    cross-image batch-statistics pass.

    A per-image kernel cannot reduce across the batch between layers, so
    the block's statistics are computed ONCE here in plain (traced,
    differentiable) JAX — a concat-form forward whose only products are
    the per-layer ``(mean, var)`` pairs: the full-prefix stats each
    norm1 consumes and the bottleneck stats each norm2 consumes.  Folded
    into affines (``ops/fused_dense_block.pack_affines``) they are
    exactly what the kernel consumes, so the kernel stays per-image
    while BN stays batch-correct; because this pass is ordinary JAX, the
    gradient through the statistics (the BN batch-correction terms) is
    exact by the chain rule — the kernel's custom VJP only owns the
    affine-constant part.

    Returns ``(norm1_stats, norm2_stats, strip_stats)`` where
    ``strip_stats`` drive the running-average updates exactly as the
    packed form's pack-creation stats do."""
    prefix_stats = [_batch_stats(x)]
    norm1_stats, norm2_stats = [], []
    feats = x
    for p in layer_params:
        mu = jnp.concatenate([s[0] for s in prefix_stats])
        var = jnp.concatenate([s[1] for s in prefix_stats])
        norm1_stats.append((mu, var))
        h = _affine_relu(
            feats, mu, var, p["norm1"]["scale"], p["norm1"]["bias"], dtype
        )
        y1 = jnp.einsum(
            "bhwc,co->bhwo", h, p["conv1"]["kernel"][0, 0].astype(dtype),
            preferred_element_type=jnp.float32,
        )
        mu2, var2 = _batch_stats(y1)
        norm2_stats.append((mu2, var2))
        h2 = _affine_relu(
            y1, mu2, var2, p["norm2"]["scale"], p["norm2"]["bias"], dtype
        )
        strip = jax.lax.conv_general_dilated(
            h2, p["conv2"]["kernel"].astype(dtype), (1, 1),
            ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        prefix_stats.append(_batch_stats(strip))
        feats = jnp.concatenate([feats, strip.astype(feats.dtype)], axis=-1)
    return norm1_stats, norm2_stats, prefix_stats[1:]


class FusedDenseBlock(nn.Module):
    """Dense block on the VMEM-resident Pallas kernel
    (``ops/fused_dense_block``), selected per block by
    ``dense_block_impl="fused"`` + ``dense_block_fused_blocks``.

    Identical parameter/batch-stats tree to the concat/packed forms
    (checkpoints interoperate, init draws are seed-identical).  Takes
    and returns a dense (B, H, W, C) tensor.  Eval folds the layers'
    running stats into the kernel's affines; train runs the two-phase
    scheme (``_fused_stats_pass`` for batch stats, then the per-image
    kernel) and updates running averages from the same strip/bottleneck
    stats the packed form would compute.  The backward is the kernel's
    ``jax.custom_vjp`` pair; gradients through the batch statistics flow
    through the stats pass + fold, so train-mode gradients match the
    packed reference exactly."""

    num_layers: int
    growth_rate: int
    bn_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        from ddl_tpu.ops.fused_dense_block import (
            block_pad,
            fused_dense_block,
            pack_affines,
        )

        c0 = x.shape[-1]
        g = self.growth_rate
        layer_params, norm1_ra, norm2_ra = [], [], []
        for i in range(self.num_layers):
            p, ra1, ra2 = _FusedLayerDecl(
                c0 + i * g, self.bn_size * g, g,
                name=f"denselayer{i + 1}",
            )()
            layer_params.append(p)
            norm1_ra.append(ra1)
            norm2_ra.append(ra2)
        if train:
            norm1_stats, norm2_stats, strip_stats = _fused_stats_pass(
                x, layer_params, g, self.dtype
            )
            if not self.is_initializing():
                for i in range(self.num_layers):
                    ra1m, ra1v = norm1_ra[i]
                    ra1m.value = (
                        _BN_MOMENTUM * ra1m.value
                        + (1 - _BN_MOMENTUM) * norm1_stats[i][0]
                    )
                    ra1v.value = (
                        _BN_MOMENTUM * ra1v.value
                        + (1 - _BN_MOMENTUM) * norm1_stats[i][1]
                    )
                    ra2m, ra2v = norm2_ra[i]
                    ra2m.value = (
                        _BN_MOMENTUM * ra2m.value
                        + (1 - _BN_MOMENTUM) * norm2_stats[i][0]
                    )
                    ra2v.value = (
                        _BN_MOMENTUM * ra2v.value
                        + (1 - _BN_MOMENTUM) * norm2_stats[i][1]
                    )
        else:
            norm1_stats = [(m.value, v.value) for m, v in norm1_ra]
            norm2_stats = [(m.value, v.value) for m, v in norm2_ra]
        packed = pack_affines(layer_params, norm1_stats, norm2_stats, c0, g)
        out = fused_dense_block(x.astype(self.dtype), packed, c0=c0, growth=g)
        pad0, _ = block_pad(c0, self.num_layers, g)
        return out[..., pad0:pad0 + c0 + self.num_layers * g]


def _bn(dtype, name: str):
    return nn.BatchNorm(
        momentum=_BN_MOMENTUM,
        epsilon=_BN_EPS,
        dtype=dtype,
        param_dtype=jnp.float32,
        name=name,
    )


class DenseLayer(nn.Module):
    """Bottleneck layer: BN-ReLU-Conv1x1(bn_size*k) -> BN-ReLU-Conv3x3(k),
    concatenated onto its input."""

    growth_rate: int
    bn_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        h = _bn(self.dtype, "norm1")(x, use_running_average=not train)
        h = nn.relu(h)
        h = nn.Conv(
            self.bn_size * self.growth_rate,
            (1, 1),
            use_bias=False,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=_conv_init,
            name="conv1",
        )(h)
        h = _bn(self.dtype, "norm2")(h, use_running_average=not train)
        h = nn.relu(h)
        h = nn.Conv(
            self.growth_rate,
            (3, 3),
            padding=1,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=_conv_init,
            name="conv2",
        )(h)
        return jnp.concatenate([x, h], axis=-1)


class DenseBlock(nn.Module):
    """A run of dense layers in the textbook form (``impl="concat"``):
    every layer concatenates its 32 new channels onto the running
    features, copying all C prior channels per layer (O(L^2)
    channel-writes per block).  The tests' reference for the packed and
    fused blocks, which share its parameter tree.  (A preallocated
    feature buffer written strip by strip, Pleiss et al. 2017, measured
    ~2x slower under XLA and is gone: PERF_HISTORY.md, 'DenseNet
    dense-block memory'.)
    """

    num_layers: int
    growth_rate: int
    bn_size: int
    dtype: Any = jnp.float32
    impl: str = "concat"

    @nn.compact
    def __call__(self, x, train: bool):
        if self.impl != "concat":
            # "packed"/"fused" route to PackedDenseBlock/FusedDenseBlock
            # in DenseNetStage before DenseBlock is ever constructed, but
            # list them: they are valid config values ("packed" the
            # default)
            raise ValueError(
                f"dense_block_impl must be 'concat', 'packed' or 'fused', "
                f"got {self.impl!r}"
            )
        for i in range(self.num_layers):
            x = DenseLayer(
                self.growth_rate, self.bn_size, self.dtype,
                name=f"denselayer{i + 1}",
            )(x, train)
        return x


class Transition(nn.Module):
    """BN-ReLU-Conv1x1 (channel halving) + 2x2 average pool, stride 2."""

    num_output_features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        x = _bn(self.dtype, "norm")(x, use_running_average=not train)
        x = nn.relu(x)
        x = nn.Conv(
            self.num_output_features,
            (1, 1),
            use_bias=False,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=_conv_init,
            name="conv",
        )(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        return x


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """Which slice of the network a pipeline stage covers: blocks [start, end)."""

    start_block: int
    end_block: int
    has_stem: bool
    has_head: bool
    in_features: int  # channels entering the stage (3 for the stem stage)


class DenseNetStage(nn.Module):
    """One pipeline stage: optional stem, a run of dense blocks (+ their
    trailing transitions), optional final-norm/pool/classifier head."""

    cfg: ModelConfig
    spec: StageSpec

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        num_blocks = len(cfg.block_config)

        if self.spec.has_stem:
            x = nn.Conv(
                cfg.num_init_features,
                (7, 7),
                strides=(2, 2),
                padding=3,
                use_bias=False,
                dtype=dtype,
                param_dtype=jnp.float32,
                kernel_init=_conv_init,
                name="conv0",
            )(x)
            x = _bn(dtype, "norm0")(x, use_running_average=not train)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))

        num_features = _features_entering_block(cfg, self.spec.start_block)
        # "fused" rides the packed machinery for transitions and for the
        # blocks NOT selected by dense_block_fused_blocks (the go/no-go
        # list from the PERF.md round-5 per-block measurement)
        packed = cfg.dense_block_impl in ("packed", "fused")
        for b in range(self.spec.start_block, self.spec.end_block):
            fused_b = (
                cfg.dense_block_impl == "fused"
                and b in tuple(cfg.dense_block_fused_blocks)
            )
            if fused_b:
                x = FusedDenseBlock(
                    num_layers=cfg.block_config[b],
                    growth_rate=cfg.growth_rate,
                    bn_size=cfg.bn_size,
                    dtype=dtype,
                    name=f"denseblock{b + 1}",
                )(x, train)
            elif packed:
                packs, stats = _split_packs(x, train)
                packs, stats = PackedDenseBlock(
                    num_layers=cfg.block_config[b],
                    growth_rate=cfg.growth_rate,
                    bn_size=cfg.bn_size,
                    dtype=dtype,
                    name=f"denseblock{b + 1}",
                )(packs, stats, train)
            else:
                x = DenseBlock(
                    num_layers=cfg.block_config[b],
                    growth_rate=cfg.growth_rate,
                    bn_size=cfg.bn_size,
                    dtype=dtype,
                    impl=cfg.dense_block_impl,
                    name=f"denseblock{b + 1}",
                )(x, train)
            num_features += cfg.block_config[b] * cfg.growth_rate
            if b != num_blocks - 1:
                num_features //= 2
                if packed:
                    if fused_b:
                        # the fused block returns a dense tensor; split it
                        # (and its stats, once) for the packed transition
                        packs, stats = _split_packs(x, train)
                    x = PackedTransition(
                        num_features, dtype, name=f"transition{b + 1}"
                    )(packs, stats, train)
                else:
                    x = Transition(
                        num_features, dtype, name=f"transition{b + 1}"
                    )(x, train)
            elif packed and not fused_b:
                # head (or stage boundary) consumes a dense tensor; one
                # concat per final block, vs one per layer in concat form
                x = jnp.concatenate(packs, axis=-1)

        if self.spec.has_head:
            x = _bn(dtype, "norm5")(x, use_running_average=not train)
            x = nn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(
                cfg.num_classes,
                dtype=dtype,
                param_dtype=jnp.float32,
                name="classifier",
            )(x)
        return x.astype(jnp.float32) if self.spec.has_head else x


def _features_entering_block(cfg: ModelConfig, block: int) -> int:
    """Channel count at the input of dense block ``block``."""
    f = cfg.num_init_features
    for b in range(block):
        f += cfg.block_config[b] * cfg.growth_rate
        f //= 2  # transition after every non-final block
    return f


def build_stages(cfg: ModelConfig, num_stages: int | None = None) -> list[DenseNetStage]:
    """Construct the stage modules.

    ``num_stages=1`` (or ``cfg.split_blocks=()``) yields the whole network as
    one stage (the single-device / pure-DP case); otherwise ``cfg.split_blocks``
    gives the dense blocks that begin stages 1..N-1.
    """
    splits: Tuple[int, ...] = tuple(cfg.split_blocks)
    if num_stages == 1:
        splits = ()
    n_blocks = len(cfg.block_config)
    if any(s <= 0 or s >= n_blocks for s in splits):
        raise ValueError(f"split_blocks {splits} out of range (1..{n_blocks - 1})")
    if list(splits) != sorted(set(splits)):
        raise ValueError(f"split_blocks {splits} must be strictly increasing")
    bounds = [0, *splits, n_blocks]
    stages = []
    for i in range(len(bounds) - 1):
        spec = StageSpec(
            start_block=bounds[i],
            end_block=bounds[i + 1],
            has_stem=(i == 0),
            has_head=(i == len(bounds) - 2),
            in_features=3 if i == 0 else _features_entering_block(cfg, bounds[i]),
        )
        stages.append(DenseNetStage(cfg, spec))
    return stages


def stage_boundary_shapes(cfg: ModelConfig, image_size: int) -> list[tuple[int, int, int]]:
    """(H, W, C) of the activation crossing each stage boundary.

    The spatial size entering block b is image_size / 4 (stem) halved once per
    preceding transition.  These are the ``lax.ppermute`` payload shapes in the
    pipeline schedule.
    """
    stages = build_stages(cfg)
    shapes = []
    for st in stages[1:]:
        b = st.spec.start_block
        hw = image_size // 4 // (2 ** b)
        shapes.append((hw, hw, st.spec.in_features))
    return shapes


def init_stages(
    stages: Sequence[DenseNetStage],
    rng: jax.Array,
    image_size: int,
    batch_size: int = 1,
):
    """Initialise every stage, feeding each the previous stage's output shape.

    Returns ``(params, batch_stats)`` as tuples with one pytree per stage —
    the natural unit for pipeline sharding (each ``pipe`` device owns one
    entry) and for the per-stage checkpoints the reference writes
    (``pp.py:84-90`` keys state by rank).

    The whole initialisation is one jitted program: un-jitted Flax init
    runs the forward eagerly, and DenseNet121's hundreds of ops dispatched
    (and compiled) one-by-one take far longer than the same work as one
    program.
    """

    def _init(rng):
        params, batch_stats = [], []
        x = jnp.zeros((batch_size, image_size, image_size, 3), jnp.float32)
        for stage in stages:
            rng, sub = jax.random.split(rng)
            x, variables = stage.init_with_output(sub, x, train=False)
            params.append(variables["params"])
            batch_stats.append(variables.get("batch_stats", {}))
        return tuple(params), tuple(batch_stats)

    return jax.jit(_init)(rng)


def apply_stage(stage: DenseNetStage, params, batch_stats, x, train: bool):
    """Pure per-stage application. Returns (output, new_batch_stats)."""
    variables = {"params": params, "batch_stats": batch_stats}
    if train:
        y, updated = stage.apply(variables, x, train=True, mutable=["batch_stats"])
        return y, updated["batch_stats"]
    y = stage.apply(variables, x, train=False)
    return y, batch_stats


def forward_stages(stages, params, batch_stats, x, train: bool):
    """Run all stages sequentially (single-device / DP forward).

    Returns (logits, new_batch_stats_tuple).
    """
    new_stats = []
    for stage, p, s in zip(stages, params, batch_stats):
        x, ns = apply_stage(stage, p, s, x, train)
        new_stats.append(ns)
    return x, tuple(new_stats)


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))
