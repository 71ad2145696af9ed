"""Vision Transformer — a second image-classification family.

The reference supports exactly one vision model (torchvision DenseNet121,
``single.py:297-299``).  This family shows the framework's transformer
stack is model-agnostic: the same ``Block`` modules that power the LM
(``models/transformer.py`` — TP over heads/MLP via the logical-axis rule
table, FSDP, remat) run *bidirectionally* (``LMConfig.causal=False``) over
a patch sequence, with a learned positional embedding and a mean-pool
classifier head.  It trains on the same APTOS-shape data path as the CNN
(224x224x3 uint8 in, 5 classes out) — see ``examples/train_vit.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ddl_tpu.models.transformer import LMConfig, RMSNorm, remat_block

__all__ = ["ViTConfig", "ViT", "make_patch_embed", "make_vit_head"]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 5  # APTOS diabetic-retinopathy grades
    d_model: int = 384
    n_layers: int = 12
    n_heads: int = 6
    # grouped-query attention (0 = multi-head); see LMConfig.n_kv_heads
    n_kv_heads: int = 0
    head_dim: int = 64
    d_ff: int = 1536
    compute_dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"  # see LMConfig.remat_policy
    fsdp: bool = False
    dropout_rate: float = 0.0  # residual dropout inside the blocks

    @property
    def num_patches(self) -> int:
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image_size {self.image_size} % patch_size "
                f"{self.patch_size} != 0"
            )
        return (self.image_size // self.patch_size) ** 2

    def block_config(self) -> LMConfig:
        """The encoder blocks, expressed as a bidirectional LMConfig so the
        LM's Block/sharding machinery is reused unchanged."""
        return LMConfig(
            vocab_size=1,  # unused (no token embedding)
            d_model=self.d_model,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            d_ff=self.d_ff,
            compute_dtype=self.compute_dtype,
            remat=self.remat,
            remat_policy=self.remat_policy,
            fsdp=self.fsdp,
            causal=False,
            dropout_rate=self.dropout_rate,
        )

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


def make_patch_embed(cfg: ViTConfig) -> nn.Conv:
    """The patchify conv ('patch_embed' in the param tree): stride = kernel
    = patch, i.e. one MXU matmul per patch.  Single source of truth shared
    by ``ViT`` and the pipeline path (``train/vit_steps.py``), so the two
    forward implementations cannot drift."""
    return nn.Conv(
        cfg.d_model,
        (cfg.patch_size, cfg.patch_size),
        strides=(cfg.patch_size, cfg.patch_size),
        dtype=cfg.dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (None, None, None, "embed")
        ),
        name="patch_embed",
    )


def make_vit_head(cfg: ViTConfig) -> nn.Dense:
    """The classifier head ('head'); f32 so the loss-side softmax is f32.
    Shared by ``ViT`` and the pipeline path."""
    return nn.Dense(
        cfg.num_classes,
        use_bias=True,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", None)
        ),
        name="head",
    )


class ViT(nn.Module):
    """images (B, H, W, 3) float -> logits (B, num_classes) f32."""

    cfg: ViTConfig
    attn_core: Optional[callable] = None

    @nn.compact
    def __call__(self, images, deterministic: bool = True):
        cfg = self.cfg
        bc = cfg.block_config()
        b = images.shape[0]
        x = make_patch_embed(cfg)(images.astype(cfg.dtype))
        x = x.reshape(b, cfg.num_patches, cfg.d_model)
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, None, "embed")
            ),
            (1, cfg.num_patches, cfg.d_model),
            jnp.float32,
        )
        x = x + pos.astype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        block = remat_block(bc)
        for i in range(cfg.n_layers):
            x, _aux = block(bc, self.attn_core, name=f"block{i}")(
                x, None, deterministic
            )
        x = RMSNorm(cfg.dtype, name="norm_f")(x)
        x = x.mean(axis=1)  # mean-pool over patches
        return make_vit_head(cfg)(x.astype(jnp.float32))
