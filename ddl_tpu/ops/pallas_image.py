"""Pallas TPU kernel: fused uint8 -> float /255 image normalization.

The framework ships batches to the device as raw uint8 (4x fewer link bytes
than the reference's host-side float normalize, ``single.py:38-42``); this
kernel performs the convert+scale as a single VMEM-resident pass, one block
per grid step, writing the compute dtype (bfloat16 on TPU) directly.  It is
the Pallas counterpart of ``ddl_tpu.ops.image.normalize_images`` (which XLA
usually fuses into the stem convolution); both paths are numerically
identical and covered by the same test.

Layout note: TPU tiles want a 128-multiple lane dimension, so the NHWC batch
is viewed as (B, H*W*C) — for 224x224x3, F = 150528 = 1176 * 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ddl_tpu.ops.interpret import interpret_default

__all__ = ["pallas_normalize_images"]

_BLOCK_COLS = 1536  # 12 lanes of 128


def _normalize_kernel(in_ref, out_ref):
    # Mosaic has no direct uint8 -> bfloat16 cast: widen through int32
    # and float32 (exact for 0..255), then scale in the output dtype as
    # ops/image.normalize_images does
    inv = jnp.asarray(1.0 / 255.0, out_ref.dtype)
    x = in_ref[:].astype(jnp.int32).astype(jnp.float32)
    out_ref[:] = x.astype(out_ref.dtype) * inv


def pallas_normalize_images(
    images, dtype=jnp.bfloat16, interpret: bool | None = None
):
    """uint8 (B, H, W, C) -> [0,1] float (B, H, W, C) in ``dtype``."""
    if interpret is None:
        interpret = interpret_default()
    b = images.shape[0]
    flat = images.reshape(b, -1)
    f = flat.shape[1]
    block = min(_BLOCK_COLS, f)
    grid = (pl.cdiv(f, block),)

    out = pl.pallas_call(
        _normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((b, f), dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((b, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((b, block), lambda j: (0, j)),
        interpret=interpret,
        name="normalize_images",
    )(flat)
    return out.reshape(images.shape)
