"""ViT training throughput benchmark (PERF.md's ViT row).

    python -m ddl_tpu.bench.vit                 # ViT-S/16, 224px, batch 64
    python -m ddl_tpu.bench.vit --no-remat

True-fenced steady-state timing of the full train step (uint8 normalize +
fwd + bwd + AdamW) on the current default backend, same data shapes as the
DenseNet headline bench (bench.py).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddl_tpu.models.transformer import REMAT_POLICIES
from ddl_tpu.models.vit import ViTConfig
from ddl_tpu.parallel.sharding import LMMeshSpec
from ddl_tpu.train.vit_steps import make_vit_step_fns
from ddl_tpu.utils.timing import fence


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--patch", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--remat-policy", default="full",
                    choices=list(REMAT_POLICIES))
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    cfg = ViTConfig(
        image_size=args.image_size,
        patch_size=args.patch,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.d_model // 64,
        head_dim=64,
        d_ff=4 * args.d_model,
        compute_dtype="bfloat16",
        remat=not args.no_remat,
        remat_policy=args.remat_policy,
    )
    fns = make_vit_step_fns(
        cfg, LMMeshSpec(), optax.adamw(3e-4), jax.random.key(0), args.batch
    )
    state = fns.init_state()
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(
        rng.integers(0, 255, (args.batch, args.image_size, args.image_size, 3))
        .astype(np.uint8)
    )
    labels = jnp.asarray(rng.integers(0, 5, (args.batch,)).astype(np.int32))
    for _ in range(3):
        state, m = fns.train(state, imgs, labels)
    fence(m["loss"])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        state, m = fns.train(state, imgs, labels)
    fence(m["loss"])
    dt = (time.perf_counter() - t0) / args.iters
    out = {
        "ms_per_step": round(dt * 1e3, 1),
        "images_per_sec": round(args.batch / dt),
        "batch": args.batch,
        "remat": "off" if args.no_remat else args.remat_policy,
        "loss": round(float(m["loss"]), 3),
    }
    from ddl_tpu.bench.mfu import append_mfu

    append_mfu(out, fns.train, dt, state, imgs, labels,
               key="mfu" if args.no_remat else "hfu")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
