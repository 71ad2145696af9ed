"""Headline benchmark: DenseNet121 training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"platform", "device_kind", "device_count"}.  Refuses any backend other
than ``tpu``: the metric is named for one chip and is never printed for
a CPU run.

Baseline derivation (BASELINE.md): the reference's best single-GPU run
averages 90.77 s/epoch; the preprocessed APTOS train split at batch 30 gives
~97 steps/epoch (2930 images — 80% of the 3662-image APTOS-2019 train set,
the standard preprocessed split; the reference logs epoch_time, not
steps/sec, so step count is derived).  That is 97 / 90.77 = 1.069 train
steps/sec at global batch 30 on the reference's best single GPU.

This bench times the same workload — DenseNet121, 224x224x3 uint8 in,
5-class head, batch 30, full train step (normalize + forward + backward +
Adam) — on one TPU chip in bfloat16 compute, steady-state (post-compile),
with device-resident input batches (host data feed overlaps compute in the
real trainer via the prefetching loader).
"""

from __future__ import annotations

import json
import time

import numpy as np

BASELINE_STEPS_PER_SEC = 97 / 90.77  # best single-GPU reference run


def main() -> None:
    import os

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures one TPU chip; JAX found {device.platform!r} "
            f"({device.device_kind}). Rehearse control flow with the tests, "
            "not with this script."
        )

    # Persistent compile cache: repeated bench runs (and the trainer) skip
    # the DenseNet121 XLA compile.
    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    import jax.numpy as jnp

    from ddl_tpu.config import ModelConfig, TrainConfig
    from ddl_tpu.models import build_stages
    from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
    from ddl_tpu.train.state import create_train_state, make_optimizer
    from ddl_tpu.train.steps import make_dp_step_fns
    from ddl_tpu.utils.timing import fence

    batch = 30
    # DDL_BENCH_IMPL enables same-session A/Bs of the dense-block impls
    # (packed default; "fused" = the round-6 Pallas block) without
    # editing the bench — the knob the gate/PERF.md protocol names.
    cfg = ModelConfig(
        compute_dtype="bfloat16",
        dense_block_impl=os.environ.get("DDL_BENCH_IMPL", "packed"),
    )
    stages = build_stages(cfg, num_stages=1)
    tx = make_optimizer(TrainConfig())
    state = create_train_state(stages, tx, jax.random.key(0), image_size=224)
    mesh = build_mesh(MeshSpec(1, 1))
    fns = make_dp_step_fns(stages, tx, mesh, jnp.bfloat16)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.integers(0, 255, (batch, 224, 224, 3)), jnp.uint8)
    labels = jnp.asarray(rng.integers(0, 5, (batch,)), jnp.int32)

    # warmup: compile + 2 steady steps
    for _ in range(3):
        state, loss, _ = fns.train(state, images, labels)
    fence(loss)

    def timed(n):
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            state, loss, _ = fns.train(state, images, labels)
        fence(loss)
        return time.perf_counter() - t0

    # Each timed run carries a fixed cost (final fence readback + pipeline
    # drain) that a single n/elapsed quote folds into the rate, making it
    # grow with the iteration count.  Timing
    # two run lengths and differencing cancels it — the slope is the true
    # per-step time — and the median of three slopes rides out host
    # contention during any one run.
    iters = int(os.environ.get("DDL_BENCH_ITERS", "50"))
    n1 = max(iters // 5, 2)
    runs = []  # (slope, undifferenced long-run rate)
    for _ in range(5):  # up to 2 retries for contention-corrupted runs
        t_long, t_short = timed(iters), timed(n1)
        s = (t_long - t_short) / (iters - n1)
        if s > 0:
            runs.append((s, iters / t_long))
        if len(runs) == 3:
            break
    if len(runs) < 3:
        raise RuntimeError(
            f"host contention: could not collect 3 positive slopes ({runs})"
        )
    runs.sort()
    slope, undiff = runs[1]
    steps_per_sec = 1.0 / slope
    out = {
        "metric": "densenet121_train_steps_per_sec_bs30_1chip",
        "value": round(steps_per_sec, 4),
        "unit": "steps/sec",
        "vs_baseline": round(steps_per_sec / BASELINE_STEPS_PER_SEC, 4),
        # the plain wall-clock quote of the same median run, fixed fence/
        # drain cost INCLUDED (the reference's epoch_time is this kind of
        # number) — the honest bracket is [undifferenced, slope]
        "value_undifferenced": round(undiff, 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": jax.device_count(),
    }
    # chip utilization: executed FLOPs from XLA cost analysis / peak bf16
    from ddl_tpu.bench.mfu import append_mfu, fused_dense_block_train_flops

    extra = 0.0
    if cfg.dense_block_impl == "fused":
        # cost analysis sees zero FLOPs in a Pallas custom call; restore
        # the fused blocks' work analytically (model convention)
        extra = fused_dense_block_train_flops(
            batch, 224, cfg.block_config, cfg.growth_rate, cfg.bn_size,
            cfg.num_init_features, cfg.dense_block_fused_blocks,
        )
        out["impl"] = cfg.dense_block_impl
    append_mfu(out, fns.train, slope, state, images, labels,
               extra_flops=extra)
    # per-device optimizer-state HBM estimate (rule-table-derived Adam
    # moment bytes, replicated vs ZeRO at the dp=8 reference mesh) —
    # informational column; the gate/baseline headline ignores it
    from ddl_tpu.bench.gate import opt_hbm_rows

    (cnn_row,) = opt_hbm_rows(dp=8, families=("cnn",))
    out["opt_hbm_bytes"] = {
        "replicated": cnn_row["replicated_bytes"],
        "zero": cnn_row["zero_bytes"],
        "dp": cnn_row["dp"],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
