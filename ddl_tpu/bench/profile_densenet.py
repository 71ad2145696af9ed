"""Per-op device-time breakdown of the headline DenseNet121 train step.

Captures a ``jax.profiler`` trace of the bs-30 train step (the
``bench.py`` headline workload) and aggregates XLA-op device time via
the shared ``bench/xprof`` analysis.  This is the evidence channel for
PERF.md's "where do the headline milliseconds go" analysis (VERDICT r3
task 1: profile the headline instead of defending it).  The default
measures the packed impl (the config default since round 4); pass
``--impl concat`` to reproduce the textbook-form table in PERF.md, or
``--impl fused`` for the round-6 trainable Pallas-block path (blocks
per ``ModelConfig.dense_block_fused_blocks``).  The same table renders
from any stored trace with ``ddl_tpu bench digest <trace_dir|latest>``.

Usage::

    python -m ddl_tpu.bench.profile_densenet [--batch 30] [--steps 10]

Prints a per-category table, the top-N individual ops with their HLO
names, and one JSON line with the category split.
"""

from __future__ import annotations

import argparse
import tempfile


def capture(batch: int, steps: int, trace_dir: str, impl: str = "packed"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.config import ModelConfig, TrainConfig
    from ddl_tpu.models import build_stages
    from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
    from ddl_tpu.train.state import create_train_state, make_optimizer
    from ddl_tpu.train.steps import make_dp_step_fns
    from ddl_tpu.utils.compile_cache import activate_compile_cache
    from ddl_tpu.utils.timing import fence

    activate_compile_cache()
    cfg = ModelConfig(compute_dtype="bfloat16", dense_block_impl=impl)
    stages = build_stages(cfg, num_stages=1)
    tx = make_optimizer(TrainConfig())
    state = create_train_state(stages, tx, jax.random.key(0), image_size=224)
    mesh = build_mesh(MeshSpec(1, 1))
    fns = make_dp_step_fns(stages, tx, mesh, jnp.bfloat16)

    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.integers(0, 255, (batch, 224, 224, 3)), jnp.uint8)
    labels = jnp.asarray(rng.integers(0, 5, (batch,)), jnp.int32)

    for _ in range(3):  # compile + steady
        state, loss, _ = fns.train(state, images, labels)
    fence(loss)

    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        state, loss, _ = fns.train(state, images, labels)
    fence(loss)
    jax.profiler.stop_trace()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=30)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--impl", default="packed",
                    choices=("concat", "packed", "fused"))
    ap.add_argument("--trace-dir", default=None,
                    help="reuse an existing trace instead of capturing")
    args = ap.parse_args()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="dn_prof_")
    if not args.trace_dir:
        capture(args.batch, args.steps, trace_dir, args.impl)

    from ddl_tpu.bench.xprof import print_report

    print_report(
        trace_dir, args.steps, args.top,
        header=f", batch {args.batch}, impl {args.impl}",
    )



if __name__ == "__main__":
    main()
