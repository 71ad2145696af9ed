"""Per-op device-time breakdown of the LM train step (dense or MoE MLP).

Same evidence channel as ``profile_densenet`` (PERF.md round 4), pointed
at the transformer family: where does an LM/MoE step's device time go —
matmul fusions, the Pallas attention custom call, MoE dispatch
sort/gather or one-hot einsums, collectives, optimizer?

Usage::

    python -m ddl_tpu.bench.profile_lm [--batch 16] [--experts 8] \
        [--d-ff 1536] [--flash] [--no-remat]

Prints a per-category table, the top-N ops, and one JSON line.
"""

from __future__ import annotations

import argparse
import tempfile


def capture(args, trace_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_steps import make_lm_step_fns
    from ddl_tpu.utils.compile_cache import activate_compile_cache
    from ddl_tpu.utils.timing import fence

    activate_compile_cache()
    cfg = LMConfig(
        vocab_size=50304,
        d_model=768,
        n_layers=12,
        n_heads=12,
        n_kv_heads=args.kv_heads,
        head_dim=64,
        d_ff=args.d_ff,
        num_experts=args.experts,
        compute_dtype="bfloat16",
        flash=bool(args.flash),
        remat=not args.no_remat,
        ce_chunk=args.ce_chunk,
        ce_vocab_chunk=args.ce_vocab_chunk,
    )
    import optax

    fns = make_lm_step_fns(
        cfg, LMMeshSpec(), optax.adamw(3e-4), jax.random.key(0),
        args.batch, args.seq_len,
    )
    state = fns.init_state()
    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.seq_len + 1)),
        jnp.int32,
    )
    inp, tgt = toks[:, :-1], toks[:, 1:]
    for _ in range(3):  # compile + steady
        state, metrics = fns.train(state, inp, tgt)
    fence(metrics["loss"])

    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        state, metrics = fns.train(state, inp, tgt)
    fence(metrics["loss"])
    jax.profiler.stop_trace()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--experts", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--ce-vocab-chunk", type=int, default=0)
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="reuse an existing trace instead of capturing")
    args = ap.parse_args()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="lm_prof_")
    if not args.trace_dir:
        capture(args, trace_dir)

    from ddl_tpu.bench.xprof import print_report

    print_report(
        trace_dir, args.steps, args.top,
        header=(f", batch {args.batch}, T {args.seq_len}, "
                f"experts {args.experts}"),
    )


if __name__ == "__main__":
    main()
