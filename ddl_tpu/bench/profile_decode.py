"""Per-op device-time breakdown of autoregressive decode (bf16 or int8).

The serving bench (``bench/decode.py``) gives rates; this gives the
*why* — the same xprof evidence channel as ``profile_densenet`` /
``profile_lm``, pointed at the generator's one-program prefill + scan.
Built to answer the int8 question: does the int8→bf16 convert fuse into
the attention/matmul reads (HBM win) or materialise converted copies
(win lost)?

    python -m ddl_tpu.bench.profile_decode --batch 32 --kv-heads 4 \
        --attn-window 1024 --quant kv
"""

from __future__ import annotations

import argparse
import tempfile


def capture(args, trace_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.infer.decode import make_lm_generator
    from ddl_tpu.models.transformer import LMConfig, TransformerLM
    from ddl_tpu.utils.compile_cache import activate_compile_cache
    from ddl_tpu.utils.timing import fence

    activate_compile_cache()
    cfg = LMConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.d_model // 64,
        n_kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        head_dim=64,
        d_ff=4 * args.d_model,
        compute_dtype="bfloat16",
        remat=False,
        flash="auto",
    )
    import flax.linen as nn

    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(0), jnp.zeros((args.batch, 8), jnp.int32)
        )["params"]
    )
    if args.quant == "kv+w":
        from ddl_tpu.ops.quant import quantize_lm_params

        params = quantize_lm_params(params)
    gen = make_lm_generator(
        cfg, prompt_len=args.prompt, max_new=args.new, batch=args.batch,
        kv_quant=args.quant in ("kv", "kv+w"),
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, args.vocab, (args.batch, args.prompt)), jnp.int32
    )
    fence(gen(params, prompt))  # compile + warm
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        out = gen(params, prompt)
    fence(out)
    jax.profiler.stop_trace()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--new", type=int, default=256,
                    help="decode tokens per profiled call")
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--attn-window", type=int, default=0)
    ap.add_argument("--quant", default="none", choices=["none", "kv", "kv+w"])
    ap.add_argument("--steps", type=int, default=3,
                    help="profiled generate() calls")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="dec_prof_")
    if not args.trace_dir:
        capture(args, trace_dir)

    from ddl_tpu.bench.xprof import print_report

    print_report(
        trace_dir, args.steps, args.top,
        header=(f", decode batch {args.batch}, prompt {args.prompt}, "
                f"new {args.new}, quant {args.quant}"),
    )


if __name__ == "__main__":
    main()
