"""Generate from a transformer-LM training snapshot (KV-cached decode).

Companion to train_lm.py: point it at the same --checkpoint-dir/--job-id
and the same model flags, and it decodes from the saved weights — any
snapshot layout (a pipeline-parallel run's snapshot is restructured to the
full layout automatically) and any mesh:

    python examples/train_lm.py --cpu-devices 8 --steps 200 \
        --checkpoint-dir /tmp/ck --save-every 100
    python examples/generate_lm.py --cpu-devices 8 --step 200 \
        --checkpoint-dir /tmp/ck --max-new 64

The reference has no generation path at all (its only inference surface is
the loss-less eval schedule, ``pp.py:146-150``); this is part of the
framework's beyond-parity LM family (``ddl_tpu/infer/decode.py``).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint-dir", required=True)
    ap.add_argument("--job-id", default="lm")
    ap.add_argument("--step", type=int, required=True,
                    help="snapshot step to load (any layout/mesh)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1,
                    help="tensor-parallel axis for decode")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="must match the training run's --kv-heads (GQA)")
    ap.add_argument("--attn-window", type=int, default=0,
                    help="must match the training run's --attn-window "
                    "(sliding-window decode reads an O(window) cache slice)")
    ap.add_argument("--experts", type=int, default=0)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--prompt-text", default=None,
                    help="byte-level text prompt (e.g. for --corpus-trained "
                    "models); output is decoded as text")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=None,
                    help="restrict sampling to the k most likely tokens")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", default="none", choices=["none", "kv", "kv+w"],
                    help="int8 serving quantization (ops/quant.py): 'kv' "
                    "stores the KV cache int8 (+per-token scales), 'kv+w' "
                    "also streams weight-only int8 matmul kernels — the "
                    "HBM-traffic levers for the bandwidth-bound decode")
    ap.add_argument("--obs-log-dir", default=None,
                    help="emit per-request decode telemetry (lengths, "
                    "latency, queue delay, TTFT, tokens/s; dispatch/wait "
                    "spans) into this log dir's event stream; inspect "
                    "with `ddl_tpu obs summarize` (p50/p95/p99 table)")
    ap.add_argument("--requests", type=int, default=1,
                    help="decode the prompt batch this many times (the "
                    "first request pays the XLA compile and is flagged "
                    "cold; >= 4 gives the obs percentiles a warm sample)")
    ap.add_argument("--cpu-devices", type=int, default=0)
    args = ap.parse_args()

    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    from ddl_tpu.checkpoint import load_snapshot, snapshot_metadata
    from ddl_tpu.infer import make_lm_generator
    from ddl_tpu.models.transformer import LMConfig
    from ddl_tpu.parallel.lm_pipeline import (
        abstract_lm_state,
        convert_lm_state,
        saved_pipe_stages,
        saved_virtual_stages,
    )
    from ddl_tpu.parallel.sharding import LMMeshSpec, build_lm_mesh

    cfg = LMConfig(
        vocab_size=256,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=8,
        n_kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        head_dim=args.d_model // 8,
        d_ff=4 * args.d_model,
        num_experts=args.experts,
        compute_dtype="bfloat16" if jax.default_backend() != "cpu" else "float32",
        fsdp=args.fsdp,
    )
    spec = LMMeshSpec(data=args.data, model=args.model)
    mesh = build_lm_mesh(spec)

    saved_md = snapshot_metadata(args.checkpoint_dir, args.job_id, args.step)
    saved_pipe = saved_pipe_stages(saved_md["state"]["params"])
    saved_virtual = saved_virtual_stages(saved_md["state"]["params"])
    # Adam's state structure is lr-independent, so any lr builds the right
    # restore skeleton; only params are used for decoding anyway.
    state, _ = load_snapshot(
        args.checkpoint_dir, args.job_id, args.step,
        abstract_lm_state(cfg, optax.adam(1e-3), saved_pipe, mesh=mesh,
                          virtual=saved_virtual),
    )
    if saved_pipe > 1:
        state = convert_lm_state(state)  # pipeline layout -> full
    print(f"loaded step {int(state.step)} (saved pipe={saved_pipe} "
          f"virtual={saved_virtual})")

    if args.int8 == "kv+w":
        from ddl_tpu.ops.quant import quantize_lm_params

        state = state.replace(params=quantize_lm_params(state.params))
    obs = None
    if args.obs_log_dir:
        from ddl_tpu.obs import EventWriter

        obs = EventWriter(args.obs_log_dir, args.job_id)
    gen = make_lm_generator(
        cfg,
        spec,
        prompt_len=args.prompt_len,
        max_new=args.max_new,
        batch=args.batch,
        temperature=args.temperature,
        top_k=args.top_k,
        mesh=mesh,
        kv_quant=args.int8 != "none",
        obs=obs,
    )

    if args.prompt_text is not None:
        enc = args.prompt_text.encode()
        if len(enc) > args.prompt_len:
            print(f"note: keeping the LAST {args.prompt_len} of "
                  f"{len(enc)} prompt bytes (raise --prompt-len to keep all)")
        raw = enc[-args.prompt_len:]  # trailing bytes = continuation context
        raw = raw.rjust(args.prompt_len, b" ")  # left-pad to the fixed shape
        prompts = np.tile(
            np.frombuffer(raw, np.uint8).astype(np.int32), (args.batch, 1)
        )
        toks = np.asarray(gen(state.params, jnp.asarray(prompts),
                              jax.random.key(args.seed)))
        for b in range(args.batch):
            text = bytes(int(t) % 256 for t in toks[b]).decode(errors="replace")
            print(f"{raw.decode(errors='replace')!r} -> {text!r}")
        return

    # default: prompts drawn from the synthetic training corpus's Markov
    # chain (the same seed-0 chain train_lm.py trains on,
    # ddl_tpu.data.synthetic_lm)
    from ddl_tpu.data.synthetic_lm import MarkovChain

    chain = MarkovChain()
    prompts = chain.sample(
        np.random.default_rng(args.seed), args.batch, args.prompt_len
    )

    from time import perf_counter

    for _ in range(max(0, args.requests - 1)):
        # warm serving requests for the percentile accumulators; the
        # submit timestamp exercises the queue-delay field
        gen(state.params, jnp.asarray(prompts),
            jax.random.key(args.seed), submitted_at=perf_counter())
    toks = np.asarray(gen(state.params, jnp.asarray(prompts),
                          jax.random.key(args.seed)))
    # score the continuations under the true chain: fraction of steps that
    # follow a plausible (top-8) transition — random tokens score ~8/256
    follows = chain.on_chain_fraction(prompts, toks)
    for b in range(args.batch):
        print(f"prompt {prompts[b].tolist()} -> {toks[b].tolist()}")
    print(f"fraction of generated steps on a top-8 chain transition: "
          f"{follows:.3f} (random would be ~{8 / 256:.3f})")


if __name__ == "__main__":
    main()
