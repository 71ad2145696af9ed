"""LM training throughput benchmark (PERF.md's tokens/sec table).

    python -m ddl_tpu.bench.lm                  # GPT-2-small-ish, T=1024
    python -m ddl_tpu.bench.lm --seq-len 4096 --batch 2 --flash

True-fenced steady-state timing of the full train step (fwd + bwd +
AdamW) on the current default backend.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddl_tpu.models.transformer import LMConfig, REMAT_POLICIES
from ddl_tpu.parallel.sharding import LMMeshSpec
from ddl_tpu.train.lm_steps import make_lm_step_fns
from ddl_tpu.utils.timing import fence


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention K/V head count (0 = MHA)")
    ap.add_argument("--attn-window", type=int, default=0,
                    help="sliding-window attention size (0 = full causal)")
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--flash", nargs="?", const="on", default="off",
                    choices=["on", "off", "auto"])
    ap.add_argument("--remat-policy", default="full",
                    choices=list(REMAT_POLICIES),
                    help="what the per-block checkpoint may save instead of "
                    "recomputing (LMConfig.remat_policy)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ce-vocab-chunk", type=int, default=0,
                    help="vocab-streamed head+CE (losses."
                    "fused_vocab_chunked_ce): vocab-block size, 0 = off")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="chunked head+CE fusion: sequence-chunk size for "
                    "the loss edge (0 = dense CE; the (B,T,V) logits are "
                    "never materialised when set)")
    ap.add_argument("--experts", type=int, default=0,
                    help="top-k MoE blocks with this many experts (0 = "
                    "dense MLP); combine with --d-ff to match active "
                    "FLOPs, e.g. 8 experts top-2 at half d_ff")
    ap.add_argument("--expert-top-k", type=int, default=2)
    ap.add_argument("--capacity-factor", type=float, default=1.5,
                    help="per-expert token capacity = k*S*cf/E; the router "
                    "drops overflow, so cf trades step time against "
                    "moe_drop_frac (watch both in the output)")
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "sort", "einsum"],
                    help="token routing path: one-hot einsum matmuls, "
                    "argsort + permutation gathers, or auto (einsum for "
                    "groups <= 2048 tokens)")
    ap.add_argument("--moe-group", type=int, default=256,
                    help="routing-group size in tokens (capacity is per "
                    "group; smaller groups cut dispatch cost ~linearly, "
                    "0 = whole sequence)")
    ap.add_argument("--d-ff", type=int, default=0,
                    help="MLP/expert hidden size (0 = 4*d_model)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    cfg = LMConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.d_model // 64,
        n_kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        head_dim=64,
        d_ff=args.d_ff or 4 * args.d_model,
        num_experts=args.experts,
        expert_top_k=args.expert_top_k,
        capacity_factor=args.capacity_factor,
        moe_dispatch=args.moe_dispatch,
        moe_group=args.moe_group,
        compute_dtype="bfloat16",
        flash={"on": True, "off": False, "auto": "auto"}[args.flash],
        remat=not args.no_remat,
        remat_policy=args.remat_policy,
        ce_chunk=args.ce_chunk,
        ce_vocab_chunk=args.ce_vocab_chunk,
    )
    # resolve flash="auto" HERE and pass the concrete cfg down, so the
    # reported "flash" field is by construction the path benchmarked
    from ddl_tpu.parallel.sharding import normalize_flash

    cfg = normalize_flash(cfg, LMMeshSpec(), args.seq_len)
    fns = make_lm_step_fns(
        cfg, LMMeshSpec(), optax.adamw(3e-4), jax.random.key(0),
        args.batch, args.seq_len,
    )
    state = fns.init_state()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, args.vocab, (args.batch, args.seq_len + 1))
    )
    inp, tgt = toks[:, :-1], toks[:, 1:]
    for _ in range(3):
        state, m = fns.train(state, inp, tgt)
    fence(m["loss"])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        state, m = fns.train(state, inp, tgt)
    fence(m["loss"])
    dt = (time.perf_counter() - t0) / args.iters
    out = {
        "ms_per_step": round(dt * 1e3, 1),
        "tokens_per_sec": round(args.batch * args.seq_len / dt),
        "seq_len": args.seq_len,
        "batch": args.batch,
        "flash": bool(cfg.flash),  # the path auto actually picked
        "flash_mode": args.flash,
        "remat": "off" if args.no_remat else args.remat_policy,
        "ce_chunk": args.ce_chunk,
        "ce_vocab_chunk": args.ce_vocab_chunk,
        "loss": round(float(m["loss"]), 3),
    }
    if args.experts:
        out["experts"] = f"{args.experts}top{args.expert_top_k}"
        out["d_ff"] = cfg.d_ff
        out["capacity_factor"] = args.capacity_factor
        # record what the model RESOLVED, not what the CLI requested —
        # auto picks an impl and the group snaps to a divisor of S
        from ddl_tpu.models.transformer import moe_routing_plan

        out["moe_dispatch"], out["moe_group"] = moe_routing_plan(
            cfg, args.seq_len
        )
        for key in ("moe_drop_frac", "moe_load_max", "moe_load_min"):
            out[key] = round(float(m[key]), 4)
    from ddl_tpu.utils.memory import hbm_stats

    mem = hbm_stats()
    if mem is not None:
        out["hbm_peak_bytes"] = int(mem["peak_bytes_in_use"])
    from ddl_tpu.bench.mfu import (
        append_mfu,
        chunked_ce_extra_flops,
        flash_attention_train_flops,
    )

    # executed FLOPs: equals MFU with remat off, HFU otherwise.  Cost
    # analysis assigns zero FLOPs to the Pallas kernel, so flash rows add
    # the kernel's banded FLOPs analytically; it also counts scan bodies
    # once, so ce_chunk rows add the missing loss-edge trips (bench/mfu.py).
    # MFU rows count theoretical model matmuls; HFU rows count what the
    # program executes (incl. score recomputes / checkpoint replays).
    accounting = "model" if args.no_remat else "executed"
    extra_flops = (
        flash_attention_train_flops(
            args.batch, cfg.n_heads, args.seq_len, cfg.head_dim,
            cfg.n_layers, window=cfg.attn_window, remat=cfg.remat,
            accounting=accounting,
        )
        if cfg.flash
        else 0.0
    )
    if cfg.ce_vocab_chunk:
        from ddl_tpu.bench.mfu import vocab_chunked_ce_extra_flops

        extra_flops += vocab_chunked_ce_extra_flops(
            args.batch, args.seq_len, args.d_model, args.vocab,
            cfg.ce_vocab_chunk, accounting=accounting,
        )
    if cfg.ce_chunk:
        extra_flops += chunked_ce_extra_flops(
            args.batch, args.seq_len, args.d_model, args.vocab,
            cfg.ce_chunk, accounting=accounting,
        )
    append_mfu(out, fns.train, dt, state, inp, tgt,
               key="mfu" if args.no_remat else "hfu",
               extra_flops=extra_flops)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
