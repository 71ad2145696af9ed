"""Train the transformer LM family on synthetic byte sequences or a corpus.

Argparse shim over ``ddl_tpu.train.lm_trainer.LMTrainer`` (the shared
training loop: default-on CSV logging, NaN watchdog, SIGTERM
checkpoint-and-exit, profiler hook).  Demonstrates the sharding-rule-driven
strategy surface the CNN entry points cannot express
(models/transformer.py): tensor parallelism, ring-attention sequence
parallelism, MoE expert parallelism, and FSDP — all selected from the
command line as mesh axis sizes, no code changes.

    python examples/train_lm.py --data 2 --seq 2 --model 2 --steps 100
    python examples/train_lm.py --experts 4 --expert-axis 2 --fsdp
    python examples/train_lm.py --pipe 2 --model 2 --microbatches 4

On a dev box without TPUs, add --cpu-devices 8 to simulate the mesh.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--seq", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--expert-axis", type=int, default=1)
    ap.add_argument("--pipe", type=int, default=1,
                    help="pipeline stages over the decoder layers")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatches when --pipe > 1 (default: --pipe)")
    ap.add_argument("--pipeline-schedule", default="gpipe",
                    choices=["gpipe", "1f1b", "zb"],
                    help="pipeline schedule when --pipe > 1: gpipe (all "
                    "forwards then all backwards), 1f1b (interleaved, "
                    "O(pipe) stage-activation residency), or zb "
                    "(zero-bubble: 1f1b with the backward split into "
                    "B/W and weight grads deferred into the cooldown "
                    "ticks; needs --virtual-stages 1)")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved pipeline: layer chunks per device "
                    "(>1 shrinks the bubble by that factor; composes with "
                    "either --pipeline-schedule; needs layers %% (pipe*V) "
                    "== 0 and microbatches %% pipe == 0)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation chunks per step (pipe=1 only)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="residual dropout rate")
    ap.add_argument("--experts", type=int, default=0, help="0 = dense MLP")
    ap.add_argument("--capacity-factor", type=float, default=1.5,
                    help="MoE warm-up expert capacity (see LMConfig)")
    ap.add_argument("--capacity-factor-min", type=float, default=1.0,
                    help="post-warm-up capacity the trainer anneals to "
                    "once the live router drop fraction converges "
                    "(= --capacity-factor disables the anneal)")
    ap.add_argument("--capacity-anneal-step", type=int, default=0,
                    help="anneal at this step regardless of the metric "
                    "(pipelined MoE runs, whose metrics lack drop_frac)")
    ap.add_argument("--moe-ep", default="auto",
                    choices=["auto", "gspmd", "alltoall"],
                    help="expert-parallel exchange: manual lax.all_to_all "
                    "dispatch or GSPMD-inserted collectives")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--attn", default=None, choices=["dense", "ring", "ulysses"],
                    help="attention impl (default: ring when --seq > 1, else dense)")
    ap.add_argument("--flash", nargs="?", const="on", default="off",
                    choices=["on", "off", "auto"],
                    help="Pallas flash-attention kernel (dense/ulysses): "
                    "'--flash' / '--flash on' forces it, '--flash auto' "
                    "picks per run from the measured seq-len crossover")
    # validated against models.transformer.REMAT_POLICIES after parsing —
    # heavy imports stay deferred until --cpu-devices is handled
    ap.add_argument("--remat-policy", default="full",
                    help="per-block checkpoint policy (speed/HBM dial; "
                    "'dots' keeps matmul outputs, ~6%% faster backward)")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable activation rematerialisation entirely "
                    "(fastest when the model fits in HBM; ~20%% over full "
                    "remat on one v5e chip)")
    ap.add_argument("--corpus", default=None,
                    help="token .npy or raw text file to train on "
                    "(default: synthetic Markov-chain bytes)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="with --corpus: evaluate held-out perplexity every "
                    "N steps (0 = off)")
    ap.add_argument("--eval-frac", type=float, default=0.05,
                    help="tail fraction of corpus windows held out for eval")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10,
                    help="console/CSV/obs period cadence in steps (1 = "
                    "per-step periods, the finest anomaly-detector feed)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=0,
                    help="linear LR warmup steps")
    ap.add_argument("--cosine", action="store_true",
                    help="cosine-decay the LR to 0 over --steps")
    ap.add_argument("--weight-decay", type=float, default=0.0,
                    help=">0 switches to decoupled AdamW")
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help=">0 enables global-norm gradient clipping")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1 optimizer-state sharding over 'data': "
                    "moments + weight update on a 1/dp shard of every "
                    "large leaf (needs the fused Adam, so plain-Adam "
                    "configs only; flat step path)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: K/V head count "
                    "(0 = same as query heads; must divide the 8 query "
                    "heads — smaller K/V projections and decode cache)")
    ap.add_argument("--attn-window", type=int, default=0,
                    help="sliding-window attention: each position attends "
                    "only the last N positions (0 = full causal history)")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="chunked head+CE fusion: sequence-chunk size for "
                    "the loss edge (0 = dense CE).  With a set chunk the "
                    "(B,T,V) logits never materialise — the big-vocab "
                    "memory lever; requires --seq 1")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="simulate N CPU devices (dev/test)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save a snapshot every --save-every steps (and on "
                    "held-out perplexity improvements / SIGTERM preemption)")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--keep-snapshots", type=int, default=0,
                    help="snapshot GC: keep only the newest K valid "
                    "snapshots (corrupt ones never count; 0 = keep all)")
    ap.add_argument("--resume-step", type=int, default=None,
                    help="restore the snapshot saved at this step (any mesh, "
                    "any pipeline layout — the saved layout is read from the "
                    "snapshot's metadata)")
    ap.add_argument("--fresh", action="store_true",
                    help="start from scratch even if this job id already "
                    "has snapshots (auto-resume is the default: a relaunch "
                    "with the same --job-id continues from the latest one)")
    ap.add_argument("--job-id", default="lm")
    ap.add_argument("--log-dir", default="training_logs",
                    help="MetricLogger CSV suite directory (loss, "
                    "tokens_per_sec, val_loss/val_ppl, epoch_time), "
                    "default-on so ddl_tpu.bench.analysis aggregates LM "
                    "runs alongside the CNN/ViT families; '' disables")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of one post-warmup "
                    "step window into this dir")
    ap.add_argument("--no-halt-on-nan", action="store_true",
                    help="keep training through non-finite losses")
    args = ap.parse_args()

    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    import jax

    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    from ddl_tpu.models.transformer import REMAT_POLICIES, LMConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.lm_trainer import LMRunConfig, LMTrainer
    from ddl_tpu.train.state import build_optimizer

    if args.remat_policy not in REMAT_POLICIES:
        ap.error(f"--remat-policy must be one of {REMAT_POLICIES}")

    flash = {"on": True, "off": False, "auto": "auto"}[args.flash]
    # Default attention core: ring when the sequence axis is sharded (the
    # tuned SP default), ulysses only when flash is *forced* (the kernel
    # cannot nest in ring).  flash=auto keeps the ring default — pass
    # --attn ulysses explicitly to let auto pick flash-ulysses under SP.
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
        capacity_factor=args.capacity_factor,
        capacity_factor_min=args.capacity_factor_min,
        capacity_anneal_step=args.capacity_anneal_step,
        moe_ep=args.moe_ep,
        compute_dtype="bfloat16" if jax.default_backend() != "cpu" else "float32",
        attn_impl=args.attn
        or (("ulysses" if flash is True else "ring") if args.seq > 1 else "dense"),
        flash=flash,
        remat=not args.no_remat,
        remat_policy=args.remat_policy,
        fsdp=args.fsdp,
        dropout_rate=args.dropout,
        ce_chunk=args.ce_chunk,
    )
    spec = LMMeshSpec(
        args.data, args.seq, args.model, args.expert_axis, pipe=args.pipe
    )
    tx = build_optimizer(
        args.lr,
        weight_decay=args.weight_decay,
        grad_clip_norm=args.clip_norm,
        lr_schedule="cosine" if args.cosine else "constant",
        warmup_steps=args.warmup,
        decay_steps=args.steps if args.cosine else 0,
        # ZeRO's sharded update lives inside the fused per-leaf
        # expression (train/fused_optim); with_zero rejects optax chains
        fused=args.zero,
    )
    run = LMRunConfig(
        batch=args.batch,
        seq_len=args.seq_len,
        steps=args.steps,
        log_every=args.log_every,
        num_microbatches=args.microbatches,
        accum_steps=args.accum,
        pipeline_schedule=args.pipeline_schedule,
        virtual_stages=args.virtual_stages,
        zero_sharding=args.zero,
        corpus=args.corpus,
        eval_every=args.eval_every,
        eval_frac=args.eval_frac,
        checkpoint_dir=args.checkpoint_dir,
        save_every=args.save_every,
        keep_snapshots=args.keep_snapshots,
        resume_step=args.resume_step,
        auto_resume=not args.fresh,
        job_id=args.job_id,
        log_dir=args.log_dir or None,
        halt_on_nan=not args.no_halt_on_nan,
        profile_dir=args.profile_dir,
    )
    trainer = LMTrainer(cfg, spec, tx, run)
    print(f"mesh={spec} experts={args.experts} fsdp={args.fsdp}")
    trainer.train()


if __name__ == "__main__":
    main()
