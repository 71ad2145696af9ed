"""Train the ViT family on the APTOS-shape image data path.

Argparse shim over ``ddl_tpu.train.vit_trainer.ViTTrainer`` (the shared
training loop: default-on CSV logging, NaN watchdog, QWK-gated snapshots,
SIGTERM checkpoint-and-exit, profiler hook).  Second vision model family
(models/vit.py): the LM's transformer blocks run bidirectionally over a
patch sequence, sharded TP over heads/MLP and DP over batch by the same
logical-axis rule table — where the reference supports exactly one vision
model (DenseNet121, single.py:297-299).

    python examples/train_vit.py --cpu-devices 8 --data 2 --model 2 \
        --image-size 32 --patch 8 --epochs 2

Uses the synthetic APTOS-shape dataset when DDL_DATASET_DIR is unset
(same fallback as the CNN trainer); point it at the real data for the
full 224px task.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pipe", type=int, default=1,
                    help="GPipe stages over the encoder blocks")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="microbatches when --pipe > 1 (default: --pipe)")
    ap.add_argument("--pipeline-schedule", default="gpipe",
                    choices=["gpipe", "1f1b", "zb"],
                    help="pipeline schedule when --pipe > 1 (1f1b: "
                    "interleaved, O(pipe) stage-activation residency; "
                    "zb: zero-bubble B/W-split 1f1b, --virtual-stages 1)")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved pipeline: layer chunks per device "
                    "(>1 shrinks the bubble by that factor)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation chunks per step (pipe=1 only)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="residual dropout rate")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1 optimizer-state sharding over 'data'. "
                    "Switches to the plain fused Adam (drops this "
                    "example's default weight-decay/clip chain — the "
                    "sharded update lives in the fused per-leaf "
                    "expression); flat step path only")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--patch", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention K/V head count (0 = MHA)")
    ap.add_argument("--num-train", type=int, default=256,
                    help="synthetic train examples (when no real dataset)")
    ap.add_argument("--num-test", type=int, default=64)
    ap.add_argument("--cpu-devices", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="checkpoints",
                    help="QWK-gated / preemption snapshot dir ('' disables)")
    ap.add_argument("--keep-snapshots", type=int, default=0,
                    help="snapshot GC: keep only the newest K valid "
                    "snapshots (corrupt ones never count; 0 = keep all)")
    ap.add_argument("--resume-epoch", type=int, default=None,
                    help="restore the snapshot saved at this epoch")
    ap.add_argument("--fresh", action="store_true",
                    help="start from scratch even if this job id already "
                    "has snapshots (auto-resume is the default: a relaunch "
                    "with the same --job-id continues from the latest one)")
    ap.add_argument("--job-id", default="vit")
    ap.add_argument("--log-dir", default="training_logs",
                    help="MetricLogger CSV suite directory (loss, "
                    "img_per_sec, val_loss/val_accuracy/qwk, epoch_time), "
                    "default-on so ddl_tpu.bench.analysis aggregates ViT "
                    "runs alongside the CNN/LM families; '' disables")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of one post-warmup "
                    "epoch into this dir")
    ap.add_argument("--no-halt-on-nan", action="store_true",
                    help="keep training through non-finite losses")
    args = ap.parse_args()

    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
    import jax

    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()

    from ddl_tpu.config import DataConfig
    from ddl_tpu.models.vit import ViTConfig
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.train.state import build_optimizer
    from ddl_tpu.train.vit_trainer import ViTRunConfig, ViTTrainer

    cfg = ViTConfig(
        image_size=args.image_size,
        patch_size=args.patch,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=max(2, args.d_model // 64),
        n_kv_heads=args.kv_heads,
        head_dim=64 if args.d_model >= 128 else args.d_model // 2,
        d_ff=4 * args.d_model,
        compute_dtype="bfloat16" if jax.default_backend() != "cpu" else "float32",
        fsdp=args.fsdp,
        dropout_rate=args.dropout,
    )
    spec = LMMeshSpec(data=args.data, model=args.model, pipe=args.pipe)
    tx = (
        build_optimizer(args.lr, fused=True)
        if args.zero
        else build_optimizer(args.lr, weight_decay=0.05, grad_clip_norm=1.0)
    )
    run = ViTRunConfig(
        batch=args.batch,
        epochs=args.epochs,
        num_microbatches=args.microbatches,
        accum_steps=args.accum,
        pipeline_schedule=args.pipeline_schedule,
        virtual_stages=args.virtual_stages,
        zero_sharding=args.zero,
        checkpoint_dir=args.checkpoint_dir or None,
        keep_snapshots=args.keep_snapshots,
        resume_epoch=args.resume_epoch,
        auto_resume=not args.fresh,
        job_id=args.job_id,
        log_dir=args.log_dir or None,
        halt_on_nan=not args.no_halt_on_nan,
        profile_dir=args.profile_dir,
    )
    dc = DataConfig(
        image_size=args.image_size,
        global_batch_size=args.batch,
        eval_batch_size=args.batch,
        synthetic_num_train=args.num_train,
        synthetic_num_test=args.num_test,
    )
    trainer = ViTTrainer(cfg, spec, tx, run, data=dc)
    print(f"mesh=(data={args.data}, model={args.model}, pipe={args.pipe}) "
          f"fsdp={args.fsdp} patches={cfg.num_patches}")
    trainer.train()


if __name__ == "__main__":
    main()
