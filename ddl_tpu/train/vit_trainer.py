"""ViT-family trainer: the vision transformer on the shared training loop.

Same shape as the CNN Trainer (epoch periods, APTOS-style image loaders,
masked full-coverage eval, QWK-gated snapshots) but driving the
transformer-family step functions (``train/vit_steps.py``) over the 5-axis
LM mesh.  Replaces the bespoke loop that lived in ``examples/train_vit.py``
through round 2, which had no preemption guard, NaN watchdog, profiler
hook, or checkpointing at all; the example is now an argparse shim.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from ddl_tpu import checkpoint as ckpt
from ddl_tpu.config import DataConfig
from ddl_tpu.data import (
    DataLoader,
    ShardedEpochSampler,
    build_datasets,
    shard_batch,
)
from ddl_tpu.models.vit import ViTConfig
from ddl_tpu.obs.steptrace import stage
from ddl_tpu.parallel.sharding import LMMeshSpec
from ddl_tpu.train.loop import BaseTrainer, _child, _phase
from ddl_tpu.train.vit_steps import make_vit_step_fns
from ddl_tpu.utils import MetricLogger, faultinject, masked_classification_eval

__all__ = ["ViTRunConfig", "ViTTrainer"]


@dataclasses.dataclass
class ViTRunConfig:
    batch: int = 32
    epochs: int = 3
    num_microbatches: int = 0
    accum_steps: int = 1
    # "gpipe" | "1f1b" | "zb" (parallel/rules.PIPELINE_SCHEDULES)
    pipeline_schedule: str = "gpipe"
    virtual_stages: int = 1
    # ZeRO-1 optimizer-state sharding over 'data' (requires a fused Adam
    # tx and the flat step path — see TrainConfig.zero_sharding)
    zero_sharding: bool = False
    checkpoint_dir: str | None = "checkpoints"
    # keep only the newest K valid snapshots (0 = all); corrupt ones
    # never count toward K — see checkpoint.gc_snapshots
    keep_snapshots: int = 0
    resume_epoch: int | None = None
    # With no explicit resume_epoch, continue from this job id's latest
    # snapshot automatically when one exists (relaunch == resume).
    auto_resume: bool = True
    save_best_qwk: bool = True
    job_id: str = "vit"
    log_dir: str | None = "training_logs"  # default-on CSV observability
    halt_on_nan: bool = True
    # "halt" | "recover" — see LMRunConfig.nan_policy
    nan_policy: str = "halt"
    nan_max_consecutive: int = 3
    nan_grace_scale: float = 0.1
    nan_grace_periods: int = 2
    preemption_save: bool = True
    profile_dir: str | None = None


class ViTTrainer(BaseTrainer):
    best_metric = "qwk"
    best_mode = "max"
    best_label = "QWK"

    def __init__(
        self,
        cfg: ViTConfig,
        spec: LMMeshSpec,
        tx,
        run: ViTRunConfig,
        data: DataConfig | None = None,
        datasets=None,
        rng: jax.Array | None = None,
    ) -> None:
        self.cfg, self.spec, self.run = cfg, spec, run
        self.job_id = run.job_id
        self.tx = tx
        self._rng = rng if rng is not None else jax.random.key(0)
        with stage("setup.model", self.obs):
            self.fns = self._make_fns()

        with stage("setup.data", self.obs):
            dc = data if data is not None else DataConfig(
                image_size=cfg.image_size,
                global_batch_size=run.batch,
                eval_batch_size=run.batch,
            )
            train_ds, test_ds = (
                datasets if datasets is not None else build_datasets(dc)
            )
            n_proc, proc = jax.process_count(), jax.process_index()
            self.train_loader = DataLoader(
                train_ds, run.batch // n_proc,
                sampler=ShardedEpochSampler(len(train_ds), n_proc, proc, seed=0),
                on_retry=self._note_io_retry,
            )
            # deterministic full-coverage eval: ordered, sentinel-padded to
            # static shapes, padded rows (label -1) masked out — same contract
            # as the CNN Trainer's eval loop
            self.test_loader = DataLoader(
                test_ds, run.batch // n_proc,
                sampler=ShardedEpochSampler(
                    len(test_ds), n_proc, proc,
                    shuffle=False, drop_last=False, pad_mode="sentinel", seed=1,
                ),
                drop_last=False, pad_last_batch=True,
                on_retry=self._note_io_retry,
            )

        self.is_logging_process = proc == 0
        self.logger = (
            MetricLogger(run.log_dir, run.job_id, global_rank=proc,
                         local_rank=proc)
            if run.log_dir
            else None
        )
        self._init_obs(run.log_dir, run.job_id, "vit")
        self._emit_pipe_schedule(
            run.pipeline_schedule, self.spec.pipe,
            run.num_microbatches or self.spec.pipe, run.virtual_stages,
        )
        self.num_periods = run.epochs
        self.halt_on_nan = run.halt_on_nan
        from ddl_tpu.train.recovery import make_policy

        self.recovery = make_policy(run)
        self.keep_snapshots = run.keep_snapshots
        self.preemption_save = run.preemption_save and bool(run.checkpoint_dir)
        self.profile_dir = run.profile_dir
        self.save_best = run.save_best_qwk and bool(run.checkpoint_dir)
        self.best_value = -1.0

        with stage("setup.model", self.obs):
            self.state = self.fns.init_state()
        self.periods_run = 0
        resume_epoch = ckpt.resolve_resume(
            run.checkpoint_dir, run.job_id, run.resume_epoch, run.auto_resume
        )
        if run.checkpoint_dir and resume_epoch is not None:
            from time import perf_counter

            t0 = perf_counter()
            self.state, self.periods_run = ckpt.run_resume_load(
                # auto-discovered epochs were verified by resolve_resume
                lambda: ckpt.load_snapshot(
                    run.checkpoint_dir, run.job_id, resume_epoch, self.state,
                    verify=run.resume_epoch is not None,
                ),
                auto=run.resume_epoch is None,
                desc=f"job {run.job_id!r} epoch {resume_epoch}",
                hint="pass --fresh (auto_resume=False)",
            )
            self._apply_cursor(resume_epoch)
            self._emit_snapshot_restore(
                perf_counter() - t0, resume_epoch,
                self.periods_run, self._resume_offset,
            )
            print(f"resumed; continuing at epoch {self.periods_run}")

    def _make_fns(self):
        run = self.run
        from ddl_tpu.train.recovery import scale_tx

        return make_vit_step_fns(
            self.cfg, self.spec, scale_tx(self.tx, self.update_scale),
            self._rng, run.batch,
            num_microbatches=run.num_microbatches,
            accum_steps=run.accum_steps,
            pipeline_schedule=run.pipeline_schedule,
            virtual_stages=run.virtual_stages,
            zero_sharding=run.zero_sharding,
        )

    def _rebuild_step_fns(self) -> None:
        self.fns = self._make_fns()

    def _snapshot_store(self):
        run = self.run
        return (run.checkpoint_dir, run.job_id) if run.checkpoint_dir else None

    def _rollback_restore(self, epoch: int) -> None:
        self.state, self.periods_run = ckpt.load_snapshot(
            self.run.checkpoint_dir, self.run.job_id, epoch, self.state,
            verify=False,
        )
        self._apply_cursor(epoch)

    def _apply_cursor(self, epoch: int) -> None:
        """Exact resume: a mid-epoch preemption snapshot re-enters its
        epoch at the recorded batch offset (same mechanism as the CNN
        family — see Trainer._apply_cursor)."""
        cur = ckpt.read_cursor(
            self.run.checkpoint_dir, self.run.job_id, epoch
        )
        if cur and int(cur.get("offset", 0)) > 0:
            self.periods_run = int(cur.get("period", self.periods_run))
            self._resume_offset = int(cur["offset"])
            print(
                f"[resume] data cursor: re-entering epoch "
                f"{self.periods_run} at batch {self._resume_offset}"
            )

    # ------------------------------------------------------- loop hooks

    def run_period(self, epoch: int, guard=None):
        self.train_loader.set_epoch(epoch)
        # exact resume: skip batches a preemption snapshot already
        # consumed this epoch (one-shot index-level skip)
        skip = self.consume_resume_offset()
        if skip:
            self.train_loader.set_start_batch(skip)
        losses, steps = [], 0
        # global event steps (epoch * steps/epoch + i) — one monotone
        # counter per host for the obs liveness/straggler comparison
        step_base = epoch * len(self.train_loader) + skip
        if self.obs is not None:
            # one ``collate`` span a batch from the loader's thread
            self.train_loader.on_collate = self.obs.collate_hook(
                step_base - skip
            )
        it = iter(self.train_loader)
        while True:
            with _phase(self.obs, "data_wait", step=step_base + steps):
                batch = next(it, None)
            if batch is None:
                break
            images, labels = batch
            with _phase(self.obs, "h2d", step=step_base + steps):
                gi, gl = shard_batch(self.fns.mesh, images, labels)
            with _phase(self.obs, "step", step=step_base + steps):
                self.state, m = self.fns.train(self.state, gi, gl)
            if self.obs is not None:
                self.obs.note_dispatch(m["loss"])
            # HBM ledger: stamp the train step's static memory budget
            # once, after its first dispatch (obs/hbm.py hbm_plan)
            self.emit_hbm_plan("train_step", self.fns.train,
                               self.state, gi, gl)
            # keep the per-step loss ON DEVICE: float()-ing it here would
            # block every step on the compiled program (the host-sync
            # anti-pattern `ddl_tpu lint` flags) — fetch once per epoch,
            # like the CNN/LM families
            losses.append(m["loss"])
            steps += 1
            faultinject.check_step(step_base + steps - 1, guard)
            if guard is not None and guard.requested:
                break
        if steps == 0:
            raise RuntimeError("empty epoch: dataset smaller than one batch")
        with _phase(self.obs, "fence", step=step_base + steps - 1):
            with _child(self.obs, "fence.drain", step=step_base + steps - 1):
                loss = float(np.mean([np.asarray(l) for l in losses]))
            if self.obs is not None:
                self.obs.device_drained()
        return {"loss": loss}, steps

    def evaluate_period(self, epoch: int) -> dict:
        self.test_loader.set_epoch(epoch)
        logits, targets = [], []
        for images, labels in self.test_loader:
            gi, gl = shard_batch(self.fns.mesh, images, labels)
            logits.append(np.asarray(self.fns.evaluate(self.state, gi)))
            targets.append(np.asarray(gl))
        return masked_classification_eval(
            np.concatenate(logits), np.concatenate(targets)
        )

    def rate_metrics(self, steps: int, elapsed: float) -> dict:
        return {"img_per_sec": steps * self.run.batch / elapsed}

    def format_train_line(self, epoch, elapsed, steps, m) -> str:
        return (
            f"epoch {epoch}: loss {m['loss']:.4f} ({steps} steps, "
            f"{elapsed:.1f}s, {steps / elapsed:.2f} steps/s)"
        )

    def format_eval_line(self, epoch, m) -> str:
        return (
            f"epoch {epoch}: val_acc {m['val_accuracy']:.4f} "
            f"qwk {m['qwk']:.4f}"
        )

    def save_snapshot(self, epoch: int) -> None:
        cursor = self.data_cursor
        if cursor and cursor.get("offset", 0) >= len(self.train_loader):
            # preempted exactly at the epoch boundary: a clean next-epoch
            # start, not an empty-remainder resume
            cursor = {"period": int(cursor["period"]) + 1, "offset": 0}
        path = ckpt.save_snapshot(
            self.run.checkpoint_dir, self.job_id, epoch, self.state,
            cursor=cursor,
        )
        print(f"epoch {epoch} | saved snapshot to {path}")

    def last_snapshot_hint(self):
        if not self.run.checkpoint_dir:
            return "none (set checkpoint_dir)"
        return ckpt.latest_epoch(self.run.checkpoint_dir, self.job_id)

    def resume_hint(self, epoch: int) -> str:
        return f"--job-id {self.job_id} --resume-epoch {epoch}"
