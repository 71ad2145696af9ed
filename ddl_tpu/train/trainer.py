"""The CNN Trainer: the DenseNet family on the shared training loop.

One trainer for all four reference entry points (``single.py`` / ``ddp.py`` /
``pp.py`` / ``ddp_n_pp.py`` each re-implement their own ``Trainer`` class —
SURVEY.md section 1): strategy is the mesh shape, the rest of the loop is
shared.  Per-epoch behaviour mirrors the reference trainer
(``single.py:169-197``): timed epoch, mean train loss, epoch-accumulated
train accuracy, full eval metric suite, CSV logging, QWK-gated snapshot
(``ddp.py:292-295`` — and unlike the reference, the save is actually wired
up).  Metric aggregation across data-parallel replicas needs no explicit
``all_gather`` (reference ``ddp.py:194-199``): step outputs are global
``jax.Array``s already, fetched to host once per epoch.

The epoch loop itself — timing, CSV logging, NaN watchdog, profiler hook,
preemption handling, snapshot gating — lives in ``train/loop.BaseTrainer``,
shared with the LM (``train/lm_trainer.py``) and ViT
(``train/vit_trainer.py``) families; this class supplies only the
CNN-specific pieces (data loaders, step functions, eval metrics, Orbax
snapshots keyed by epoch).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ddl_tpu import checkpoint as ckpt
from ddl_tpu.config import Config
from ddl_tpu.data import DataLoader, ShardedEpochSampler, build_datasets, shard_batch
from ddl_tpu.models import build_stages, stage_boundary_shapes
from ddl_tpu.obs.steptrace import stage
from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
from ddl_tpu.train.loop import BaseTrainer, _child, _phase
from ddl_tpu.train.state import create_train_state, make_optimizer
from ddl_tpu.train.steps import make_dp_step_fns
from ddl_tpu.utils import MetricLogger, faultinject, masked_classification_eval

__all__ = ["Trainer", "resolve_job_id"]


def resolve_job_id() -> str:
    """Job identity from the launcher env (reference reads TORCHX_JOB_ID,
    ``single.py:102``); the last path segment is the job name."""
    raw = os.environ.get("DDL_JOB_ID") or os.environ.get("TORCHX_JOB_ID") or "local"
    return raw.split("/")[-1]


def _to_host(x) -> np.ndarray:
    """Fetch a (possibly multi-host-sharded) jax.Array fully to this host."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


class Trainer(BaseTrainer):
    best_metric = "qwk"
    best_mode = "max"
    best_label = "QWK"

    def __init__(self, cfg: Config, mesh=None, datasets=None) -> None:
        cfg.validate()
        self.cfg = cfg
        self.job_id = resolve_job_id()
        with stage("setup.model", self.obs):
            self.mesh = mesh if mesh is not None else build_mesh(
                MeshSpec(cfg.mesh.data, cfg.mesh.pipe)
            )

            pipelined = cfg.strategy in ("pp", "dp_pp")
            self.stages = build_stages(cfg.model, num_stages=None if pipelined else 1)
            self.tx = make_optimizer(cfg.train)
            self._zero = False
            if cfg.train.zero_sharding:
                from ddl_tpu.train.fused_optim import with_zero

                # CNN DDP params are replicated (cnn_rules: everything P()),
                # so param_specs=None; with_zero no-ops at mesh data=1
                self.tx = with_zero(self.tx, self.mesh)
                self._zero = getattr(self.tx, "zero", None) is not None
            rng = jax.random.key(cfg.train.seed)
            self.state = create_train_state(
                self.stages, self.tx, rng, cfg.data.image_size,
                mesh=self.mesh if self._zero else None,
            )
            if cfg.model.pretrained_path:
                from ddl_tpu.models.convert import load_torch_checkpoint

                p, bs, skipped = load_torch_checkpoint(
                    cfg.model.pretrained_path, self.state.params, self.state.batch_stats
                )
                self.state = self.state.replace(params=p, batch_stats=bs)
                if skipped:
                    print(f"[ddl_tpu] pretrained overlay skipped keys: {skipped}")
            self._rebuild_step_fns()
            self.grad_stats_fn = None
            if cfg.train.log_gradient_stats and not pipelined:
                from ddl_tpu.train.steps import make_grad_stats_fn

                self.grad_stats_fn = make_grad_stats_fn(
                    self.stages, self.mesh, jnp.dtype(cfg.model.compute_dtype),
                    zero_sharding=self._zero,
                )

        with stage("setup.data", self.obs):
            train_ds, test_ds = datasets if datasets is not None else build_datasets(cfg.data)
            # Host-level sharding (DistributedSampler analog, ddp.py:343): each
            # process loads 1/process_count of the global batch; per-chip
            # sharding happens on-device via NamedSharding.
            n_proc, proc = jax.process_count(), jax.process_index()
            if cfg.data.global_batch_size % n_proc:
                raise ValueError("global_batch_size must divide by process count")
            per_proc_batch = cfg.data.global_batch_size // n_proc
            per_proc_eval = cfg.data.eval_batch_size // n_proc
            self.train_loader = DataLoader(
                train_ds,
                per_proc_batch,
                sampler=ShardedEpochSampler(
                    len(train_ds), n_proc, proc,
                    shuffle=cfg.data.shuffle, drop_last=cfg.data.drop_last,
                    seed=cfg.train.seed,
                ),
                num_workers=cfg.data.num_workers,
                drop_last=cfg.data.drop_last,
                on_retry=self._note_io_retry,
            )
            # Eval is deterministic and full-coverage: ordered (no shuffle), no
            # dropped tail — sentinel padding keeps batch shapes static (one
            # compiled eval fn) and every test sample is counted exactly once,
            # the SPMD analog of the reference evaluating everything
            # (single.py:199-258).  Round 1 inherited shuffle+drop_last here,
            # which made eval metrics (and the QWK save gate) a shifting subset.
            self.test_loader = DataLoader(
                test_ds,
                per_proc_eval,
                sampler=ShardedEpochSampler(
                    len(test_ds), n_proc, proc,
                    shuffle=False, drop_last=False, pad_mode="sentinel",
                    seed=cfg.train.seed + 1,
                ),
                num_workers=cfg.data.num_workers,
                drop_last=False,
                pad_last_batch=True,
                on_retry=self._note_io_retry,
            )
            if len(test_ds) == 0:
                raise ValueError("empty eval set")

        # resume decision happens BEFORE the logger so the CSV lineage
        # column records auto-resumed runs too, not just flag-resumed ones
        self._resume_job = cfg.train.snapshot_job_id
        self._resume_epoch = cfg.train.snapshot_epoch
        self._resume_auto = False
        if self._resume_job is None:
            # snapshot_epoch without a job id means THIS job at that epoch
            found = ckpt.resolve_resume(
                cfg.train.checkpoint_dir, self.job_id,
                explicit=cfg.train.snapshot_epoch,
                auto=cfg.train.auto_resume,
            )
            if found is not None:
                self._resume_job, self._resume_epoch = self.job_id, found
                self._resume_auto = cfg.train.snapshot_epoch is None
        self.logger = MetricLogger(
            cfg.train.log_dir,
            self.job_id,
            global_rank=proc,
            local_rank=proc,
            model_start_job_id=self._resume_job,
        )
        self.is_logging_process = proc == 0
        self._init_obs(cfg.train.log_dir, self.job_id, "cnn")
        self.epochs_run = 0
        # shared-loop knobs (train/loop.BaseTrainer)
        self.num_periods = cfg.train.max_epochs
        self.halt_on_nan = cfg.train.halt_on_nan
        from ddl_tpu.train.recovery import make_policy

        self.recovery = make_policy(cfg.train)
        self.keep_snapshots = cfg.train.keep_snapshots
        self.preemption_save = cfg.train.preemption_save
        self.profile_dir = cfg.train.profile_dir
        self.save_best = cfg.train.save_best_qwk
        self.best_value = -1.0
        self._snapshot_mgr = None
        if self._resume_job is not None:
            self._load_snapshot()

    def _rebuild_step_fns(self) -> None:
        """(Re)build the compiled step functions — also the grace dial:
        during a post-rollback grace window the optimizer is wrapped so
        its updates are scaled by ``update_scale`` (state-tree-identical,
        ``train/recovery.scale_tx``)."""
        cfg = self.cfg
        from ddl_tpu.train.recovery import scale_tx

        tx = scale_tx(self.tx, self.update_scale)
        compute_dtype = jnp.dtype(cfg.model.compute_dtype)
        if cfg.strategy in ("pp", "dp_pp"):
            from ddl_tpu.parallel.pipeline import make_pipeline_step_fns

            self.step_fns = make_pipeline_step_fns(
                self.stages,
                tx,
                self.mesh,
                compute_dtype,
                num_microbatches=cfg.train.num_microbatches,
                boundary_shapes=stage_boundary_shapes(cfg.model, cfg.data.image_size),
                num_classes=cfg.model.num_classes,
                remat=cfg.model.remat,
                schedule=cfg.train.pipeline_schedule,
            )
        else:
            from ddl_tpu.ops import get_normalizer

            self.step_fns = make_dp_step_fns(
                self.stages,
                tx,
                self.mesh,
                compute_dtype,
                normalizer=get_normalizer(cfg.model.pallas_normalize),
            )

    def _snapshot_store(self):
        t = self.cfg.train
        return (t.checkpoint_dir, self.job_id) if t.checkpoint_dir else None

    def _rollback_restore(self, epoch: int) -> None:
        self.state, self.epochs_run = ckpt.load_snapshot(
            self.cfg.train.checkpoint_dir, self.job_id, epoch, self.state,
            verify=False,
        )
        self._apply_cursor(self.job_id, epoch)

    def _apply_cursor(self, job_id: str, epoch: int) -> None:
        """Exact-resume refinement: if the snapshot's manifest carries a
        mid-epoch data cursor (a preemption landed partway through the
        epoch), re-enter THAT epoch at the recorded batch offset instead
        of skipping its remaining batches — the resumed stream replays
        no batch and skips none."""
        cur = ckpt.read_cursor(
            self.cfg.train.checkpoint_dir, job_id, epoch
        )
        if cur and int(cur.get("offset", 0)) > 0:
            self.epochs_run = int(cur.get("period", self.epochs_run))
            self._resume_offset = int(cur["offset"])
            print(
                f"[resume] data cursor: re-entering epoch "
                f"{self.epochs_run} at batch {self._resume_offset}"
            )

    # ------------------------------------------------------------------

    # ``epochs_run`` is this family's public name for the loop's resume
    # cursor (tests and the CLI read it); keep both views in sync.
    @property
    def periods_run(self) -> int:
        return self.epochs_run

    @periods_run.setter
    def periods_run(self, value: int) -> None:
        self.epochs_run = value

    def _load_snapshot(self) -> None:
        t = self.cfg.train
        path = ckpt.snapshot_path(
            t.checkpoint_dir, self._resume_job, self._resume_epoch
        )
        if not path.exists():
            print(f"No snapshot at {path}; starting fresh")
            return
        print(f"Loading snapshot from {path}")
        from time import perf_counter

        t0 = perf_counter()
        self.state, self.epochs_run = ckpt.run_resume_load(
            # an auto-discovered epoch was integrity-verified by
            # resolve_resume moments ago; only explicit resumes re-verify
            lambda: ckpt.load_snapshot(
                t.checkpoint_dir, self._resume_job, self._resume_epoch,
                self.state, verify=not self._resume_auto,
            ),
            auto=self._resume_auto,
            desc=str(path),
            hint="pass train.auto_resume=false",
        )
        self._apply_cursor(self._resume_job, self._resume_epoch)
        self._emit_snapshot_restore(
            perf_counter() - t0, self._resume_epoch,
            self.epochs_run, self._resume_offset,
        )
        print(f"Resuming training from epoch {self.epochs_run}")

    def save_snapshot(self, epoch: int) -> None:
        cursor = self.data_cursor
        if cursor and cursor.get("offset", 0) >= len(self.train_loader):
            # preempted exactly at the epoch's end: the stream is fully
            # consumed, so the cursor is a clean next-epoch start (a
            # literal offset would resume into an empty remainder)
            cursor = {"period": int(cursor["period"]) + 1, "offset": 0}
        if self.cfg.train.async_checkpoint:
            if self._snapshot_mgr is None:
                self._snapshot_mgr = ckpt.SnapshotManager(
                    self.cfg.train.checkpoint_dir, self.job_id
                )
            path = self._snapshot_mgr.save(epoch, self.state, cursor=cursor)
        else:
            path = ckpt.save_snapshot(
                self.cfg.train.checkpoint_dir, self.job_id, epoch,
                self.state, cursor=cursor,
            )
        print(f"Epoch {epoch} | Saved snapshot to {path}")

    def wait_for_saves(self) -> None:
        if self._snapshot_mgr is not None:
            self._snapshot_mgr.wait()

    def last_snapshot_hint(self):
        return ckpt.latest_epoch(self.cfg.train.checkpoint_dir, self.job_id)

    def resume_hint(self, epoch: int) -> str:
        return (
            f"train.snapshot_job_id={self.job_id} "
            f"train.snapshot_epoch={epoch}"
        )

    # ------------------------------------------------------------------

    def run_period(self, epoch: int, guard=None):
        """One training epoch; returns (metric dict, steps).

        ``guard`` (a ``PreemptionGuard``) stops the epoch after the
        in-flight step when a preemption signal has arrived.
        """
        self.train_loader.set_epoch(epoch)
        # exact resume: skip the batches a preemption snapshot already
        # consumed this epoch (index-level skip — nothing is loaded and
        # discarded; one-shot, later epochs start at 0)
        skip = self.consume_resume_offset()
        if skip:
            self.train_loader.set_start_batch(skip)
        losses, preds, targets = [], [], []
        steps = 0
        # event steps are GLOBAL (epoch * steps/epoch + i) so the obs
        # liveness/straggler comparison sees one monotone counter per
        # host, the same unit the LM family's global step gives it
        step_base = epoch * len(self.train_loader) + skip
        if self.obs is not None:
            # one ``collate`` span a batch from the loader's thread
            self.train_loader.on_collate = self.obs.collate_hook(
                step_base - skip
            )
        it = iter(self.train_loader)
        while True:
            # data_wait = host-side batch production (the loader), h2d =
            # device placement, step = compiled-step dispatch; the device
            # time dispatch hides surfaces in the period-end fence phase.
            # A phase that begins with the device known idle carries a
            # child that says so (obs/steptrace.py)
            with _phase(self.obs, "data_wait", step=step_base + steps):
                batch = next(it, None)
            if batch is None:
                break
            images, labels = batch
            with _phase(self.obs, "h2d", step=step_base + steps):
                gi, gl = shard_batch(self.mesh, images, labels)
            if self.grad_stats_fn is not None and self.is_logging_process:
                # before the train step: it donates (consumes) self.state
                stats = jax.device_get(self.grad_stats_fn(self.state, gi, gl))
                self.logger.log_gradient_stats(stats, step=steps)
            with _phase(self.obs, "step", step=step_base + steps):
                self.state, loss, pred = self.step_fns.train(self.state, gi, gl)
            if self.obs is not None:
                self.obs.note_dispatch(loss)
            # HBM ledger: stamp the train step's static memory budget
            # once, after its first dispatch (obs/hbm.py hbm_plan)
            self.emit_hbm_plan("train_step", self.step_fns.train,
                               self.state, gi, gl)
            losses.append(loss)
            preds.append(pred)
            targets.append(gl)
            steps += 1
            faultinject.check_step(step_base + steps - 1, guard)
            if guard is not None and guard.requested:
                break
        if steps == 0:
            raise RuntimeError("empty epoch: dataset smaller than one batch")
        with _phase(self.obs, "fence", step=step_base + steps):
            # the early losses are copied while the device still runs; when
            # the last one is here the device has drained, and the copies
            # after it are host time with the device idle
            with _child(self.obs, "fence.drain", step=step_base + steps):
                mean_loss = float(np.mean([_to_host(l) for l in losses]))
            if self.obs is not None:
                self.obs.device_drained()
            with _child(self.obs, "fence.d2h", step=step_base + steps):
                y_pred = np.concatenate([_to_host(p) for p in preds])
                y_true = np.concatenate([_to_host(t) for t in targets])
        accuracy = float(np.mean(y_pred == y_true))
        return {"loss": mean_loss, "train_accuracy": accuracy}, steps

    def evaluate(self, epoch: int) -> dict:
        """Eval loop -> metric dict (reference ``_evaluate``, single.py:199-251).

        Deterministic and full-coverage: rows padded to static shape carry
        label -1 and are masked out, so metrics are computed over every test
        sample exactly once and are epoch-order invariant."""
        self.test_loader.set_epoch(epoch)
        logits, targets = [], []
        for images, labels in self.test_loader:
            gi, gl = shard_batch(self.mesh, images, labels)
            logits.append(self.step_fns.evaluate(self.state, gi))
            targets.append(gl)
        all_logits = np.concatenate([_to_host(l) for l in logits])
        all_targets = np.concatenate([_to_host(t) for t in targets])
        return masked_classification_eval(all_logits, all_targets)

    # -------------------------------------------------- loop hooks

    def evaluate_period(self, epoch: int) -> dict:
        return self.evaluate(epoch)

    def format_train_line(self, epoch, elapsed, steps, m) -> str:
        return (
            f"Epoch {epoch} | Time: {elapsed:.2f}s | Steps: {steps} | "
            f"Loss: {m['loss']:.4f} | Training Accuracy: {m['train_accuracy']:.4f}"
        )

    def format_eval_line(self, epoch, m) -> str:
        return (
            f"Epoch {epoch} | Validation Loss: {m['val_loss']:.4f} | "
            f"Accuracy: {m['val_accuracy']:.4f} | QWK: {m['qwk']:.4f}"
        )
