"""The family-agnostic training loop: one loop for CNN, LM, and ViT.

The reference re-implements its trainer once per entry point (``single.py``
/ ``ddp.py`` / ``pp.py`` / ``ddp_n_pp.py`` each carry a near-identical
``Trainer`` class — SURVEY.md §1); round 1-2 of this framework fixed that
for the CNN family but re-grew the disease for the beyond-parity LM/ViT
families as bespoke example loops.  This module is the fix: every generic
concern lives here exactly once —

* the period loop (a period is an epoch for the vision families, a fixed
  step window for the LM family) with wall-clock timing,
* default-on CSV metric logging (``utils/csv_logger.MetricLogger``),
* the NaN policy: halt with a pointer at the last good snapshot
  (``nan_policy="halt"``), or recover in-loop (``"recover"``): skip the
  bad period's metrics/eval/snapshot, and after K consecutive hits roll
  back to the last valid snapshot with a reduced-LR grace window
  (``train/recovery.RecoveryPolicy``),
* the ``jax.profiler`` trace hook (one post-warmup period),
* preemption handling (SIGTERM → finish the in-flight period → snapshot →
  clean exit, ``utils/preemption.PreemptionGuard``),
* snapshot gating: best-eval-metric improvements (QWK for the vision
  families, val perplexity for the LM) and/or a fixed cadence,
* HBM watermark logging (``utils/memory.hbm_stats``),
* fault-injection hooks (``utils/faultinject``) so every recovery path
  above is provable by a CPU-only test.

Families subclass :class:`BaseTrainer` and implement only what is genuinely
family-specific: how to run one period, how to evaluate, and how to write a
snapshot.  ``train/trainer.py`` (CNN), ``train/lm_trainer.py`` and
``train/vit_trainer.py`` are the three instantiations.
"""

from __future__ import annotations

import os
from contextlib import nullcontext
from time import perf_counter

import jax
import numpy as np

from ddl_tpu.utils import faultinject
from ddl_tpu.utils.memory import hbm_stats

__all__ = ["BaseTrainer"]


def _phase(obs, name: str, step: int | None = None):
    """Obs phase context, or a no-op when the trainer runs untraced."""
    return obs.phase(name, step=step) if obs is not None else nullcontext()


def _child(obs, name: str, step: int | None = None):
    """A span under the open phase that is not a phase itself
    (``fence.drain``, ``fence.d2h``), or a no-op when untraced."""
    return obs.child(name, step=step) if obs is not None else nullcontext()


class BaseTrainer:
    """Template-method training loop.

    Subclass contract — attributes (set in ``__init__``):
      ``state``              the (donated/rebound) train state
      ``job_id``             job identity for logs and snapshots
      ``logger``             a ``MetricLogger`` or ``None``
      ``is_logging_process`` whether this host writes CSV rows
      ``periods_run``        resume cursor (first period to run)
      ``num_periods``        total periods in a full run
      ``halt_on_nan``        raise on non-finite training loss
      ``preemption_save``    install a SIGTERM guard around the run
      ``profile_dir``        trace one post-warmup period here (or None)
      ``save_best``          gate snapshots on eval-metric improvements
      ``best_metric``        eval-dict key for the gate (or None)
      ``best_mode``          "max" (accuracy-like) or "min" (loss-like)
      ``best_value``         current best (init -inf for max, +inf for min)

    and methods:
      ``run_period(period, guard) -> (train_metrics: dict, steps: int)``
          run one period, rebinding ``self.state``; poll
          ``guard.requested`` at step boundaries and stop early when set.
      ``evaluate_period(period) -> dict | None``
          eval metrics for this period boundary, or None to skip.
      ``save_snapshot(period) -> None``
          write a resumable snapshot for this period.
      ``wait_for_saves() -> None``
          block until async snapshot writes commit (default no-op).

    Optional overrides: ``rate_metrics`` (extra throughput rows),
    ``snapshot_due`` (fixed save cadence), ``format_train_line`` /
    ``format_eval_line`` (console output), ``period_label``,
    ``best_label``, ``resume_hint``.
    """

    period_label = "Epoch"
    # CSV name for the per-period wall time; step-based families relabel it
    # (their periods are windows, not epochs) and log their own epoch_time.
    time_metric = "epoch_time"
    # Structured event tracing (obs/steptrace.StepTrace), set by families
    # that construct an EventWriter; None runs the loop untraced.
    obs = None
    # Hung-step watchdog deadline in seconds (0/None = off); families may
    # set it, and the DDL_WATCHDOG_S env var is the operator override.
    watchdog_s = None
    # In-loop non-finite-loss recovery (train/recovery.RecoveryPolicy) or
    # None; with None, halt_on_nan keeps its round-1 halt semantics.
    recovery = None
    # Update scaling during a post-rollback grace window; families that
    # can honor it override set_update_scale (one step-fn rebuild).
    update_scale = 1.0
    # True after a preemption-triggered early exit — the CLI turns this
    # into the supervisor's resumable exit code when supervised.
    preempted = False
    # Snapshot garbage collection: keep the newest K *valid* snapshots
    # (corrupt ones never count toward K — checkpoint.gc_snapshots);
    # 0 = unlimited.  Families set it from their run config.
    keep_snapshots = 0
    # The best-eval-metric snapshot's store key (set by the loop when a
    # save was gated on improvement): GC never deletes it — keep bounds
    # the cadence retention, not the best-model one.
    best_snapshot_epoch = None
    # The data-stream position the NEXT snapshot represents, set by the
    # loop before every save_snapshot call: {"period", "offset"} where
    # offset is the number of batches this period had consumed when the
    # state was captured (0 for a period-boundary save, partial for a
    # preemption save).  Families record it in the snapshot manifest
    # (checkpoint.save_snapshot(cursor=...)) so an exact resume replays
    # no batch and skips none (checkpoint.read_cursor).
    data_cursor = None
    # Batches of the resume period already consumed by the snapshot being
    # restored (from its cursor); the family's run_period skips them.
    _resume_offset = 0

    def consume_resume_offset(self) -> int:
        """The batch offset the first resumed period starts at; one-shot
        (subsequent periods start at 0)."""
        offset, self._resume_offset = self._resume_offset, 0
        return offset

    # ---------------------------------------------------------- overrides

    def rate_metrics(self, steps: int, elapsed: float) -> dict:
        """Extra per-period throughput metrics (tokens/sec, img/sec, ...)."""
        return {}

    # Measured once per process (placement is static after build); the
    # loop stamps it into every period event's rates so `obs export`/
    # `obs fleet` can gauge per-device optimizer-state HBM — the number
    # ZeRO sharding exists to shrink.
    _opt_hbm_cache = None

    def opt_state_hbm_bytes(self) -> int | None:
        """Per-device bytes of this run's live optimizer state: each
        leaf's actual shard shape (so ZeRO/TP sharding is reflected)
        times its dtype width.  None when no state is held."""
        if self._opt_hbm_cache is not None:
            return self._opt_hbm_cache
        import math

        opt_state = getattr(getattr(self, "state", None), "opt_state", None)
        if opt_state is None:
            return None
        total = 0
        for leaf in jax.tree.leaves(opt_state):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            sharding = getattr(leaf, "sharding", None)
            try:
                shard_shape = (
                    sharding.shard_shape(shape)
                    if sharding is not None else shape
                )
            except (TypeError, ValueError):
                shard_shape = shape
            total += math.prod(shard_shape) * dtype.itemsize
        self._opt_hbm_cache = total
        return total

    # Param-shard bytes, measured once like the optimizer gauge; the
    # second tracked category of the HBM ledger (obs/hbm.py).
    _param_hbm_cache = None
    # program labels this trainer has already stamped an hbm_plan for
    _hbm_planned = None

    def param_hbm_bytes(self) -> int | None:
        """Per-device bytes of this run's live parameters (actual shard
        shapes, so ZeRO-3/TP sharding is reflected); None when no
        parameter tree is held."""
        if self._param_hbm_cache is not None:
            return self._param_hbm_cache
        from ddl_tpu.obs.hbm import tree_shard_bytes

        params = getattr(getattr(self, "state", None), "params", None)
        self._param_hbm_cache = tree_shard_bytes(params)
        return self._param_hbm_cache

    def emit_hbm_plan(self, label: str, fn, *args, parts=None, **kwargs) -> None:
        """Stamp one ``hbm_plan`` static budget for a compiled program,
        once per label per trainer.  Families call it right AFTER the
        program's first dispatch (the run's own compile has happened;
        the plan's AOT lower->compile then rides the XLA compile caches
        instead of racing the first step).  Costs one extra backend
        compile per program when the persistent cache is cold —
        ``DDL_HBM_PLAN=off`` disables, ``=aval`` keeps the cheap
        shape-arithmetic budget without the executable analysis.
        ``parts``: the family's own scopes, for the plan's second scope
        table (``hbm.plan_program``).  All of it (the second lowering and
        compile, the compiled text, the scope tables, their file) lies in
        one ``setup.plan`` span: what the plan costs a start."""
        if self.obs is None:
            return
        if self._hbm_planned is None:
            self._hbm_planned = set()
        if label in self._hbm_planned:
            return
        self._hbm_planned.add(label)
        mode = os.environ.get("DDL_HBM_PLAN", "").lower()
        if mode in ("0", "off", "false"):
            return
        from ddl_tpu.obs import hbm
        from ddl_tpu.obs.steptrace import stage

        with stage("setup.plan", self.obs, label=label):
            hbm.plan_program(
                self.obs.writer, label, fn, args, kwargs,
                mode="aval" if mode == "aval" else "full", parts=parts,
            )

    def _emit_hbm_sample(self, step=None, context=None) -> None:
        """One ``hbm_sample`` live breakdown: tracked params/optimizer
        bytes against the device watermark (obs/hbm.live_sample)."""
        if self.obs is None:
            return
        from ddl_tpu.obs import hbm

        hbm.live_sample(
            self.obs.writer,
            params_bytes=self.param_hbm_bytes(),
            opt_bytes=self.opt_state_hbm_bytes(),
            step=step,
            context=context,
        )

    def snapshot_due(self, period: int) -> bool:
        """Fixed-cadence snapshots, independent of the best-metric gate."""
        return False

    def log_due(self, period: int) -> bool:
        """Whether this period boundary is a logging/printing point.  Epoch
        families log every epoch; the LM gates on its ``log_every`` cadence
        so eval/save boundaries don't add extra log rows."""
        return True

    def wait_for_saves(self) -> None:
        return None

    def _snapshot_store(self) -> tuple | None:
        """``(checkpoint_dir, job_id)`` when this trainer checkpoints,
        else None — the handle the rollback template walks for valid
        snapshots.  Families with checkpointing override; the default
        keeps checkpoint-less runs on the halt path."""
        return None

    def _rebuild_step_fns(self) -> None:
        """Rebuild the compiled step functions after the optimizer wrap
        changed (grace entry/exit, ``recovery.scale_tx``).  Default
        no-op for stubs/tests."""

    def _rollback_restore(self, epoch: int) -> None:
        """Restore ``self.state`` from the (already-verified) snapshot
        ``epoch`` and rewind the family's resume cursor."""
        raise NotImplementedError

    # one agreement key per in-loop rollback this process performs: the
    # NaN-recovery path is SPMD-identical across hosts (every host sees
    # the same non-finite loss at the same period), so the counter
    # advances in lockstep and scopes each rollback's rank-0 agreement
    _rollback_seq = 0

    def rollback_to_snapshot(self) -> bool:
        """Restore the latest *valid* snapshot and rewind the resume
        cursor; return False when there is nothing to roll back to.

        On a pod, WHICH snapshot is the rollback target is a rank-0
        agreement (``coord.agreed_rollback_epoch``), not a per-host
        ``latest_valid_epoch`` walk: under a torn NAS view (host A sees
        snapshot 12 committed, host B still sees 11) per-host choices
        diverge and the restored worlds silently fork."""
        store = self._snapshot_store()
        if store is None:
            return False
        self.wait_for_saves()  # commit any in-flight async snapshot first
        from ddl_tpu import checkpoint as ckpt
        from ddl_tpu import coord

        seq = self._rollback_seq
        self._rollback_seq = seq + 1
        epoch = coord.agreed_rollback_epoch(
            store[1], lambda: ckpt.latest_valid_epoch(*store), seq
        )
        if epoch is None:
            return False
        self._rollback_restore(epoch)
        print(f"[recovery] restored snapshot {epoch}")
        return True

    def _gc_snapshots(self) -> None:
        """Keep-last-K snapshot GC after a save (no-op unless the family
        checkpoints and ``keep_snapshots`` > 0).  Only the logging
        process prunes — every host shares the snapshot store."""
        store = self._snapshot_store()
        if (
            not self.keep_snapshots
            or store is None
            or not getattr(self, "is_logging_process", True)
        ):
            return
        from ddl_tpu import checkpoint as ckpt

        protect = (
            (self.best_snapshot_epoch,)
            if self.best_snapshot_epoch is not None else ()
        )
        for path, reason in ckpt.gc_snapshots(
            *store, keep=self.keep_snapshots, protect=protect
        ):
            print(f"[gc] removed snapshot {path}: {reason}")

    def set_update_scale(self, scale: float) -> None:
        """Scale subsequent optimizer updates by ``scale`` (the
        reduced-LR grace after a rollback): one step-function rebuild
        per dial turn, state-tree-identical (``recovery.scale_tx``)."""
        if scale == self.update_scale:
            return
        self.update_scale = scale
        self._rebuild_step_fns()

    def _note_io_retry(self, exc: BaseException, attempt: int) -> None:
        """Data-loader retry callback: count transient-I/O retries into
        the obs event stream so a degrading NAS is visible before it
        becomes an outage."""
        self.io_retries = getattr(self, "io_retries", 0) + 1
        if self.obs is not None:
            self.obs.writer.emit(
                "io_retry", error=str(exc), attempt=attempt
            )

    def _init_obs(self, log_dir, job_id: str, family: str) -> None:
        """Shared trainer wiring for the structured event stream (every
        host writes its own file; obs/events.py).  No-op without a log
        dir, so the obs story tracks the CSV one.

        File attribution goes through ``launch.host_id`` — the launcher
        env (``DDL_HOST_ID``/``DDL_PROCESS_ID``) wins over the JAX
        process index.  Identical on a real multihost pod, but sim-pod
        children are each JAX process 0 and must not merge into one
        stream (``obs pod`` attributes skew by stream)."""
        if log_dir:
            from ddl_tpu.launch import host_id
            from ddl_tpu.obs import StepTrace

            self.obs = StepTrace.create(log_dir, job_id, family, host=host_id())
            # warm-restart observability: one compile_cache event per
            # incarnation (no-op when the persistent cache is off) — the
            # warm-relaunch drill reads warm/entries_before next to
            # restart_latency and the recompile goodput bucket
            from ddl_tpu.utils.compile_cache import emit_cache_event

            emit_cache_event(self.obs.writer)

    def _emit_snapshot_restore(
        self, dur: float, epoch, period: int, offset: int = 0
    ) -> None:
        """One ``snapshot_restore`` event per startup restore: how long
        the restore took (the goodput ledger's ``checkpoint`` bucket —
        today only the in-loop save is a traced phase) plus the resume
        cursor the restored state represents (``period``/``offset``),
        from which the ledger charges a prior incarnation's periods
        beyond the cursor as rolled-back (replayed) work.  Families call
        it right after their startup restore; the in-loop rollback path
        stays on the ``rollback`` event instead (emitting both would
        double-charge the replay)."""
        if self.obs is None:
            return
        self.obs.writer.emit(
            "snapshot_restore",
            dur=dur,
            epoch=epoch,
            period=int(period),
            offset=int(offset),
        )
        # the restored state is the startup-resident memory: account it
        # before the first period's sample (the ledger's restore column)
        self._emit_hbm_sample(context="restore")

    def _emit_pipe_schedule(
        self, schedule: str, pipe: int, microbatches: int, virtual: int = 1
    ) -> None:
        """One ``pipe_schedule`` event per run when pipeline parallelism
        is active: the schedule's identity plus the modeled per-stage
        F/B/W/idle accounting (``obs/schedule_model.py``).  The schedule
        is static for the whole run, so one event suffices — ``obs
        trace --step`` recomputes the lanes from these parameters and
        scales them into any step's measured window, and ``obs
        summarize`` renders the bubble line.  Combinations the model
        does not cover (interleaved 1F1B) emit the identity fields with
        the modeled ones null."""
        if self.obs is None or pipe <= 1:
            return
        from ddl_tpu.obs.schedule_model import schedule_summary

        try:
            summ = schedule_summary(schedule, pipe, microbatches, virtual)
        except ValueError:
            summ = {}
        self.obs.writer.emit(
            "pipe_schedule",
            schedule=schedule,
            pipe=pipe,
            microbatches=microbatches,
            virtual=virtual,
            makespan=summ.get("makespan"),
            idle_units=summ.get("idle_units"),
            bubble_fraction=summ.get("bubble_fraction"),
            per_stage=summ.get("per_stage"),
        )

    @property
    def best_label(self) -> str:
        return (self.best_metric or "metric").upper()

    def resume_hint(self, period: int) -> str:
        return f"job_id={self.job_id} {self.period_label.lower()}={period}"

    def format_train_line(
        self, period: int, elapsed: float, steps: int, metrics: dict
    ) -> str:
        body = " | ".join(f"{k}: {v:.4f}" for k, v in metrics.items())
        return (
            f"{self.period_label} {period} | Time: {elapsed:.2f}s | "
            f"Steps: {steps} | {body}"
        )

    def format_eval_line(self, period: int, metrics: dict) -> str:
        body = " | ".join(f"{k}: {v:.4f}" for k, v in metrics.items())
        return f"{self.period_label} {period} | {body}"

    def log_index(self, period: int) -> int:
        """CSV 'epoch' column for this period (LM maps periods to steps)."""
        return period

    # ------------------------------------------------------------- gating

    def _improved(self, eval_metrics: dict | None) -> bool:
        if (
            not self.save_best
            or self.best_metric is None
            or not eval_metrics
            or self.best_metric not in eval_metrics
        ):
            return False
        value = float(eval_metrics[self.best_metric])
        better = value > self.best_value if self.best_mode == "max" else (
            value < self.best_value
        )
        if better:
            self.best_value = value
            print(f"New Best Validation {self.best_label}: {value:.4f}")
        return better

    # ---------------------------------------------------------- the loop

    def train(self, max_periods: int | None = None, guard=None) -> None:
        from ddl_tpu.utils.preemption import PreemptionGuard

        if guard is None and self.preemption_save:
            # enter the loop directly (not through self.train) so family
            # overrides wrapping train() run exactly once
            with PreemptionGuard() as installed:
                return self._train_loop(max_periods, installed)
        return self._train_loop(max_periods, guard)

    def _train_loop(self, max_periods: int | None, guard) -> None:
        max_periods = max_periods or self.num_periods
        obs = self.obs
        watchdog = None
        if obs is not None:
            # the env var is the operator OVERRIDE (set it to raise the
            # deadline past a long first compile, or to 0 to disable),
            # so it wins over a family-set watchdog_s
            env = os.environ.get("DDL_WATCHDOG_S")
            if env not in (None, ""):
                deadline = float(env)
            else:
                deadline = self.watchdog_s or 0
            if deadline > 0:
                from ddl_tpu.obs.watchdog import Watchdog

                # under supervision (DDL_SUPERVISED) the supervisor sets
                # DDL_WATCHDOG_ACTION=exit: stall -> dump stacks -> exit
                # resumable -> relaunch, instead of hanging forever
                action = os.environ.get("DDL_WATCHDOG_ACTION", "dump")
                watchdog = Watchdog(
                    obs.writer, deadline, on_stall=action,
                    capturer=obs.capturer,
                ).start()
                obs.watchdog = watchdog
        try:
            self._run_periods(max_periods, guard, obs)
        except Exception as exc:
            # allocation failure: dump the forensic memory snapshot
            # (resident buffers + the plans that predicted them) into
            # the event stream before the process dies — the memory
            # analogue of the watchdog's stack dump
            if obs is not None:
                from ddl_tpu.obs import hbm

                if hbm.is_oom_error(exc):
                    hbm.dump_oom(
                        obs.writer, exc,
                        params_bytes=self.param_hbm_bytes(),
                        opt_bytes=self.opt_state_hbm_bytes(),
                    )
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
            if obs is not None:
                obs.finish(verbose=getattr(self, "is_logging_process", True))

    def _run_periods(self, max_periods: int, guard, obs) -> None:
        # Profile one post-warmup period when configured (the reference's
        # only timing is perf_counter epoch walls, single.py:171-174; this
        # captures a full XLA device trace instead).
        profile_period = None
        if self.profile_dir:
            profile_period = min(self.periods_run + 1, max_periods - 1)
        # a while over the resume cursor, not a for over a frozen range:
        # the recovery policy's rollback rewinds periods_run mid-run
        while self.periods_run < max_periods:
            period = self.periods_run
            if period == profile_period:
                jax.profiler.start_trace(self.profile_dir)
            if obs is not None:
                obs.begin_period()
            start = perf_counter()
            # where this period's data stream starts (nonzero only for
            # the first period after an exact mid-period resume) — a
            # preemption cursor must record skip + steps, not just steps
            offset_base = self._resume_offset
            train_metrics, steps = self.run_period(period, guard)
            elapsed = perf_counter() - start
            if period == profile_period:
                jax.profiler.stop_trace()
                self._print_profile_digest()
            train_metrics = faultinject.poison_loss(train_metrics)
            loss = train_metrics.get("loss")
            idx = self.log_index(period)
            # one rate_metrics call per period, shared by the CSV rows
            # and the period obs event (the fleet rollup reads MFU and
            # the family throughput rates from the event stream)
            rates = self.rate_metrics(steps, elapsed)
            opt_hbm = self.opt_state_hbm_bytes()
            if opt_hbm:
                rates.setdefault("opt_hbm_bytes", opt_hbm)
            if loss is not None and not np.isfinite(loss):
                handled = self._handle_nonfinite(period, idx, loss, obs)
                if handled:
                    # the bad period is not logged/evaluated/snapshotted;
                    # its period event still flows (the obs stream must
                    # show the excursion, not hide it)
                    if obs is not None:
                        obs.end_period(
                            period, idx, elapsed, steps, train_metrics,
                            rates=rates, offset=offset_base,
                        )
                    if guard is not None and guard.requested:
                        # preempted mid-recovery: exit inside the grace
                        # window NOW, without snapshotting the poisoned
                        # period — the relaunch resumes from the last
                        # good snapshot
                        self.preempted = True
                        self.wait_for_saves()
                        print(
                            f"Preempted during non-finite-loss recovery "
                            f"at {self.period_label.lower()} {period}; "
                            f"exiting without snapshotting the poisoned "
                            f"period. Last good snapshot: "
                            f"{self.last_snapshot_hint()}"
                        )
                        return
                    continue
                if self.halt_on_nan:
                    raise RuntimeError(
                        f"Non-finite training loss {loss} at "
                        f"{self.period_label.lower()} {period}; halting. "
                        f"Last snapshot: {self.last_snapshot_hint()}"
                    )
            elif self.recovery is not None and self.recovery.on_finite():
                self.set_update_scale(1.0)
                print(
                    "[recovery] grace window over; update scale back to 1.0"
                )
            if self.log_due(period):
                with _phase(obs, "logging", step=idx):
                    print(
                        self.format_train_line(
                            period, elapsed, steps, train_metrics
                        )
                    )
                    if self.logger is not None and self.is_logging_process:
                        self.logger.log_many(train_metrics, idx)
                        self.logger.log(self.time_metric, elapsed, idx)
                        # steps/sec/chip is BASELINE.json's target metric;
                        # the reference only logs epoch_time (steps derived
                        # offline).
                        self.logger.log("steps_per_sec", steps / elapsed, idx)
                        self.logger.log_many(rates, idx)
                        # HBM watermark (no reference analog; utils/memory.py)
                        mem = hbm_stats()
                        if mem is not None:
                            self.logger.log(
                                "hbm_peak_bytes", mem["peak_bytes_in_use"], idx
                            )

            with _phase(obs, "eval", step=idx):
                eval_metrics = self.evaluate_period(period)
            if eval_metrics:
                with _phase(obs, "logging", step=idx):
                    print(self.format_eval_line(period, eval_metrics))
                    if self.logger is not None and self.is_logging_process:
                        self.logger.log_many(eval_metrics, idx)

            improved = self._improved(eval_metrics)
            if improved or self.snapshot_due(period):
                with _phase(obs, "checkpoint", step=idx):
                    # a boundary save: the period's data is fully consumed
                    self.data_cursor = {"period": period + 1, "offset": 0}
                    self.save_snapshot(period)
                    if improved:
                        # idx is the snapshot's store key in every
                        # family (epoch for CNN/ViT, the boundary step
                        # for the LM — the same mapping save_snapshot
                        # uses); GC must never reap the best model
                        self.best_snapshot_epoch = idx
                    self._gc_snapshots()
            preempted = guard is not None and guard.requested
            if preempted:
                # Preempted (SIGTERM): checkpoint what we have and exit
                # cleanly; the partially-trained period is saved under its
                # own number, so the relaunch resumes at the next one.
                # Save BEFORE end_period so the blocking final commit —
                # the interesting cost of a preempted run — lands in this
                # period's checkpoint phase total.
                with _phase(obs, "checkpoint", step=idx):
                    # a mid-period save: record how far into the period's
                    # data stream the state got, so the resumed run
                    # re-enters THIS period at that offset instead of
                    # skipping the period's remaining batches
                    self.data_cursor = {
                        "period": period, "offset": offset_base + steps
                    }
                    self.save_snapshot(period)
                    self.wait_for_saves()
                    self._gc_snapshots()
            if obs is not None:
                obs.end_period(
                    period, idx, elapsed, steps, train_metrics,
                    rates=rates, offset=offset_base,
                )
                # HBM ledger: one live per-category breakdown per period
                # beside the period event's bare watermark (obs/hbm.py)
                self._emit_hbm_sample(step=idx)
            self.periods_run = period + 1
            if preempted:
                self.preempted = True
                print(
                    f"Preempted at {self.period_label.lower()} {period}; "
                    f"snapshot committed. Resume with {self.resume_hint(period)}"
                )
                return
        self.wait_for_saves()

    def _print_profile_digest(self) -> None:
        """Render the captured period's per-op digest right at the run
        (the ROADMAP's "open every perf PR with a digest" rule: the
        trainer's own ``profile_dir`` hook now hands over the top-op
        table instead of a bare trace directory — same renderer as
        ``ddl_tpu bench digest``).  Digest failures never cost the run."""
        if not getattr(self, "is_logging_process", True):
            return
        try:
            from ddl_tpu.bench.xprof import op_digest

            dig = op_digest(self.profile_dir, top=5)
            ops = "  ".join(
                f"{k}={v:.1f}ms" for k, v in dig["ops"].items()
            )
            print(
                f"[profile] trace {self.profile_dir}: "
                f"total {dig['total_ms']:.1f}ms — {ops}"
            )
            print(
                f"[profile] full table: ddl_tpu bench digest "
                f"{self.profile_dir}"
            )
        except Exception as e:  # ddl-lint: disable=broad-except — a
            # digest render failure (exotic trace layout, missing plane)
            # must never kill a training run; the trace itself is already
            # on disk and the message points at it
            print(f"[profile] digest unavailable ({e}); trace in "
                  f"{self.profile_dir}")

    def _handle_nonfinite(self, period, idx, loss, obs) -> bool:
        """Recovery-policy reaction to a non-finite period loss; returns
        True when the policy absorbed it (skip or rollback), False to
        fall through to halt_on_nan."""
        if self.recovery is None:
            return False
        pol = self.recovery
        action = pol.on_nonfinite()
        if obs is not None:
            obs.anomaly.record(
                idx,
                "nonfinite_loss",
                value=float(loss),
                consecutive=pol.consecutive,
                action=action,
            )
        label = self.period_label.lower()
        if action == "skip":
            print(
                f"[recovery] non-finite loss ({loss}) at {label} {period}: "
                f"skipping the period "
                f"({pol.consecutive}/{pol.max_consecutive} consecutive)"
            )
            self.periods_run = period + 1
            return True
        if pol.rollbacks >= pol.max_rollbacks:
            raise RuntimeError(
                f"Non-finite training loss persisted through "
                f"{pol.rollbacks} rollback(s); giving up. "
                f"Last snapshot: {self.last_snapshot_hint()}"
            )
        restore_t0 = perf_counter()
        if not self.rollback_to_snapshot():
            raise RuntimeError(
                f"Non-finite training loss for {pol.consecutive} "
                f"consecutive {label}s and no snapshot to roll back to. "
                f"Last snapshot: {self.last_snapshot_hint()}"
            )
        hits = pol.consecutive
        pol.on_rollback()
        self.set_update_scale(pol.grace_scale)
        if obs is not None:
            # period: the bad period in PERIOD units (step=idx is the
            # CSV/log index, a step number for the LM family) — the
            # goodput ledger charges the rolled-back periods >= resumed_at
            # plus this pending bad one as replayed work; restore_dur
            # books the rollback restore into the checkpoint bucket
            obs.writer.emit(
                "rollback",
                step=idx,
                period=period,
                resumed_at=self.periods_run,
                restore_dur=perf_counter() - restore_t0,
                grace_scale=pol.grace_scale,
                grace_periods=pol.grace_periods,
            )
        print(
            f"[recovery] non-finite loss for {hits} consecutive {label}s: "
            f"rolled back to {label} {self.periods_run}; reduced-LR grace "
            f"x{pol.grace_scale} for {pol.grace_periods} {label}(s)"
        )
        return True

    def last_snapshot_hint(self):
        return "none"
