"""LM-family trainer: the transformer LM on the shared training loop.

Round 1-2 trained this family from a bespoke loop in ``examples/train_lm.py``
— 380 lines re-implementing stepping, logging, eval, and checkpointing,
*without* the aux subsystems the CNN Trainer has (no preemption guard, no
NaN watchdog, no profiler hook, opt-in CSV).  That reproduced the
per-script-trainer defect SURVEY.md §1 documents in the reference
(``single.py:92-269`` vs ``ddp.py:102-326``).  This module puts the
flagship family on ``train/loop.BaseTrainer`` instead: SIGTERM now leaves
a resumable snapshot, NaN halts with a pointer at the last good one, CSV
observability is default-on, and ``examples/train_lm.py`` shrinks to an
argparse shim.

The LM is step-based, not epoch-based, so a loop *period* here is a step
window ending at the next cadence boundary — the union of the logging,
eval, and snapshot cadences' multiples — so each cadence fires exactly at
its own multiples (no more, no less; coprime cadences do not collapse the
window to one step).  The CSV 'epoch' column carries the global step at
the period end; per-window walls log as ``window_time`` while
``epoch_time`` keeps its whole-run meaning for cross-family aggregation
(``bench/analysis.epoch_time_per_job``).
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import os
from time import perf_counter

import jax
import jax.numpy as jnp
import numpy as np

from ddl_tpu import checkpoint as ckpt
from ddl_tpu.models.transformer import LMConfig
from ddl_tpu.obs.steptrace import stage
from ddl_tpu.parallel.sharding import LMMeshSpec
from ddl_tpu.train.lm_steps import STEP_PARTS, make_lm_step_fns
from ddl_tpu.train.loop import BaseTrainer, _child, _phase
from ddl_tpu.utils import MetricLogger, faultinject

__all__ = ["LMRunConfig", "LMTrainer"]


@dataclasses.dataclass
class LMRunConfig:
    """Run-level settings for the LM family (model/mesh live in
    ``LMConfig`` / ``LMMeshSpec``; this is everything else the old bespoke
    loop took from the command line)."""

    batch: int = 16
    seq_len: int = 256
    steps: int = 100
    num_microbatches: int = 0
    accum_steps: int = 1
    # "gpipe" | "1f1b" | "zb" (parallel/rules.PIPELINE_SCHEDULES): zb is
    # the zero-bubble B/W-split 1F1B — weight grads deferred into the
    # cooldown ticks; requires virtual_stages == 1
    pipeline_schedule: str = "gpipe"
    virtual_stages: int = 1
    # ZeRO-1 optimizer-state sharding over 'data' (requires a fused Adam
    # tx and the flat step path — see TrainConfig.zero_sharding)
    zero_sharding: bool = False
    # data: token corpus path (.npy or raw text; encoded on first use) or
    # None for the synthetic Markov-chain byte stream
    corpus: str | None = None
    eval_every: int = 0  # held-out eval cadence in steps (0 = off)
    eval_frac: float = 0.05  # tail fraction of corpus windows held out
    checkpoint_dir: str | None = None
    save_every: int = 50  # snapshot cadence in steps
    # keep only the newest K valid snapshots (0 = all); corrupt ones
    # never count toward K — see checkpoint.gc_snapshots
    keep_snapshots: int = 0
    resume_step: int | None = None
    # With no explicit resume_step, continue from this job id's latest
    # snapshot automatically when one exists (relaunch == resume).
    auto_resume: bool = True
    job_id: str = "lm"
    log_dir: str | None = "training_logs"  # default-on CSV observability
    log_every: int = 10  # console/CSV cadence in steps
    halt_on_nan: bool = True
    # Non-finite-loss policy: "halt" (round-1 behaviour, honors
    # halt_on_nan) or "recover" (skip the bad window; after
    # nan_max_consecutive hits, roll back to the latest valid snapshot
    # with a reduced-LR grace window — train/recovery.RecoveryPolicy).
    nan_policy: str = "halt"
    nan_max_consecutive: int = 3
    nan_grace_scale: float = 0.1
    nan_grace_periods: int = 2
    preemption_save: bool = True
    profile_dir: str | None = None


class LMTrainer(BaseTrainer):
    period_label = "window"
    time_metric = "window_time"  # epoch_time logs once, as whole-run wall
    best_metric = "val_ppl"
    best_mode = "min"
    best_label = "PPL"

    def __init__(
        self,
        cfg: LMConfig,
        spec: LMMeshSpec,
        tx,
        run: LMRunConfig,
        rng: jax.Array | None = None,
    ) -> None:
        self.cfg, self.spec, self.run = cfg, spec, run
        self.job_id = run.job_id
        self._rng = rng if rng is not None else jax.random.key(0)
        self.tx = tx
        with stage("setup.model", self.obs):
            self.fns = self._make_fns(cfg)

        # periods end at the union of the cadences' multiples, so each
        # cadence fires exactly at its own multiples (log 10 / eval 4 ->
        # boundaries 4, 8, 10, 12, ...) and coprime cadences never
        # collapse the window to single steps
        if run.log_every < 1:
            raise ValueError(f"log_every must be >= 1, got {run.log_every}")
        cadences = [run.log_every]
        if run.eval_every:
            cadences.append(run.eval_every)
        if run.checkpoint_dir and run.save_every:
            cadences.append(run.save_every)
        bounds = {run.steps}
        for c in cadences:
            bounds.update(range(c, run.steps + 1, c))
        self._boundaries = sorted(bounds)
        self.num_periods = len(self._boundaries)

        with stage("setup.data", self.obs):
            self._build_data()

        proc = jax.process_index()
        self.is_logging_process = proc == 0
        self.logger = (
            MetricLogger(run.log_dir, run.job_id, global_rank=proc,
                         local_rank=proc)
            if run.log_dir
            else None
        )
        self._init_obs(run.log_dir, run.job_id, "lm")
        self._emit_pipe_schedule(
            run.pipeline_schedule, self.spec.pipe,
            run.num_microbatches or self.spec.pipe, run.virtual_stages,
        )
        self.halt_on_nan = run.halt_on_nan
        from ddl_tpu.train.recovery import make_policy

        self.recovery = make_policy(run)
        self.keep_snapshots = run.keep_snapshots
        self.preemption_save = run.preemption_save
        self.profile_dir = run.profile_dir
        self.save_best = bool(run.checkpoint_dir) and bool(run.eval_every)
        self.best_value = float("inf")

        with stage("setup.model", self.obs):
            self.state = self.fns.init_state()
        self._start_step = 0
        resume_step = ckpt.resolve_resume(
            run.checkpoint_dir, run.job_id, run.resume_step,
            run.auto_resume, unit="step",
        )
        restore_dur = None
        if run.checkpoint_dir and resume_step is not None:
            from time import perf_counter

            t0 = perf_counter()
            # cross-LAYOUT resume is handled inside _resume; what fails
            # here is a genuinely different model config
            ckpt.run_resume_load(
                lambda: self._resume(resume_step),
                auto=run.resume_step is None,
                desc=f"job {run.job_id!r} step {resume_step}",
                hint="pass --fresh (auto_resume=False)",
            )
            restore_dur = perf_counter() - t0
        # first period whose boundary lies beyond the resume step
        self.periods_run = bisect.bisect_right(
            self._boundaries, self._start_step
        )
        if restore_dur is not None:
            # offset: steps into the resume window already covered by
            # the snapshot (LM periods are step windows, so a step-keyed
            # resume inside a window is the mid-period-cursor analog).
            # Also seed the loop's period-event offset with it, so the
            # resumed window's event states the slice it describes —
            # what the goodput ledger's replay charging compares resume
            # cursors against (_period_bounds already resumes by
            # _start_step; run_period just consumes the one-shot value)
            window_start = (
                self._boundaries[self.periods_run - 1]
                if self.periods_run else 0
            )
            self._resume_offset = max(
                0, self._start_step - window_start
            )
            self._emit_snapshot_restore(
                restore_dur, resume_step, self.periods_run,
                self._resume_offset,
            )

    def _make_fns(self, cfg: LMConfig):
        run = self.run
        from ddl_tpu.train.recovery import scale_tx

        return make_lm_step_fns(
            cfg, self.spec, scale_tx(self.tx, self.update_scale), self._rng,
            run.batch, run.seq_len,
            num_microbatches=run.num_microbatches,
            accum_steps=run.accum_steps,
            pipeline_schedule=run.pipeline_schedule,
            virtual_stages=run.virtual_stages,
            zero_sharding=run.zero_sharding,
        )

    def _rebuild_step_fns(self) -> None:
        self.fns = self._make_fns(self.cfg)

    def _snapshot_store(self):
        run = self.run
        return (run.checkpoint_dir, run.job_id) if run.checkpoint_dir else None

    def _rollback_restore(self, step: int) -> None:
        run = self.run
        self.state, _ = ckpt.load_snapshot(
            run.checkpoint_dir, run.job_id, step, self.state, verify=False
        )
        self._start_step = int(self.state.step)
        self._anchor_shuffle(step)
        self.periods_run = bisect.bisect_right(
            self._boundaries, self._start_step
        )

    def _maybe_anneal_capacity(self, m: dict) -> None:
        """Post-warm-up MoE capacity anneal, keyed off the LIVE router
        drop fraction: once ``moe_drop_frac`` falls under
        ``cfg.capacity_anneal_drop`` the warm-up headroom
        (``capacity_factor``) is pure overhead — drop to
        ``capacity_factor_min`` and rebuild the step functions (one
        recompile; params/optimizer state are capacity-independent, so
        the train state carries over untouched).  See LMConfig's
        capacity_factor_min docs for the measured warm-up/steady-state
        numbers."""
        cfg = self.cfg
        if not cfg.num_experts or cfg.moe_dropless:
            return  # no experts, or a dropless layer: no capacity to anneal
        target = min(cfg.capacity_factor_min, cfg.capacity_factor)
        if cfg.capacity_factor <= target:
            return
        step = int(self.state.step)
        drop = m.get("moe_drop_frac")
        by_metric = drop is not None and drop <= cfg.capacity_anneal_drop
        # step-count fallback: the pipeline path doesn't surface the live
        # drop metric (router stats sown inside the manual pipe region)
        by_step = (
            cfg.capacity_anneal_step and step >= cfg.capacity_anneal_step
        )
        if not (by_metric or by_step):
            return
        reason = (
            f"router drop_frac {drop:.4f} <= {cfg.capacity_anneal_drop}"
            if by_metric
            else f"step {step} >= capacity_anneal_step "
                 f"{cfg.capacity_anneal_step}"
        )
        import dataclasses as _dc

        self.cfg = _dc.replace(cfg, capacity_factor=target)
        self.fns = self._make_fns(self.cfg)
        if self.is_logging_process:
            print(
                f"step {step:4d} | capacity anneal: {reason} — "
                f"capacity_factor {cfg.capacity_factor} -> {target} "
                "(one-time recompile)"
            )

    # ------------------------------------------------------------- data

    def _build_data(self) -> None:
        run = self.run
        self._eval_batches = None
        self._batches = None  # TokenBatches on the corpus path, for
        # shuffle-cursor persistence (save_snapshot/_anchor_shuffle)
        n_proc, proc = jax.process_count(), jax.process_index()
        self._n_proc = n_proc
        if run.corpus:
            # real corpus: memmapped token windows, host-sharded per
            # process; each process loads 1/n_proc of the global batch and
            # the shards are assembled into one global jax.Array
            from ddl_tpu.data.lm_corpus import (
                TokenBatches,
                TokenCorpus,
                encode_text_file,
            )

            if run.batch % n_proc:
                raise ValueError(
                    f"batch {run.batch} must divide by process count {n_proc}"
                )
            path = run.corpus
            if not path.endswith(".npy"):
                npy = path + ".npy"
                stale = not os.path.exists(npy) or (
                    os.path.getmtime(npy) < os.path.getmtime(path)
                )
                if stale and proc == 0:  # encode once, one writer
                    encode_text_file(path, npy)
                if n_proc > 1:
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices("corpus_encode")
                path = npy
            corpus = TokenCorpus(path, run.seq_len)
            if corpus.max_token() >= self.cfg.vocab_size:
                raise ValueError(
                    f"corpus has token id {corpus.max_token()} but the "
                    f"model's vocab_size is {self.cfg.vocab_size}; "
                    "out-of-range ids would be silently clamped by the "
                    "embedding gather"
                )
            eval_view = None
            if run.eval_every:
                train_view, ev = corpus.split(run.eval_frac)
                if len(ev) >= run.batch:
                    eval_view = ev
                else:
                    # too small to fill one batch: keep every window
                    print(
                        f"note: eval split ({len(ev)} windows) smaller than "
                        f"one batch of {run.batch}; held-out eval disabled — "
                        "grow eval_frac or shrink batch"
                    )
                    train_view = corpus
            else:
                train_view = corpus
            batches = TokenBatches(
                train_view, run.batch // n_proc, n_proc, proc, seed=0
            )
            self._batches = batches
            self._eval_batches = (
                TokenBatches(eval_view, run.batch // n_proc, n_proc, proc,
                             shuffle=False, seed=0)
                if eval_view is not None
                else None
            )
            print(
                f"corpus: {len(corpus)} windows of {run.seq_len}+1 tokens, "
                f"{len(batches)} train batches/epoch/host"
                + (f", {len(self._eval_batches)} eval batches"
                   if self._eval_batches else "")
            )
            self._gspec = None
            if n_proc > 1:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                self._gspec = NamedSharding(
                    self.fns.mesh, P(("data", "expert"), "seq")
                )

            def sample_batch(step):
                # pure in step -> a resumed run continues the stream exactly
                inp, tgt = batches.batch_at(step)
                return self._to_global(inp), self._to_global(tgt)

        else:
            # synthetic corpus: byte sequences from a fixed order-1 Markov
            # chain — learnable structure with a known entropy floor
            # (shared with generate_lm.py via ddl_tpu.data.synthetic_lm)
            from ddl_tpu.data.synthetic_lm import MarkovChain

            if self.cfg.vocab_size < 256:
                raise ValueError(
                    f"synthetic Markov stream emits byte ids 0..255 but "
                    f"vocab_size is {self.cfg.vocab_size}; out-of-range "
                    "targets corrupt the loss — use vocab_size >= 256 or "
                    "pass a corpus"
                )
            chain = MarkovChain()

            def sample_batch(step):
                # seeded by step so a resumed run continues the stream
                # instead of re-consuming batches already trained on
                rng = np.random.default_rng(1000 + step)
                seqs = chain.sample(rng, run.batch, run.seq_len + 1)
                return jnp.asarray(seqs[:, :-1]), jnp.asarray(seqs[:, 1:])

        self._sample_batch = sample_batch

    def _to_global(self, x):
        # multi-host: assemble host shards into one global array
        if self._n_proc > 1:
            return jax.make_array_from_process_local_data(self._gspec, x)
        return jnp.asarray(x)

    # ----------------------------------------------------------- resume

    def _resume(self, resume_step: int) -> None:
        run = self.run
        from ddl_tpu.parallel.lm_pipeline import (
            saved_pipe_stages,
            saved_virtual_stages,
        )

        # The snapshot itself records its layout (pipe stages AND
        # interleaved virtual count) — no flag to get wrong.
        saved_md = ckpt.snapshot_metadata(
            run.checkpoint_dir, run.job_id, resume_step
        )
        saved_pipe = saved_pipe_stages(saved_md["state"]["params"])
        saved_virtual = saved_virtual_stages(saved_md["state"]["params"])
        # auto-discovered steps were integrity-verified by resolve_resume;
        # only an explicit --resume-step still needs the check here
        verify = run.resume_step is not None
        if saved_pipe == self.spec.pipe and saved_virtual == run.virtual_stages:
            self.state, _ = ckpt.load_snapshot(
                run.checkpoint_dir, run.job_id, resume_step, self.state,
                verify=verify,
            )
            print("resumed (snapshots are mesh-independent)")
        else:
            # Cross-layout resume: the snapshot was written with a
            # different pipe stage count (possibly none).  Restore through
            # an abstract skeleton of the saved layout (no init, no step
            # functions — the saved run's batch/mesh/flash settings are
            # irrelevant to the state tree), then restructure params +
            # optimizer state and re-place onto this run's mesh.
            from ddl_tpu.parallel.lm_pipeline import (
                abstract_lm_state,
                convert_lm_state,
            )

            restored, _ = ckpt.load_snapshot(
                run.checkpoint_dir, run.job_id, resume_step,
                abstract_lm_state(
                    self.cfg, self.tx, saved_pipe, mesh=self.fns.mesh,
                    virtual=saved_virtual,
                ),
                verify=verify,
            )
            if self.spec.pipe > 1:
                if saved_pipe > 1:  # restage: merge, then re-split below
                    restored = convert_lm_state(restored)
                self.state = convert_lm_state(
                    restored, n_stages=self.spec.pipe,
                    virtual=run.virtual_stages, like=self.state,
                )
            else:  # saved_pipe > 1 here (layouts differ): merge + place
                self.state = convert_lm_state(restored, like=self.state)
            print(
                f"resumed across layouts (saved pipe={saved_pipe} "
                f"virtual={saved_virtual} -> run pipe={self.spec.pipe} "
                f"virtual={run.virtual_stages})"
            )
        self._start_step = int(self.state.step)
        self._anchor_shuffle(resume_step)
        print(f"continuing from step {self._start_step}")

    def _anchor_shuffle(self, snap_step: int) -> None:
        """Re-anchor the corpus shuffle from the restored snapshot's
        cursor: the persisted (shuffle_epoch, epoch_pos) pins the epoch
        reshuffle trajectory across restarts — including elastic ones
        where the shard layout changed batches/epoch.  Pre-shuffle-cursor
        snapshots anchor nothing (divmod fallback, the old behaviour)."""
        if self._batches is None:
            return
        cur = ckpt.read_cursor(
            self.run.checkpoint_dir, self.run.job_id, snap_step
        )
        if cur and "shuffle_epoch" in cur:
            self._batches.anchor_resume(
                snap_step, cur["shuffle_epoch"], cur.get("epoch_pos", 0)
            )

    # ------------------------------------------------------- loop hooks

    def _period_bounds(self, period: int) -> tuple[int, int]:
        p0 = self._boundaries[period - 1] if period else 0
        return max(p0, self._start_step), self._boundaries[period]

    def run_period(self, period: int, guard=None):
        # one-shot: the resume offset only describes the FIRST resumed
        # window (the loop stamps it into that window's period event;
        # _period_bounds resumes by _start_step regardless)
        self.consume_resume_offset()
        p0, p1 = self._period_bounds(period)
        metrics, steps = {}, 0
        for i in range(p0, p1):
            # data_wait covers corpus sampling AND the host->device /
            # global-array assembly (they are one call here); step is the
            # compiled-step dispatch, whose hidden device time lands in
            # the period-end fence below
            with _phase(self.obs, "data_wait", step=i):
                inp, tgt = self._sample_batch(i)
            with _phase(self.obs, "step", step=i):
                self.state, m = self.fns.train(self.state, inp, tgt)
            if self.obs is not None:
                # one output that is not donated, for the idle account
                self.obs.note_dispatch(next(iter(m.values())))
            # HBM ledger: stamp the train step's static memory budget
            # once, after its first dispatch (obs/hbm.py hbm_plan)
            self.emit_hbm_plan("train_step", self.fns.train,
                               self.state, inp, tgt, parts=STEP_PARTS)
            steps += 1
            faultinject.check_step(i, guard)
            if guard is not None and guard.requested:
                break
        if steps:
            with _phase(self.obs, "fence", step=p0 + steps - 1):
                # the first metric's copy returns when the device has
                # drained; the others' copies (a round trip each) are host
                # time with the device idle.  Same copies, same order.
                items = iter(m.items())
                with _child(self.obs, "fence.drain", step=p0 + steps - 1):
                    metrics = {k: float(v) for k, v in [next(items)]}
                if self.obs is not None:
                    self.obs.device_drained()
                with _child(self.obs, "fence.d2h", step=p0 + steps - 1):
                    metrics.update((k, float(v)) for k, v in items)
            self._maybe_anneal_capacity(metrics)
        return metrics, steps

    def log_index(self, period: int) -> int:
        return self._period_bounds(period)[1]

    def log_due(self, period: int) -> bool:
        # log only at log_every multiples (and the final step), so eval and
        # snapshot boundaries don't densify the CSV/console cadence
        p1 = self._period_bounds(period)[1]
        return p1 % self.run.log_every == 0 or p1 == self.run.steps

    def format_train_line(self, period, elapsed, steps, m) -> str:
        p0, p1 = self._period_bounds(period)
        body = " ".join(f"{k} {v:.4f}" for k, v in m.items())
        return f"step {p1 - 1:4d} {body} ({steps / elapsed:.2f} steps/s)"

    def format_eval_line(self, period, m) -> str:
        return (
            f"  heldout: ce {m['val_loss']:.4f} ppl {m['val_ppl']:.2f}"
        )

    def rate_metrics(self, steps: int, elapsed: float) -> dict:
        tok_s = (steps / elapsed) * self.run.batch * self.run.seq_len
        out = {"tokens_per_sec": tok_s}
        u = self._mfu_estimate(tok_s)
        if u is not None:
            out["mfu"] = u
        return out

    def _mfu_estimate(self, tokens_per_sec: float) -> float | None:
        """Steady-state MFU from the 6ND estimate: ``6 * params *
        tokens/s`` achieved FLOP/s over the pod's peak dense bf16
        FLOP/s.  The analytic transformer train-step cost (fwd 2ND +
        bwd 4ND, attention-core excluded) — coarser than the bench's
        cost-analysis number but free every period, which is what the
        fleet rollup needs.  None off-TPU (peak unknown) — the metric
        is meaningless on the CPU sim."""
        import jax

        from ddl_tpu.bench.mfu import device_peak_flops

        peak = device_peak_flops()
        if peak is None or tokens_per_sec <= 0:
            return None
        if getattr(self, "_param_count", None) is None:
            self._param_count = sum(
                x.size for x in jax.tree_util.tree_leaves(self.state.params)
            )
        total_peak = peak * max(1, jax.device_count())
        return 6.0 * self._param_count * tokens_per_sec / total_peak

    def evaluate_period(self, period: int) -> dict | None:
        run = self.run
        p1 = self._period_bounds(period)[1]
        if (
            self._eval_batches is None
            or not run.eval_every
            or p1 % run.eval_every
        ):
            return None
        ces = []
        for e_inp, e_tgt in self._eval_batches:
            em = self.fns.evaluate(
                self.state, self._to_global(e_inp), self._to_global(e_tgt)
            )
            ces.append(float(em["ce"]))
        ce = float(np.mean(ces))
        return {"val_loss": ce, "val_ppl": math.exp(ce)}

    def snapshot_due(self, period: int) -> bool:
        if not self.run.checkpoint_dir or not self.run.save_every:
            return False
        return self._period_bounds(period)[1] % self.run.save_every == 0

    def save_snapshot(self, period: int) -> None:
        # label with the true optimizer step (preemption can end a period
        # early), so resume_step and the training stream line up exactly
        step = int(jax.device_get(self.state.step))
        # the LM data stream is keyed by global step (sample_batch is
        # pure in step), so step IS the exact-resume cursor; period/
        # offset ride along for the pod sim's no-dup/no-skip audit
        cursor = dict(self.data_cursor or {}, step=step)
        if self._batches is not None:
            # persist the shuffle trajectory too (epoch of the global
            # reshuffle + position within it), so a resume beyond one
            # corpus pass — or under a respec'd data axis, where
            # batches/epoch changed — reseeds the SAME permutation
            # sequence instead of re-deriving it from a divmod against
            # the new epoch length
            cursor.update(self._batches.cursor_state(step))
        path = ckpt.save_snapshot(
            self.run.checkpoint_dir, self.job_id, step, self.state,
            cursor=cursor,
        )
        print(f"step {step} | saved snapshot to {path}")

    def last_snapshot_hint(self):
        if not self.run.checkpoint_dir:
            return "none (set checkpoint_dir)"
        return ckpt.latest_epoch(self.run.checkpoint_dir, self.job_id)

    def resume_hint(self, period: int) -> str:
        step = int(jax.device_get(self.state.step))
        return f"--job-id {self.job_id} --resume-step {step}"

    # --------------------------------------------------------------- run

    def train(self, max_periods: int | None = None, guard=None) -> None:
        if self.run.checkpoint_dir is None and self.preemption_save:
            # nothing to save into: the guard would catch SIGTERM and then
            # fail in save_snapshot — run unguarded instead
            self.preemption_save = False
        t0 = perf_counter()
        super().train(max_periods, guard)
        dt = perf_counter() - t0
        steps_run = int(jax.device_get(self.state.step)) - self._start_step
        if steps_run:
            print(
                f"{steps_run} steps in {dt:.1f}s ({steps_run / dt:.2f} steps/s)"
            )
        if self.logger is not None and self.is_logging_process:
            # whole run as one epoch row, so epoch_time keeps the same unit
            # across families in bench/analysis.epoch_time_per_job
            self.logger.log("epoch_time", dt, 0)
