"""Per-step phase attribution for the shared training loop.

Splits each step/period of a run into a fixed phase vocabulary —

    data_wait    host-side batch production (loader / corpus sampling)
    h2d          host-to-device transfer + global-array assembly
    step         dispatch of the compiled train step
    fence        blocking on device completion / metric fetch
    eval         period-boundary evaluation
    checkpoint   snapshot writes
    logging      console + CSV emission

— as ``span`` events (``obs/events.py``), accumulated per period and
emitted as one ``period`` event carrying the phase-total breakdown,
throughput, recompile count (via ``jax.monitoring``'s backend-compile
duration events), and the HBM watermark (``utils/memory.hbm_stats``).
XLA dispatch is asynchronous, so ``step`` measures *dispatch* and the
device time it hides surfaces in ``fence`` — the two together bound the
compiled program; ``utils/timing.fence`` is the true-completion fence
behind the ``fence`` phase.

Beside the phases the trace keeps the program's own account of the idle
device, as child spans that are NOT phases (they go through
``writer.span`` alone: no period total, so ``obs goodput`` still sums):

    data_wait.idle  a ``data_wait`` / ``h2d`` phase that began with the
    h2d.idle        device known idle: the trainer hands ``note_dispatch``
                    one non-donated output of each step, and at a phase's
                    start its non-blocking ``is_ready()`` says whether the
                    device has finished all it was given.  Nothing is
                    dispatched inside these phases, so the device is idle
                    to their end.  A lower bound, exact to a phase: a
                    device that runs dry inside a phase shows at the next
                    (a ``step`` phase gets no child: a dispatch onto an
                    idle device launches the program somewhere inside the
                    call, and no host clock says where)
    fence.drain     the period-end copies up to the one whose return says
                    the device has drained (``device_drained``); its tail,
                    from the device's last op to that return, is idle time
                    no host clock can see
    fence.d2h       the copies after that, all with the device idle
    collate         one batch built on the loader's producer thread
                    (``collate_hook``, ``DataLoader(on_collate=)``)

A child is written when its parent phase's span is (``DDL_OBS_STEP_SPANS``:
0 turns every per-step span off, children and ``collate`` included, and
with them the ``is_ready()`` calls).  Every phase and child also enters a
``jax.profiler.TraceAnnotation`` (``step`` a ``StepTraceAnnotation``
besides), a flag test while no profiler session runs: a profile then holds
the program's phases and the device's ops on one clock.

Set-up has spans too, from the process's start to the first period.
They are not phases either (no period total), and they are measured
before a stream is open as well as after, so they go through the
process's ``CompileLog`` (``utils/compile_cache.py``), which keeps what it
is given until a ``StepTrace`` opens a stream and then writes through it:

    setup.boot      the process's start, by the kernel's record of it, to
                    the first stage of the first trainer: interpreter,
                    imports, the backend's client, whatever the caller
                    did first.  Once a process
    setup.model     step functions, model and state initialisation
    setup.data      datasets, corpus and loaders
    setup.plan      one program's ``hbm_plan`` (``label``): the second
                    lowering and compile, the compiled text, the scope
                    tables and their file (``BaseTrainer.emit_hbm_plan``)
    compile.trace   every trace, lowering and backend compile of the
    compile.lower   process (``fn``: JAX's name for the function), as
    compile.backend ``jax.monitoring`` times them; the last with
                    ``cache_hit`` and ``cache_load_s``.  Inside a stage
                    they name it as ``parent``; inside a ``step`` phase
                    they are a recompile with its function and its step

``period.compiles`` / ``compile_s`` count the ``compile.backend`` spans
of the period and their seconds.

``AnomalyMonitor`` rides along: every ``end_period`` feeds the rolling
detectors, and ``finish()`` surfaces everything they caught.
"""

from __future__ import annotations

import os
import time
from collections import defaultdict
from contextlib import ExitStack, contextmanager, nullcontext

from ddl_tpu.obs.anomaly import AnomalyMonitor
from ddl_tpu.obs.events import SOWN_COUNTERS, EventWriter

__all__ = ["PER_STEP_PHASES", "PHASES", "StepTrace", "stage"]

PHASES = (
    "data_wait",
    "h2d",
    "step",
    "fence",
    "eval",
    "checkpoint",
    "logging",
)

_relaunch_consumed = False


def _consume_relaunch_ts() -> float | None:
    """DDL_RELAUNCH_TS, handed out at most once per process (the first
    StepTrace built after a supervised relaunch owns the measurement)."""
    global _relaunch_consumed
    if _relaunch_consumed:
        return None
    raw = os.environ.get("DDL_RELAUNCH_TS")
    if not raw:
        return None
    _relaunch_consumed = True
    try:
        return float(raw)
    except ValueError:
        return None

# Phases that occur once per TRAINING STEP — the only ones the 1-in-N
# span sampler thins.  eval/checkpoint/logging fire once per period
# boundary (one write each, and a preemption's blocking checkpoint span
# is exactly what an incident review needs), so they always emit.
PER_STEP_PHASES = frozenset({"data_wait", "h2d", "step", "fence"})
# What the sampler thins: those phases, their children (``fence.drain``
# goes with ``fence``), and the loader thread's ``collate``.
PER_STEP_SPANS = PER_STEP_PHASES | {"collate"}
# The phases whose body dispatches nothing: one that begins with the
# device known idle keeps it idle to its end, and says so in a child.
IDLE_PHASES = frozenset({"data_wait", "h2d"})


_booted = False


@contextmanager
def stage(name: str, obs: "StepTrace | None" = None, **fields):
    """A ``setup.*`` stage of a trainer's start: a span through the
    process's ``CompileLog`` (written now if a stream is open, else kept
    for the next one) and a ``TraceAnnotation`` of the same name.  ``obs``
    is the trainer's own trace, None while it has opened no stream: its
    start is then kept for the stream it will open, whatever stream an
    earlier trainer of the process left open.  The process's first stage
    also writes ``setup.boot``, from the process's start to this stage's."""
    global _booted
    from jax.profiler import TraceAnnotation

    from ddl_tpu.utils.compile_cache import compile_log

    log = compile_log()
    if obs is None:
        log.detach()
    t0 = time.time()
    if not _booted:
        _booted = True
        from ddl_tpu.launch import process_start_ts

        born = process_start_ts()
        if born is not None:
            log.record("setup.boot", min(born, t0), t0)
    open_stages = log.stages()
    with TraceAnnotation(name):
        open_stages.append(name)
        try:
            yield
        finally:
            open_stages.pop()
            log.record(name, t0, time.time(), **fields)


class StepTrace:
    """The object a trainer threads through its loop.

    ``phase(name)`` is the single instrumentation primitive: a context
    manager that times the region, emits a ``span`` event, adds the
    duration to the current period's totals, and beats the watchdog
    (when one is attached) so the stall deadline bounds a phase, not a
    whole period.
    """

    def __init__(
        self,
        writer: EventWriter,
        anomaly: AnomalyMonitor | None = None,
        emit_step_spans: bool | int = True,
        capturer=None,
    ) -> None:
        self.writer = writer
        # profile-on-anomaly (obs/profiler.TraceCapturer, or None): armed
        # by the anomaly monitor, driven at step boundaries by phase()
        self.capturer = capturer
        if anomaly is None:
            anomaly = AnomalyMonitor(writer, capturer=capturer)
        elif capturer is not None and anomaly.capturer is None:
            anomaly.capturer = capturer
        self.anomaly = anomaly
        # span emission policy: False/0 = no per-step spans, True/1 =
        # every step, N > 1 = a 1-in-N sampler (steps where step % N == 0
        # emit their phase spans) — per-step visibility at 1/N of the
        # flushed-NAS-write cost on 10k-step periods.  Period events
        # (phase totals, throughput, anomalies) always flow.
        self.emit_step_spans = int(emit_step_spans)
        self.watchdog = None
        from ddl_tpu.utils.compile_cache import compile_log

        # the process's compiles: the counters a period's ``compiles`` /
        # ``compile_s`` are differences of, and the ``compile.*`` and
        # ``setup.*`` spans, which a stream that ``create`` opened writes
        self._compiles = compile_log()
        self._takes_spans = False
        self._open_step = None  # the open phase's step, for those spans
        self._period_compiles = self._compiles.count
        self._period_compile_s = self._compiles.secs
        self._totals: dict[str, float] = defaultdict(float)
        self.run_totals: dict[str, float] = defaultdict(float)
        self._needs_run_start = False  # set by finish() for train() reuse
        # restart-latency origin: the supervisor's relaunch-decision
        # wall clock (DDL_RELAUNCH_TS).  The first completed "step"
        # phase of this process emits one `restart_latency` event
        # against it — decision -> first step, the whole restart cost
        # (rendezvous, backoff, snapshot restore, recompile) in one
        # gateable number.  Consumed once per process, not per
        # StepTrace: a second train() segment is not a restart.
        self._relaunch_ts = _consume_relaunch_ts()
        # the idle account: the last dispatched step's output (not
        # donated) and whether the device is known to have finished
        # everything it was given
        self._last_out = None
        self._device_idle = False
        # only trainers build a StepTrace, so JAX is there; imported here
        # because the obs read path imports this module and no JAX
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        self._annotate, self._annotate_step = TraceAnnotation, StepTraceAnnotation

    @classmethod
    def create(
        cls,
        log_dir,
        job_id: str,
        family: str,
        host: int | None = None,
        emit_step_spans: bool | int | None = None,
        **writer_kwargs,
    ) -> "StepTrace":
        """One-line trainer wiring: build the writer, emit ``run_start``.

        ``emit_step_spans=None`` reads the ``DDL_OBS_STEP_SPANS`` env
        var — ``0``/``false`` disables per-step spans, an integer ``N``
        samples 1-in-N steps — the operator dial for runs where two
        flushed JSONL writes per step onto a NAS is real overhead
        (10k-step periods); period events (phase totals, throughput,
        anomalies) keep flowing either way.

        Profile-on-anomaly rides the same wiring: with ``DDL_OBS_PROFILE``
        set (``obs/profiler.py``), anomaly firings arm a rate-limited
        ``jax.profiler`` window over the next steps, and the resulting
        ``profile_capture`` event lands in this writer's stream."""
        if emit_step_spans is None:
            env = os.environ.get("DDL_OBS_STEP_SPANS", "").lower()
            if env in ("0", "false", "off"):
                emit_step_spans = 0
            elif env.isdigit():
                emit_step_spans = int(env)
            else:
                emit_step_spans = 1
        writer = EventWriter(log_dir, job_id, host=host, **writer_kwargs)
        writer.emit("run_start", family=family, job_id=job_id)
        from ddl_tpu.obs.profiler import capturer_from_env

        capturer = capturer_from_env(
            writer,
            writer.path.parent / "xprof" / f"h{writer.host:03d}",
        )
        trace = cls(writer, emit_step_spans=emit_step_spans, capturer=capturer)
        # a trainer's stream: it takes the process's set-up and compile
        # spans from now on, what was heard before it opened first
        trace._takes_spans = True
        trace._compiles.attach(trace)
        return trace

    def _span_due(self, name: str, step: int | None) -> bool:
        """The 1-in-N step-span sampler.  Only per-step phases are
        thinned (a child span goes with its parent, ``collate`` with the
        steps); period-boundary phases (eval/checkpoint/logging — one
        write per period, not the per-step cost the sampler bounds)
        follow the all-or-nothing setting regardless of their step tag."""
        n = self.emit_step_spans
        if n <= 0:
            return False
        if n == 1 or step is None or name.partition(".")[0] not in PER_STEP_SPANS:
            return True
        return step % n == 0

    def heard(self, name: str, start: float, end: float, fields: dict) -> None:
        """The ``CompileLog``'s sink: one ``setup.*`` or ``compile.*``
        span, timed where it happened.  Written unless per-step spans are
        off altogether; a compile inside a phase carries that phase's
        step."""
        if self.emit_step_spans > 0:
            self.writer.span_at(name, start, end, step=self._open_step, **fields)

    # ------------------------------------------------------------------
    # the idle account

    def note_dispatch(self, out) -> None:
        """The trainer's call right after it dispatched a train step:
        ``out`` is one output of that step that is not donated (the
        loss).  The device has work again."""
        self._last_out = out
        self._device_idle = False

    def device_drained(self) -> None:
        """The trainer's call when a blocking copy of the last step's
        output has returned: the device is idle, exactly."""
        self._last_out = None
        self._device_idle = True

    def _idle_now(self) -> bool:
        """Whether the device is known idle, asked without blocking.
        ``is_ready`` is a method of the concrete ``ArrayImpl``, not of
        ``jax.Array``: an output without it reads as unknown, never as
        idle."""
        if not self._device_idle and self._last_out is not None:
            ready = getattr(self._last_out, "is_ready", None)
            if ready is not None and ready():
                self.device_drained()
        return self._device_idle

    def _enter(self, stack: ExitStack, name: str, step, write: bool, **fields):
        """Enter ``name`` into the profiler's trace (a flag test while no
        session runs) and, if ``write``, into the event stream."""
        stack.enter_context(self._annotate(name, step=step))
        if write:
            stack.enter_context(
                self.writer.span(name, step=step, **fields)
            )

    def child(self, name: str, step: int | None = None):
        """A span under the open phase (``fence.drain``, ``fence.d2h``)
        that is not a phase: no period total, no watchdog beat.  Written
        when its parent's span is."""
        if not self._span_due(name, step):
            return nullcontext()
        stack = ExitStack()
        self._enter(stack, name, step, True)
        return stack

    def collate_hook(self, step_base: int):
        """The ``DataLoader(on_collate=)`` hook for one period: a
        ``collate`` span a batch from the producer thread, its step the
        period's first plus the batch's index.  None when per-step spans
        are off: no hook, no cost."""
        if self.emit_step_spans <= 0:
            return None
        return lambda batch: self.child("collate", step=step_base + batch)

    @contextmanager
    def phase(self, name: str, step: int | None = None, **fields):
        if (
            name == "step"
            and step is not None
            and self.capturer is not None
        ):
            # step boundary: start an armed profile window / close one
            # whose step budget is spent (obs/profiler.TraceCapturer)
            self.capturer.on_step(step)
        t0 = time.perf_counter()
        completed = False
        self._open_step = step
        try:
            with ExitStack() as stack:
                if name == "step" and step is not None:
                    stack.enter_context(self._annotate_step("train", step_num=step))
                due = self._span_due(name, step)
                self._enter(stack, name, step, due, **fields)
                if due and name in IDLE_PHASES and self._idle_now():
                    self._enter(stack, name + ".idle", step, True)
                yield
            completed = True
        finally:
            self._open_step = None
            dur = time.perf_counter() - t0
            self._totals[name] += dur
            self.run_totals[name] += dur
            if (
                completed
                and name == "step"
                and self._relaunch_ts is not None
            ):
                # first COMPLETED step after a supervised relaunch:
                # stamp decision -> first-step wall time, once.  A step
                # that raised (crash/preemption mid-compile) must not
                # consume the measurement — the restart didn't succeed,
                # and a decision->crash time would pollute the gate.
                latency = time.time() - self._relaunch_ts
                origin, self._relaunch_ts = self._relaunch_ts, None
                self.writer.emit(
                    "restart_latency", step=step,
                    latency=latency, decision_ts=origin,
                )
            if self.watchdog is not None:
                self.watchdog.beat(step)

    def begin_period(self) -> None:
        if self._needs_run_start:
            # a second train() on the same trainer: mark the new segment
            # so run_end consumers don't attribute it to the previous one
            self.writer.emit("run_start", resumed=True)
            self._needs_run_start = False
        if self._takes_spans and not self._compiles.attached(self):
            # a stream closed by finish(), or taken over by a later
            # trainer's start: the trainer that trains has the spans
            self._compiles.attach(self)
        self._totals = defaultdict(float)
        self._period_compiles = self._compiles.count
        self._period_compile_s = self._compiles.secs
        if self.watchdog is not None:
            self.watchdog.beat()

    def end_period(
        self,
        period: int,
        idx: int,
        elapsed: float,
        steps: int,
        metrics: dict | None = None,
        rates: dict | None = None,
        offset: int = 0,
    ) -> dict:
        """Emit the per-period summary event and feed the anomaly
        detectors; returns the phase-total dict.  ``rates`` is the
        family's ``rate_metrics`` dict (tokens/sec, img/sec, mfu, ...);
        stamping it into the period event is what lets the fleet rollup
        (``obs fleet``) tabulate MFU per job without the CSVs.
        ``offset`` is the batch offset this period's data stream STARTED
        at (nonzero only for the first period after an exact mid-period
        resume) — together with ``steps`` it states exactly which slice
        of the period this event describes, which is what lets the
        goodput ledger decide whether a later resume replays it."""
        from ddl_tpu.utils.memory import hbm_stats

        phases = dict(self._totals)
        # hbm_stats degrades to None itself on backends without memory
        # stats (utils/memory.py) — no try needed here
        mem = hbm_stats()
        loss = None
        if metrics:
            raw = metrics.get("loss")
            loss = float(raw) if raw is not None else None
        steps_per_sec = steps / elapsed if elapsed > 0 else 0.0
        compiles = self._compiles.count - self._period_compiles
        compile_s = self._compiles.secs - self._period_compile_s
        self.writer.emit(
            "period",
            step=idx,
            period=period,
            steps=steps,
            offset=offset,
            elapsed=elapsed,
            steps_per_sec=steps_per_sec,
            phases=phases,
            loss=loss,
            compiles=compiles,
            compile_s=compile_s,
            hbm_peak_bytes=mem["peak_bytes_in_use"] if mem else None,
            **({"rates": dict(rates)} if rates else {}),
            # a dropless expert layer's counters and a Mamba stack's
            # largest state (lm_steps.sown_metrics), as the period's last
            # step read them; absent for every other program
            **{k: float(metrics[k]) for k in SOWN_COUNTERS
               if metrics and metrics.get(k) is not None},
        )
        self.anomaly.observe_period(
            idx,
            loss=loss,
            steps_per_sec=steps_per_sec,
            hbm_bytes=mem["bytes_in_use"] if mem else None,
            compiles=compiles,
            ssm_state=metrics.get("ssm_state_absmax") if metrics else None,
        )
        return phases

    def finish(self, verbose: bool = True) -> list[dict]:
        """End-of-run: emit ``run_end`` with the whole-run phase totals
        and anomaly count, print what the detectors caught, close the
        stream.  Returns the anomaly list."""
        anomalies = self.anomaly.anomalies
        if self.capturer is not None:
            # close a profile window the run ended inside of (its
            # profile_capture event must precede run_end/close)
            self.capturer.finish()
        self.writer.emit(
            "run_end",
            phases=dict(self.run_totals),
            anomalies=len(anomalies),
            stalls=self.watchdog.stalls if self.watchdog else 0,
        )
        if verbose and anomalies:
            print(f"[obs] {len(anomalies)} anomalies detected this run:")
            for line in self.anomaly.summary_lines():
                print(f"[obs]   {line}")
        self._compiles.detach(self)  # a closed stream takes no span
        self.writer.close()
        # reset per-run state so a second train() on the same trainer
        # reports its own segment, not cumulative double-counted totals
        self.run_totals = defaultdict(float)
        self.anomaly = AnomalyMonitor(self.writer, capturer=self.capturer)
        self._needs_run_start = True
        return anomalies
