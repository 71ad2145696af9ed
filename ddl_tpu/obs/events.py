"""Structured JSONL event/span writer.

One file per host at ``<log_dir>/by_job_id/<job_id>/events-h<host>.jsonl``
— beside the reference-schema metric CSVs, so a run directory carries
both views of the same run.  Every line is one JSON object with a fixed
envelope:

    ts    wall-clock unix seconds (cross-host alignment, NTP precision)
    mono  monotonic seconds (exact ordering/durations within a host)
    run   run id — one per trainer/process launch (DDL_RUN_ID or random)
    host  process index (multihost runs write disjoint files)
    step  step/period context, or null
    kind  event kind ("span", "period", "heartbeat", "stall", ...)

plus kind-specific fields.  Spans add ``name``/``dur`` and record their
nesting (``parent``/``depth``) from a per-thread span stack, so a phase
inside a period inside a run reconstructs without timestamps agreeing
across threads.  Writes are line-buffered and flushed per event — a
hung or SIGKILLed job keeps everything up to its last completed event,
which is the point (the watchdog's stall dump must survive the death it
predicts).
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
import warnings
from contextlib import contextmanager
from pathlib import Path

__all__ = [
    "EVENT_KINDS",
    "ANOMALY_TYPES",
    "SOWN_COUNTERS",
    "EventWriter",
    "events_path",
    "read_events",
]

# ---------------------------------------------------------------------------
# Event-name registry.  Every ``kind`` emitted anywhere in the package
# must be listed here: dashboards, `obs summarize`, and CI queries match
# events BY NAME, so a typo'd kind is a silently-invisible event stream.
# The static analyzer (`ddl_tpu lint`, analysis/astlint.py) checks every
# ``.emit("<kind>")`` call site against this tuple without importing
# JAX; ``EventWriter.emit`` warns at runtime for dynamic kinds the
# linter cannot see.  Extend the tuple in the same change that emits the
# new kind.
# ---------------------------------------------------------------------------
EVENT_KINDS = (
    # events.py / steptrace.py envelope
    "span", "run_start", "run_end", "period",
    # watchdog.py liveness
    "heartbeat", "stall", "watchdog_exit",
    # anomaly.py detectors + loop recovery
    "anomaly", "rollback",
    # profiler.py anomaly-triggered jax.profiler windows (trace dir +
    # per-op device-time digest; also the ok=False disable markers)
    "profile_capture",
    # loop.py data-path retries
    "io_retry",
    # infer/decode.py per-request serving telemetry
    "decode",
    # serve/ continuous-batching engine: admission/shed decisions, lane
    # retirement, and block-pool occupancy snapshots (per-request latency
    # still flows through "decode" so one percentile pipeline serves
    # both the one-shot and the continuous-batching paths).
    # serve_admit/serve_shed/serve_retire, "decode", and the serving
    # trace_span/trace_mark events additionally carry optional
    # ``tenant``/``priority_class`` tags (serve/scheduler.tenant_tags —
    # omitted entirely when the request is untagged, so pre-tenant
    # streams are byte-identical); the fold buckets tagged events into
    # per-tenant digests and goodput accounts, and obs/slo.py evaluates
    # per-class error budgets over them.  Untagged events fold into the
    # "default" tenant (obs/serving.tenant_of)
    "serve_admit", "serve_shed", "serve_retire", "kv_pool_stats",
    # prefix caching (round 17): a request admitted onto cached prompt
    # blocks (cached_tokens/blocks args), a finished prefill registering
    # its prompt blocks in the content-keyed index, and the one write a
    # shared block can see — the copy-on-write block duplication.
    # serve_admit additionally carries cached_tokens/prefill_tokens and
    # an optional scenario tag (serve-bench --scenario)
    "prefix_hit", "prefix_insert", "kv_cow_copy",
    # snapshot restore at trainer startup (all three families): dur +
    # the resume cursor (period/offset) the restored state represents.
    # The goodput ledger (obs/goodput.py) books the dur into the
    # `checkpoint` bucket and uses the cursor to charge a prior
    # incarnation's periods beyond it as rolled-back (replayed) work —
    # an exact preemption resume charges nothing, a crash resume
    # charges everything past the snapshot
    "snapshot_restore",
    # supervisor.py restart lifecycle
    "supervisor_start", "supervisor_relaunch", "supervisor_done",
    # pod-level coordinated recovery (coord.py + PodSupervisor);
    # peer_lost is the elastic eviction decision — a peer silent past
    # the eviction grace (or absent from a join barrier), answered by a
    # shrunken-membership restart epoch instead of a pod abort
    "coord_barrier", "peer_stale", "peer_lost", "pod_restart",
    # warm restarts (utils/compile_cache.py): one event per incarnation
    # recording where the persistent topology-keyed XLA cache points and
    # whether it started warm (entries_before > 0) plus hit/miss
    # counters — read next to restart_latency and the recompile goodput
    # bucket by the warm-relaunch drill
    "compile_cache",
    # serve/engine.py preempt-drain: admission closed, queued requests
    # shed tenant-tagged, in-flight lanes finishing — the multi-tenant
    # SLO gates see a drain, not a cliff
    "serve_drain",
    # relaunch-decision -> child-first-step wall time, emitted by
    # StepTrace on a relaunched child's first completed step (the
    # supervisor stamps DDL_RELAUNCH_TS); gateable via `obs diff
    # --fail-slowdown` — the metric the elastic-restart/compile-cache
    # ROADMAP direction must move
    "restart_latency",
    # pipeline-schedule identity + modeled per-stage F/B/W/idle
    # accounting (obs/schedule_model.py), one event per pipelined run
    # (train/loop.BaseTrainer._emit_pipe_schedule); `obs trace --step`
    # rebuilds the schedule lanes from it and summarize renders the
    # modeled bubble line
    "pipe_schedule",
    # causal tracing (obs/trace.py): a completed span / an instant mark
    # carrying trace/span/parent ids — emitted natively where causality
    # is not reconstructable from the aggregate kinds (the serving
    # request path: admit -> queue -> prefill -> each ridden decode
    # dispatch -> retire/shed).  Training step and incident traces are
    # DERIVED from the existing kinds by the trace builder instead.
    "trace_span", "trace_mark",
    # elastic scale-UP (round 24): join_request is the joiner side (an
    # evicted/replacement host publishing its marker and waiting),
    # peer_join is the leader observing fresh join markers and growing
    # the membership at the next restart boundary; serve_resume is a
    # parked serving request re-admitted after the grow epoch with its
    # partial output re-prefilled (serve/engine.resume_parked)
    "join_request", "peer_join", "serve_resume",
    # HBM ledger (obs/hbm.py): hbm_plan is a per-program static budget
    # stamped at compile time (executable memory analysis, aval
    # fallback); hbm_sample is the periodic live per-category breakdown
    # against the device watermark; hbm_oom_dump is the allocation-
    # failure forensic snapshot (resident buffers + the plans that
    # predicted them) emitted before the process dies
    "hbm_plan", "hbm_sample", "hbm_oom_dump",
)

# ``type`` values carried by "anomaly" events (AnomalyMonitor.record and
# the rolling detectors in obs/anomaly.py).
ANOMALY_TYPES = (
    "loss_spike", "throughput_regression", "hbm_growth", "nonfinite_loss",
    "ssm_state_growth",
)

# Step metrics that the layers of a model sow (``train/lm_steps.sown_metrics``:
# a dropless expert layer's counters, under softmax scores with the top-k
# mass; a Mamba stack's largest state) and that the ``period`` event copies
# as its last step read them; the fold keeps the latest of each for
# ``obs summarize``.
SOWN_COUNTERS = (
    "moe_local_rows", "moe_load_max_over_mean", "moe_rows_dropped", "moe_buffer_fill",
    "moe_topk_mass", "ssm_state_absmax",
)

_warned_kinds: set[str] = set()


def events_path(log_dir: str | os.PathLike, job_id: str, host: int = 0) -> Path:
    return Path(log_dir) / "by_job_id" / job_id / f"events-h{host:03d}.jsonl"


def _default_host() -> int:
    from ddl_tpu.launch import host_id

    return host_id()


class EventWriter:
    """Append JSON event lines; thread-safe (the watchdog thread emits
    through the same writer as the training loop)."""

    def __init__(
        self,
        log_dir: str | os.PathLike,
        job_id: str,
        host: int | None = None,
        run_id: str | None = None,
    ) -> None:
        self.job_id = job_id
        self.host = _default_host() if host is None else int(host)
        self.run_id = run_id or os.environ.get("DDL_RUN_ID") or uuid.uuid4().hex[:12]
        # pod restart epoch (DDL_RESTART_EPOCH, set by the pod
        # supervisor): stamped into every event so telemetry attributes
        # cleanly to an incarnation; omitted entirely outside pod mode
        try:
            self.restart_epoch = int(
                os.environ.get("DDL_RESTART_EPOCH") or 0
            )
        except ValueError:
            self.restart_epoch = 0
        self.path = events_path(log_dir, job_id, self.host)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._lock = threading.Lock()
        self._file = open(self.path, "a", buffering=1)
        self._spans = threading.local()  # per-thread open-span name stack

    def emit(
        self, kind: str, step: int | None = None, *, at: float | None = None, **fields
    ) -> dict:
        """Write one event, stamped now; ``at`` (a ``time.time()`` in the
        past) stamps it then instead, ``mono`` moved back alike."""
        if kind not in EVENT_KINDS and kind not in _warned_kinds:
            # warn (once per kind), don't drop: ad-hoc kinds in probes/
            # tests still flow, but anything shipping in the package is
            # caught here at runtime and by `ddl_tpu lint` statically
            _warned_kinds.add(kind)
            warnings.warn(
                f"obs event kind {kind!r} is not registered in "
                "ddl_tpu.obs.events.EVENT_KINDS; consumers matching by "
                "name will not see it",
                stacklevel=2,
            )
        now = time.time()
        event = {
            "ts": now if at is None else at,
            "mono": time.monotonic() - (0.0 if at is None else now - at),
            "run": self.run_id,
            "host": self.host,
            "step": step,
            "kind": kind,
            **(
                {"repoch": self.restart_epoch}
                if self.restart_epoch else {}
            ),
            **fields,
        }
        line = json.dumps(event, default=_jsonable)
        with self._lock:
            if self._file.closed:  # e.g. a second train() after finish()
                self._file = open(self.path, "a", buffering=1)
            self._file.write(line + "\n")
            self._file.flush()
        return event

    @contextmanager
    def span(self, name: str, step: int | None = None, **fields):
        """Time a region and emit one ``span`` event on exit, recording
        its parent/depth from this thread's open-span stack."""
        stack = getattr(self._spans, "stack", None)
        if stack is None:
            stack = self._spans.stack = []
        parent = stack[-1] if stack else None
        stack.append(name)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            self.emit(
                "span", step=step, name=name, dur=dur,
                parent=parent, depth=len(stack), **fields,
            )

    def span_at(
        self, name: str, start: float, end: float, step: int | None = None, **fields
    ) -> dict:
        """One ``span`` event for a region that was timed elsewhere on
        ``time.time()``'s clock (a compile that ``jax.monitoring``
        reports, a stage that ran before this stream was open): ``ts`` is
        its end and ``dur`` its length, as every span.  ``parent`` /
        ``depth`` are this thread's open spans' unless the caller, who
        knew the nesting when the region ran, hands them."""
        stack = getattr(self._spans, "stack", None) or ()
        fields.setdefault("parent", stack[-1] if stack else None)
        fields.setdefault("depth", len(stack))
        return self.emit(
            "span", step=step, at=end, name=name, dur=end - start, **fields
        )

    def close(self) -> None:
        with self._lock:
            if not self._file.closed:
                self._file.close()


def _jsonable(x):
    """Fallback encoder: numpy scalars and anything else stringifiable."""
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


def read_events(path: str | os.PathLike) -> list[dict]:
    """Parse one event file; tolerates a torn final line (the writer may
    have died mid-write — everything before it is still valid)."""
    events = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except FileNotFoundError:
        pass
    return events
