"""Unified runtime telemetry: structured events, step-phase spans,
stall watchdog, anomaly detection, and run inspection.

The reference's only observability is append-only per-metric CSVs on a
NAS (``single.py:260-269``).  This package is the shared event model the
CSVs lack: every trainer family and the decode path write one JSONL
event stream per host (``obs/events.py``), with per-step phase spans
(``obs/steptrace.py``), a liveness watchdog that dumps thread stacks
instead of hanging silently (``obs/watchdog.py``), rolling anomaly
detectors (``obs/anomaly.py``), and a run-inspection CLI
(``obs/report.py``, ``python -m ddl_tpu.cli obs ...``).

The CSVs keep the reference schema and stay the cross-run aggregation
surface (``bench/analysis.py``); the event stream adds what they cannot
express — nesting, per-host liveness, and sub-period attribution.

The diagnosis layer on top (PR 5): anomaly-triggered ``jax.profiler``
capture windows with per-op digests (``obs/profiler.py``), serving-side
latency percentiles over the decode path's per-request events
(``obs/serving.py``), and the pod-wide cross-host view — straggler/skew
table, barrier-wait attribution, unified incident timeline
(``obs/pod.py``, ``ddl_tpu obs pod``).

The streaming layer (PR 8): every read path runs through the
incremental fold engine (``obs/fold.py``) — a resumable reducer over
appended bytes whose versioned sidecar makes ``summarize``/``pod`` and
every ``obs watch`` refresh / ``obs export`` scrape O(appended bytes),
byte-identical to a cold full parse; plus cross-host clock-skew
estimation from barrier completions, mergeable t-digest serving
percentiles, and the ``restart_latency`` relaunch-to-first-step metric.

The causal layer (PR 10): ``obs/trace.py`` renders ONE request /
incident / training step as a clock-offset-corrected, causally-linked
Chrome trace (``ddl_tpu obs trace``) from native
``trace_span``/``trace_mark`` events (the serving path) plus spans
derived from the existing kinds; ``obs/fleet.py`` rolls up every job
under a log root into one table / combined Prometheus scrape
(``ddl_tpu obs fleet``).

The accounting layer (PR 20): ``obs/goodput.py`` folds all of the
above into the one number fleet operation bills by — an exhaustive
per-(host, restart-epoch) chip-time account (productive vs data-wait /
recompile / modeled bubble / rolled-back replay / checkpoint / stall /
barrier / restart-gap / untracked residual, sums-to-total by
construction) rendered by ``ddl_tpu obs goodput`` and re-used by
summarize / watch / export / fleet / the ``obs diff
--fail-goodput-drop`` CI gate.

The tenant layer (PR 21): requests tagged ``tenant``/``priority_class``
at ``ServeEngine.submit`` split every serving digest, serve counter,
and goodput account per tenant (untagged traffic folds into
``"default"`` — ``serving.tenant_of``); ``obs/slo.py`` evaluates
declarative per-class error budgets from a job-level ``slo.json`` into
burn rates with fast/slow alert windows (``ddl_tpu obs slo``,
``ddl_obs_tenant_*`` export series, the ``obs diff --fail-slo-burn``
CI gate).
"""

from ddl_tpu.obs.anomaly import (
    AnomalyMonitor,
    HBMGrowthDetector,
    LossSpikeDetector,
    StateGrowthDetector,
    ThroughputRegressionDetector,
)
from ddl_tpu.obs.events import EventWriter, events_path, read_events
from ddl_tpu.obs.fold import JobFold, StreamFold, estimate_clock_offsets, fold_job
from ddl_tpu.obs.goodput import ledger_from_fold, render_goodput
from ddl_tpu.obs.profiler import TraceCapturer
from ddl_tpu.obs.serving import (
    QuantileAccumulator,
    ServingStats,
    TDigest,
    tenant_of,
)
from ddl_tpu.obs.slo import evaluate_slo, load_slo, render_slo
from ddl_tpu.obs.steptrace import PHASES, StepTrace
from ddl_tpu.obs.watchdog import Watchdog

__all__ = [
    "AnomalyMonitor",
    "EventWriter",
    "HBMGrowthDetector",
    "JobFold",
    "LossSpikeDetector",
    "PHASES",
    "QuantileAccumulator",
    "ServingStats",
    "StateGrowthDetector",
    "StepTrace",
    "StreamFold",
    "TDigest",
    "ThroughputRegressionDetector",
    "TraceCapturer",
    "Watchdog",
    "estimate_clock_offsets",
    "evaluate_slo",
    "events_path",
    "fold_job",
    "ledger_from_fold",
    "load_slo",
    "read_events",
    "render_goodput",
    "render_slo",
    "tenant_of",
]
