"""Incremental fold engine: the single resumable reducer behind the
whole obs read path.

Before this module, every ``obs summarize``/``obs pod`` invocation
re-read and re-parsed the job's complete JSONL streams; only the serving
percentile accumulators were incremental (the PR-6 tail-cursor cache,
``obs/cursor.py``, which this module generalizes).  Fine for a CI smoke
— pathological for a week-long run an operator glances at every few
minutes, and a non-starter for ``obs watch``'s refresh loop.

The engine maintains, per event stream (one per host file), a
``StreamFold``: phase/step/period aggregates, host liveness, the
anomaly/stall/restart/capture timeline, per-(repoch, period) skew rows,
barrier-wait sums and barrier-completion timestamps (the clock-skew
fit's inputs), serving percentile digests, and serve/admission counters.
``fold_job`` resumes the folds from a versioned sidecar beside the
streams (``.obs_fold.json``): per file a **byte cursor** plus the
serialized fold state, so each invocation seeks every stream to its
cursor, folds only the appended tail, and rewrites the sidecar
atomically — O(appended bytes), with rendered output **byte-identical**
to a cold full parse (every reducer is per-stream and every render-time
merge is deterministic; the serving digests are per-stream and mergeable
for exactly this reason — ``obs/serving.TDigest``).

Safety guards carried over from the cursor cache, per stream:

* only **complete** lines are consumed — a torn final line (writer died
  or is mid-append) stays past the cursor and is re-read once whole;
* a file that **shrank** below its cursor (rotation, truncation), one
  **re-created** under the same name (a re-used job id — caught by a
  fingerprint of the consumed head even when the new file is larger),
  or a tracked stream that **disappeared** outright each invalidate the
  whole cache and trigger a clean rebuild;
* a version/capacity mismatch or a structurally-corrupt sidecar
  rebuilds too.  The cache is an optimization, never a gate: anything
  unreadable is discarded and the fold restarts from byte 0.

Pure stdlib — no JAX — like the rest of the obs read path.
"""

from __future__ import annotations

import hashlib
import json
import os
import statistics
import threading
from pathlib import Path

from ddl_tpu.obs.events import SOWN_COUNTERS
from ddl_tpu.obs.hbm import PLAN_FIELDS, sample_categories
from ddl_tpu.obs.serving import ServingStats, tenant_of

__all__ = [
    "JobFold",
    "SIDECAR_NAME",
    "StreamFold",
    "estimate_clock_offsets",
    "fold_job",
]

SIDECAR_NAME = ".obs_fold.json"
# v1/v2 were the serving-only cursor sidecar (obs/cursor.py); v3 was the
# whole-summary fold with t-digest serving state; v4 added the causal-
# trace reducer (trace_span/trace_mark counts + slowest-request cell)
# and per-repoch rate metrics (mfu); v5 added the per-device
# optimizer-state HBM gauge (opt_hbm_bytes); v6 added the prefix-cache
# counters (prefix_hit/prefix_insert/kv_cow_copy + serve_admit's
# cached/prefill token split); v7 added the pipe_schedule cell (pipeline
# schedule identity + modeled bubble accounting); v8 adds the goodput
# ledger reducer (per-repoch wall-clock accounting: window bounds,
# phase/compile/restore/stall sums, replay charging off rollback +
# snapshot_restore cursors — obs/goodput.py renders it); v9 adds the
# per-tenant attribution layer (ServingStats per-tenant digests, the
# tenant_serve admit/shed/retire counters, and the per-repoch per-tenant
# served/queued/shed chip-second split obs/slo.py evaluates budgets
# over); v10 adds the HBM-ledger reducer (per-repoch memory cells:
# peak-watermark category breakdown off hbm_sample, bounded last-wins
# static plans off hbm_plan, and the hbm_oom_dump forensic cell —
# obs/hbm.py renders the account); v11 adds the per-host last-wins map of
# the model's sown step counters off the period event
# (events.SOWN_COUNTERS); v12 adds the start-up account to the goodput
# reducer (the seconds of the ``setup.*`` spans, the bucket ``startup``;
# the incarnation's window begins where its ``setup.boot`` does; the
# set-up line's sums by stage and of the ``compile.*`` spans before the
# first period)
# — older sidecars rebuild cleanly
VERSION = 12

# the serving-cursor sidecar this module's cache superseded; removed
# opportunistically when the fold sidecar is written so a job dir does
# not carry two generations of cache
LEGACY_SIDECAR = ".serving_cursor.json"

# kinds worth a line on the cross-host incident timeline (lifecycle +
# incidents; spans/heartbeats/periods are volume, not narrative)
TIMELINE_KINDS = (
    "run_start", "run_end", "supervisor_start", "supervisor_relaunch",
    "supervisor_done", "pod_restart", "peer_stale", "coord_barrier",
    "anomaly", "stall", "watchdog_exit", "rollback", "profile_capture",
    "restart_latency", "snapshot_restore",
    # elastic membership churn: eviction (peer_lost), the joiner's ask
    # (join_request) and the leader's grow decision (peer_join) — the
    # scale-down/scale-up narrative the incident timeline exists to tell
    "peer_lost", "join_request", "peer_join",
    # an allocation-failure forensic dump is the last thing a dying
    # process says — always narrative
    "hbm_oom_dump",
)

# kinds emitted by a SUPERVISOR process into the same stream as its
# child trainer.  They are job-scoped coordination, not incarnation
# compute, so the goodput ledger excludes them from the per-(host,
# repoch) incarnation windows (a supervisor keeps stamping repoch-0
# events for the whole job's lifetime — letting them extend the window
# would make every later incarnation overlap repoch 0's account).
SUPERVISOR_KINDS = frozenset((
    "supervisor_start", "supervisor_relaunch", "supervisor_done",
    "pod_restart", "peer_stale", "coord_barrier",
    "peer_lost", "join_request", "peer_join",
))

# goodput per-repoch replay bookkeeping: retain the last N periods'
# (step+fence seconds, offset, steps) triples — a rollback/resume only
# ever rewinds to a recent snapshot, and the sidecar must stay bounded
_GOODPUT_PERIOD_CAP = 160
_GOODPUT_PERIOD_KEEP = 128

# per-stream cap on each retained incident-event list (anomalies,
# stalls, captures, timeline).  The sidecar must stay bounded no matter
# how long the run — a week of recurring loss spikes must not turn
# every 2s `obs watch` tick into a multi-MB JSON rewrite (the cost
# model is O(appended bytes), not O(total incidents)).  Totals keep
# counting past the cap; renders show the retained tail and say so.
MAX_EVENTS_PER_LIST = 512


def _stream_host(name: str) -> int | None:
    """Host id from the stream file name (``events-h012.jsonl`` -> 12);
    the file name is authoritative — sim-pod children each believe they
    are host 0 while their streams are per-host."""
    stem = name.rsplit(".", 1)[0]
    try:
        return int(stem.split("-h")[-1])
    except ValueError:
        return None


def _new_host_rec() -> dict:
    return {
        "last_step": None, "pstep": None, "pstep_ts": None,
        "last_ts": None, "stalls": 0,
    }


def _new_period_agg() -> dict:
    return {
        "n": 0, "steps": 0, "elapsed": 0.0, "compiles": 0,
        "hbm": None, "phases": {}, "sps": [], "sown": {},
    }


def _new_repoch_agg() -> dict:
    return {
        "periods": 0, "steps": 0, "elapsed": 0.0, "compiles": 0,
        "phases": {}, "last_sps": None, "last_step": None, "loss": None,
        "last_ts": None, "mfu": None, "opt_hbm_bytes": None,
    }


def _new_goodput() -> dict:
    """One (repoch) incarnation's goodput-ledger accumulation.  Every
    field is a sum, a min/max, or a bounded last-wins map, so resumed
    slices reduce identically to one pass (the byte-identity contract).
    ``periods`` maps period -> [step+fence seconds, start offset, steps]
    — the coverage record replay charging consumes (and pops) when a
    rollback or snapshot-restore cursor says that ground is re-run."""
    return {
        "first_ts": None, "last_ts": None,  # incarnation-scoped kinds
        "decision_ts": None,  # earliest restart decision INTO this repoch
        "phases": {}, "compile_s": 0.0, "restore_s": 0.0,
        "stall_s": 0.0, "gap_s": 0.0, "rolled_back_s": 0.0,
        # start-up: seconds under ``setup.*`` spans (outside every phase
        # by construction); the last relaunch gap charged, [from, to],
        # which a new process's set-up spans take their part back from;
        # and the set-up line: seconds by stage, and of the ``compile.*``
        # spans heard before the incarnation's first period
        "startup_s": 0.0, "gap_at": None, "setup": {}, "started": False,
        "serve_t0": None, "serve_t1": None,
        "periods": {}, "await_bad": None,
        # per-tenant chip-second split of the serving window: sums of
        # decode durations (served) and queue delays (queued) plus the
        # shed count, keyed by the normalized tenant tag — what the
        # goodput ledger's per-tenant accounts and obs/slo.py's
        # availability burn rates reduce from
        "tenants": {},
    }


def _new_tenant_goodput() -> dict:
    return {"served_s": 0.0, "queued_s": 0.0, "requests": 0, "shed": 0}


# per-repoch cap on retained static plans (distinct compiled programs
# are few — train/eval steps, prefill/decode buckets); drops are counted
# so the render can say coverage was bounded, never silently truncated
_HBM_PLAN_CAP = 64


def _new_hbm() -> dict:
    """One (repoch) incarnation's HBM-ledger cell (obs/hbm.py renders
    it).  ``watermark``/``at_peak`` are a paired max cell: the largest
    sampled live watermark plus the tracked category bytes captured at
    that same sample (ties resolve to the later sample — deterministic
    under any resume slicing, events arrive in stream order).  ``plans``
    is bounded last-wins per program label; ``oom`` is last-wins."""
    return {
        "samples": 0,
        "watermark": 0,      # max sampled bytes_in_use
        "device_peak": 0,    # max backend peak_bytes_in_use
        "limit": None,       # last-wins bytes_limit
        "synthetic": False,  # any sample lacked backend memory stats
        "last": {},          # last sample's tracked category bytes
        "at_peak": {},       # tracked category bytes at the peak sample
        "plans": {},         # label -> static budget (bounded last-wins)
        "plans_dropped": 0,
        "oom_count": 0,
        "oom": None,         # last-wins slim forensic dump
    }


class StreamFold:
    """One event stream's running reduction.  ``consume`` is the single
    entry point; everything else is serialization.  All state is either
    a sum, a min/max, an ordered append-only list, or a last-wins cell —
    so feeding the same event sequence in any number of resumed slices
    produces the same state as feeding it in one pass."""

    def __init__(self, host: int | None, capacity: int = 4096) -> None:
        self.host = host
        self.capacity = int(capacity)
        self.events = 0
        self.runs: set[str] = set()
        self.repochs: set[int] = set()
        # summarize-side aggregates, keyed by the events' own host field
        self.hosts: dict[int, dict] = {}
        self.phost: dict[int, dict] = {}
        # pod-side aggregates, attributed to the STREAM (file-name host)
        self.pod = {
            "periods": 0, "steps": 0.0, "elapsed": 0.0,
            "stalls": 0, "anomalies": 0, "captures": 0, "restarts": 0,
            "last_step": None,
        }
        self.ptable: dict[str, list] = {}  # "repoch:period" -> [sps, step_s, wait_s]
        self.by_repoch: dict[int, dict] = {}  # export surface
        self.span_sums: dict[str, float] = {}
        self.anomaly_types: dict[str, int] = {}
        self.anomalies: list[dict] = []
        self.stalls: list[dict] = []
        self.captures: list[dict] = []
        self.timeline: list[dict] = []
        # totals keep counting past MAX_EVENTS_PER_LIST truncation
        self.totals = {
            "anomalies": 0, "stalls": 0, "captures": 0, "timeline": 0,
        }
        self.barrier_waits: dict[str, float] = {}
        self.barrier_ts: dict[str, float] = {}  # "repoch:name" -> completion ts
        # restart-latency running aggregates: bounded however many
        # restarts a run survives ("by_repoch" is last-wins per epoch)
        self.restart_latency = {
            "n": 0, "sum": 0.0, "max": None, "last": None,
            "last_ts": None, "by_repoch": {},  # str(repoch) -> [ts, latency]
        }
        self.serve = {
            "admit": 0, "shed": 0, "retire": 0, "kv_last": None,
            # prefix-cache economics (round 17): hit/insert/CoW counts
            # plus the cached-vs-computed prompt-token split off
            # serve_admit — the numbers behind summarize's hit-rate line
            "prefix_hits": 0, "prefix_hit_tokens": 0, "prefix_inserts": 0,
            "cow_copies": 0, "cached_tokens": 0, "prefill_tokens": 0,
        }
        # per-tenant admit/shed/retire counters (normalized tag; kept
        # OUT of self.serve so the flat-counter sums there stay flat) —
        # the shed-rate / availability inputs obs/slo.py evaluates
        self.tenant_serve: dict[str, dict] = {}
        # job-level restart accounting: every host of a pod emits its
        # own pod_restart event for the SAME pod-wide restart, so the
        # per-stream "restarts" counter (kept for the per-host export/
        # watch surfaces) over-counts by the pod size when summed.
        # Distinct restart EPOCHS dedupe across streams; single-host
        # supervisor relaunches are counted separately (each is real).
        self.pod_restart_epochs: set[int] = set()
        self.relaunches = 0
        # causal-trace reducer (obs/trace.py kinds): span/mark counts
        # plus a max cell over ROOT request spans — what `obs trace
        # --slowest-request` selects on without re-reading any stream.
        # "slowest" is [dur, trace_id, t1]; the (dur, trace_id) tuple
        # max is deterministic under any resume slicing.
        self.trace = {
            "spans": 0, "marks": 0, "requests": 0, "slowest": None,
        }
        # pipeline-schedule cell (pipe_schedule events): last-wins — the
        # schedule is static per run, and on a resume the newest event
        # describes the layout actually training
        self.pipe_schedule: dict | None = None
        # goodput ledger (obs/goodput.py renders it): per-repoch
        # incarnation accounts plus the stream's all-event time span
        # (the job-level wall clock, supervisor coordination included)
        self.goodput: dict[int, dict] = {}
        # HBM ledger (obs/hbm.py): per-repoch memory cells fed by the
        # hbm_sample/hbm_plan/hbm_oom_dump kinds
        self.hbm: dict[int, dict] = {}
        self.all_span: list = [None, None]  # [first_ts, last_ts], any kind
        self.serving = ServingStats(capacity)

    def _tenant_counters(self, e: dict) -> dict:
        t = tenant_of(e)
        ts = self.tenant_serve.get(t)
        if ts is None:
            ts = self.tenant_serve[t] = {
                "admit": 0, "shed": 0, "retire": 0,
                "cached_tokens": 0, "prefill_tokens": 0,
            }
        return ts

    def _push(self, key: str, item: dict) -> None:
        lst = getattr(self, key)
        lst.append(item)
        self.totals[key] += 1
        if len(lst) > MAX_EVENTS_PER_LIST:
            del lst[: len(lst) - MAX_EVENTS_PER_LIST]

    # ------------------------------------------------------------ ingest

    def consume(self, e: dict) -> None:
        self.events += 1
        run = e.get("run")
        if run:
            self.runs.add(str(run))
        kind = e.get("kind")
        step = e.get("step")
        ts = e.get("ts")
        h = e.get("host", 0)
        repoch = int(e.get("repoch", 0) or 0)
        self.repochs.add(repoch)

        rec = self.hosts.setdefault(h, _new_host_rec())
        if ts is not None and (rec["last_ts"] is None or ts >= rec["last_ts"]):
            rec["last_ts"] = ts

        # -- goodput window bookkeeping --------------------------------
        if ts is not None:
            if self.all_span[0] is None or ts < self.all_span[0]:
                self.all_span[0] = ts
            if self.all_span[1] is None or ts > self.all_span[1]:
                self.all_span[1] = ts
        if kind not in SUPERVISOR_KINDS:
            g = self.goodput.setdefault(repoch, _new_goodput())
            if ts is not None:
                if (
                    kind == "run_start"
                    and not e.get("resumed")
                    and g["last_ts"] is not None
                    and ts > g["last_ts"]
                ):
                    # a NEW process's run_start after a dead window in
                    # the same repoch (single-host supervised relaunch):
                    # the dead time is restart gap, not untracked
                    g["gap_s"] += ts - g["last_ts"]
                    g["gap_at"] = [g["last_ts"], ts]
                if g["first_ts"] is None or ts < g["first_ts"]:
                    g["first_ts"] = ts
                if g["last_ts"] is None or ts > g["last_ts"]:
                    g["last_ts"] = ts
        else:
            g = None

        if kind == "period":
            self._consume_period(e, h, step, ts, repoch)
        elif kind == "span":
            if not e.get("depth"):
                name = e.get("name", "?")
                self.span_sums[name] = (
                    self.span_sums.get(name, 0.0) + e.get("dur", 0.0)
                )
            self._track_step(rec, step)
            if g is not None and ts is not None:
                self._consume_setup_span(g, e, ts)
        elif kind == "heartbeat":
            self._track_step(rec, step)
        elif kind == "stall":
            self._track_step(rec, step)
            rec["stalls"] += 1
            self.pod["stalls"] += 1
            # goodput: the hung window is time since the last beat.
            # Charged only under the "exit" escalation, where the
            # wedged phase is GUARANTEED never to emit its span (the
            # process dies) — in "dump" mode a recovered phase later
            # reports its full duration including the hang, and
            # charging both would attribute the same wall clock twice
            # (a dump-mode hang the process never recovers from lands
            # in untracked instead, which is honest)
            if g is not None and e.get("action") == "exit":
                g["stall_s"] += float(e.get("age", 0.0) or 0.0)
            slim = {k: v for k, v in e.items() if k != "stacks"}
            slim["stacks_n"] = len(e.get("stacks") or {})
            self._push("stalls", slim)
        elif kind == "anomaly":
            self.pod["anomalies"] += 1
            atype = str(e.get("type"))
            self.anomaly_types[atype] = self.anomaly_types.get(atype, 0) + 1
            self._push("anomalies", dict(e))
        elif kind == "profile_capture":
            if e.get("ok"):
                self.pod["captures"] += 1
            self._push("captures", dict(e))
        elif kind in ("supervisor_relaunch", "pod_restart"):
            self.pod["restarts"] += 1
            if kind == "pod_restart":
                self.pod_restart_epochs.add(int(e.get("epoch", 0) or 0))
            else:
                self.relaunches += 1
        elif kind == "coord_barrier":
            name = e.get("name", "?")
            self.barrier_waits[name] = (
                self.barrier_waits.get(name, 0.0) + e.get("wait", 0.0)
            )
            done = e.get("completed_ts", ts)
            if done is not None:
                self.barrier_ts[f"{repoch}:{name}"] = done
        elif kind == "restart_latency":
            dts = e.get("decision_ts")
            if g is not None and dts is not None:
                # earliest restart decision INTO this incarnation: the
                # ledger starts the incarnation's wall clock here, so
                # the relaunch gap (rendezvous, backoff, spawn, ...)
                # is accounted instead of falling between windows
                if g["decision_ts"] is None or dts < g["decision_ts"]:
                    g["decision_ts"] = float(dts)
            lat = e.get("latency")
            if lat is not None:
                rl = self.restart_latency
                rl["n"] += 1
                rl["sum"] += float(lat)
                rl["max"] = (
                    lat if rl["max"] is None else max(rl["max"], lat)
                )
                if rl["last_ts"] is None or (ts or 0.0) >= rl["last_ts"]:
                    rl["last"] = lat
                    rl["last_ts"] = ts or 0.0
                prev = rl["by_repoch"].get(str(repoch))
                if prev is None or (ts or 0.0) >= prev[0]:
                    rl["by_repoch"][str(repoch)] = [ts or 0.0, lat]
        elif kind == "decode":
            if g is not None and ts is not None:
                # serving activity window (one-shot decode AND engine
                # requests): [min(ts - dur), max(ts)] — a coarse union
                # approximation that is exact for the back-to-back
                # request trains the smokes run
                t0 = float(ts) - float(e.get("dur", 0.0) or 0.0)
                if g["serve_t0"] is None or t0 < g["serve_t0"]:
                    g["serve_t0"] = t0
                if g["serve_t1"] is None or ts > g["serve_t1"]:
                    g["serve_t1"] = ts
            if g is not None:
                # per-tenant chip-second split: the request's decode
                # duration is chip time served to its tenant, its queue
                # delay is time the tenant waited for a lane — both
                # plain sums, so resumed slices reduce identically
                tg = g["tenants"].setdefault(
                    tenant_of(e), _new_tenant_goodput()
                )
                tg["served_s"] += float(e.get("dur", 0.0) or 0.0)
                tg["queued_s"] += float(e.get("queue_delay", 0.0) or 0.0)
                tg["requests"] += 1
            self.serving.observe(e)
        elif kind == "serve_admit":
            self.serve["admit"] += 1
            self.serve["cached_tokens"] += int(e.get("cached_tokens", 0))
            self.serve["prefill_tokens"] += int(
                e.get("prefill_tokens", e.get("prompt_len", 0) or 0)
            )
            ten = self._tenant_counters(e)
            ten["admit"] += 1
            ten["cached_tokens"] += int(e.get("cached_tokens", 0))
            ten["prefill_tokens"] += int(
                e.get("prefill_tokens", e.get("prompt_len", 0) or 0)
            )
        elif kind == "serve_shed":
            self.serve["shed"] += 1
            self._tenant_counters(e)["shed"] += 1
            if g is not None:
                g["tenants"].setdefault(
                    tenant_of(e), _new_tenant_goodput()
                )["shed"] += 1
        elif kind == "serve_retire":
            self.serve["retire"] += 1
            self._tenant_counters(e)["retire"] += 1
        elif kind == "kv_pool_stats":
            self.serve["kv_last"] = dict(e)
        elif kind == "prefix_hit":
            self.serve["prefix_hits"] += 1
            self.serve["prefix_hit_tokens"] += int(
                e.get("cached_tokens", 0)
            )
        elif kind == "prefix_insert":
            self.serve["prefix_inserts"] += int(e.get("blocks", 1))
        elif kind == "kv_cow_copy":
            self.serve["cow_copies"] += 1
        elif kind == "trace_span":
            tr = self.trace
            tr["spans"] += 1
            if e.get("name") == "request" and e.get("trace"):
                tr["requests"] += 1
                t0, t1 = e.get("t0"), e.get("t1")
                if t0 is not None and t1 is not None:
                    cand = [float(t1) - float(t0), str(e["trace"]), t1]
                    cur = tr["slowest"]
                    if cur is None or (cand[0], cand[1]) > (
                        cur[0], cur[1]
                    ):
                        tr["slowest"] = cand
        elif kind == "trace_mark":
            self.trace["marks"] += 1
        elif kind == "pipe_schedule":
            self.pipe_schedule = dict(e)
        elif kind == "rollback":
            if g is not None:
                # in-loop NaN rollback: every period already recorded at
                # or beyond the resume point is about to be re-run —
                # charge it as rolled-back work.  The bad period's own
                # event arrives AFTER this rollback event (end_period
                # runs after the recovery handler), so remember it
                g["restore_s"] += float(e.get("restore_dur", 0.0) or 0.0)
                self._charge_replay(
                    g, int(e.get("resumed_at", 0) or 0), 0
                )
                if e.get("period") is not None:
                    g["await_bad"] = int(e["period"])
        elif kind == "snapshot_restore":
            if g is not None:
                g["restore_s"] += float(e.get("dur", 0.0) or 0.0)
                p = int(e.get("period", 0) or 0)
                off = int(e.get("offset", 0) or 0)
                # replay charge: work recorded beyond the restored
                # cursor was lost and is about to be re-run.  Charge the
                # SAME repoch (single-host supervised relaunches share
                # repoch 0) and EVERY earlier repoch (pod mode: the
                # dying incarnation holds the newest lost periods, but a
                # resume-from-scratch also re-runs ground older
                # incarnations saved — pop-on-charge keeps each record
                # chargeable at most once, so walking all of them never
                # double-counts)
                self._charge_replay(g, p, off)
                for r in sorted(self.goodput):
                    if r < repoch:
                        self._charge_replay(self.goodput[r], p, off)
        elif kind == "hbm_sample":
            hb = self.hbm.setdefault(repoch, _new_hbm())
            hb["samples"] += 1
            if e.get("synthetic"):
                hb["synthetic"] = True
            if e.get("limit") is not None:
                hb["limit"] = int(e["limit"])
            cats = sample_categories(e)
            hb["last"] = cats
            wm = int(e.get("watermark", 0) or 0)
            if wm >= hb["watermark"]:
                # paired max cell: the watermark AND the category bytes
                # observed at that same sample move together
                hb["watermark"] = wm
                hb["at_peak"] = cats
            pk = int(e.get("peak", 0) or 0)
            if pk > hb["device_peak"]:
                hb["device_peak"] = pk
        elif kind == "hbm_plan":
            hb = self.hbm.setdefault(repoch, _new_hbm())
            label = str(e.get("label", "?"))
            if label in hb["plans"] or len(hb["plans"]) < _HBM_PLAN_CAP:
                hb["plans"][label] = {k: e.get(k) for k in PLAN_FIELDS}
            else:
                hb["plans_dropped"] += 1
        elif kind == "hbm_oom_dump":
            hb = self.hbm.setdefault(repoch, _new_hbm())
            hb["oom_count"] += 1
            hb["oom"] = {
                "ts": ts,
                "step": step,
                "error": e.get("error"),
                "watermark": e.get("watermark"),
                "limit": e.get("limit"),
                "buffers": list(e.get("buffers") or []),
            }

        if kind in ("span", "heartbeat", "stall"):
            if step is not None:
                self.pod["last_step"] = (
                    step if self.pod["last_step"] is None
                    else max(self.pod["last_step"], step)
                )
        if kind in TIMELINE_KINDS:
            self._push(
                "timeline",
                {k: v for k, v in e.items() if k != "stacks"},
            )

    def _consume_setup_span(self, g: dict, e: dict, ts: float) -> None:
        """The start-up account of one incarnation.  A ``setup.*`` span
        is start-up seconds, and it may begin before the stream's first
        event (``setup.boot`` begins with the process): the incarnation's
        window and the stream's span reach back to it, and where a
        relaunch gap was charged up to this process's ``run_start`` the
        span takes its part of that gap back, so no second is booked
        twice.  ``compile.*`` spans before the first period are the
        set-up line's compile sums (after it they are recompiles, which
        ``period.compile_s`` carries)."""
        name, dur = str(e.get("name", "")), float(e.get("dur", 0.0) or 0.0)
        setup = g["setup"]
        if name.startswith("setup."):
            start = ts - dur
            g["startup_s"] += dur
            setup[name[6:]] = setup.get(name[6:], 0.0) + dur
            if start < g["first_ts"]:
                g["first_ts"] = start
            if start < self.all_span[0]:
                self.all_span[0] = start
            gap = g["gap_at"]
            if gap is not None:
                g["gap_s"] -= max(0.0, min(ts, gap[1]) - max(start, gap[0]))
        elif name.startswith("compile.") and not g["started"]:
            if name == "compile.backend":
                setup["backend"] = setup.get("backend", 0.0) + dur
                key = "hits" if e.get("cache_hit") else "misses"
                setup[key] = setup.get(key, 0) + 1
            else:
                setup["trace_lower"] = setup.get("trace_lower", 0.0) + dur

    @staticmethod
    def _charge_replay(g: dict, period: int, offset: int) -> None:
        """Move recorded period coverage at/beyond a resume cursor
        ``(period, offset)`` into the rolled-back bucket.  A period
        event describes batches ``[o, o + steps)`` of its period; the
        cursor says batches up to ``offset`` of ``period`` (and every
        earlier period) are SAVED — only the part beyond it was lost.
        An exact preemption resume therefore charges nothing (its
        recorded coverage ends exactly at the cursor), while a crash
        resumed from an older snapshot charges everything past it.
        Charged coverage is removed (a second restore must not
        double-charge ground already charged) but the SAVED slice of a
        boundary-straddling record is kept — a deeper later restore
        must still be able to charge it."""
        for key in sorted(g["periods"], key=int):
            p = int(key)
            if p < period:
                continue
            sf, o, steps = g["periods"][key]
            if p > period or not steps:
                g["rolled_back_s"] += sf
                del g["periods"][key]
                continue
            saved_steps = max(0, min(offset, o + steps) - o)
            charged = (steps - saved_steps) / steps
            g["rolled_back_s"] += sf * charged
            if saved_steps > 0:
                # keep the saved slice [o, o + saved_steps) at its
                # share of the recorded seconds
                g["periods"][key] = [
                    sf * (saved_steps / steps), o, saved_steps,
                ]
            else:
                del g["periods"][key]

    @staticmethod
    def _track_step(rec: dict, step) -> None:
        if step is not None:
            rec["last_step"] = (
                step if rec["last_step"] is None
                else max(rec["last_step"], step)
            )

    def _consume_period(self, e, h, step, ts, repoch) -> None:
        phases = e.get("phases") or {}
        sps = e.get("steps_per_sec")

        # -- goodput ledger accumulation -------------------------------
        g = self.goodput.setdefault(repoch, _new_goodput())
        for name, dur in phases.items():
            g["phases"][name] = g["phases"].get(name, 0.0) + dur
        g["compile_s"] += float(e.get("compile_s", 0.0) or 0.0)
        g["started"] = True
        step_fence = phases.get("step", 0.0) + phases.get("fence", 0.0)
        p = e.get("period")
        if p is not None:
            p = int(p)
            if g["await_bad"] is not None and p == g["await_bad"]:
                # the non-finite period a rollback just rewound past:
                # its compute is replayed ground, never saved coverage
                g["rolled_back_s"] += step_fence
                g["await_bad"] = None
            else:
                g["periods"][str(p)] = [
                    step_fence,
                    int(e.get("offset", 0) or 0),
                    int(e.get("steps", 0) or 0),
                ]
                if len(g["periods"]) > _GOODPUT_PERIOD_CAP:
                    drop = sorted(g["periods"], key=int)
                    for k in drop[: len(drop) - _GOODPUT_PERIOD_KEEP]:
                        del g["periods"][k]

        key = f"{repoch}:{e.get('period')}"
        self.ptable[key] = [
            sps,
            phases.get("step", 0.0),
            phases.get("data_wait", 0.0),
        ]
        self.pod["periods"] += 1
        self.pod["steps"] += e.get("steps", 0)
        self.pod["elapsed"] += e.get("elapsed", 0.0)

        agg = self.phost.setdefault(h, _new_period_agg())
        agg["n"] += 1
        agg["steps"] += e.get("steps", 0)
        agg["elapsed"] += e.get("elapsed", 0.0)
        agg["compiles"] += e.get("compiles", 0) or 0
        for name, dur in phases.items():
            agg["phases"][name] = agg["phases"].get(name, 0.0) + dur
        if sps:  # the cold parse filtered falsy steps_per_sec too
            agg["sps"].append(sps)
        # `is not None`, not truthiness: a backend reporting a true 0
        # watermark is a measurement, distinct from "no stats at all"
        hbm = e.get("hbm_peak_bytes")
        if hbm is not None:
            agg["hbm"] = hbm if agg["hbm"] is None else max(agg["hbm"], hbm)
        # the model's own step counters, as the latest period read them
        agg["sown"].update({k: e[k] for k in SOWN_COUNTERS if e.get(k) is not None})

        br = self.by_repoch.setdefault(repoch, _new_repoch_agg())
        br["periods"] += 1
        br["steps"] += e.get("steps", 0)
        br["elapsed"] += e.get("elapsed", 0.0)
        br["compiles"] += e.get("compiles", 0) or 0
        for name, dur in phases.items():
            br["phases"][name] = br["phases"].get(name, 0.0) + dur
        if sps is not None:
            br["last_sps"] = sps
        if step is not None:
            br["last_step"] = step
        if e.get("loss") is not None:
            br["loss"] = e.get("loss")
        if ts is not None:
            br["last_ts"] = ts
        # rate metrics ride the period event (steptrace.end_period
        # ``rates=``); mfu is the one the fleet rollup tabulates
        rates = e.get("rates") or {}
        if rates.get("mfu") is not None:
            br["mfu"] = rates["mfu"]
        if rates.get("opt_hbm_bytes") is not None:
            br["opt_hbm_bytes"] = rates["opt_hbm_bytes"]

        if step is not None:
            rec = self.hosts.setdefault(h, _new_host_rec())
            rec["pstep"] = step
            rec["pstep_ts"] = ts

    # ------------------------------------------------------------- state

    def state_dict(self) -> dict:
        return {
            "host": self.host,
            "capacity": self.capacity,
            "events": self.events,
            "runs": sorted(self.runs),
            "repochs": sorted(self.repochs),
            "hosts": {str(h): r for h, r in self.hosts.items()},
            "phost": {str(h): a for h, a in self.phost.items()},
            "pod": self.pod,
            "ptable": self.ptable,
            "by_repoch": {str(r): a for r, a in self.by_repoch.items()},
            "span_sums": self.span_sums,
            "anomaly_types": self.anomaly_types,
            "anomalies": self.anomalies,
            "stalls": self.stalls,
            "captures": self.captures,
            "timeline": self.timeline,
            "totals": self.totals,
            "barrier_waits": self.barrier_waits,
            "barrier_ts": self.barrier_ts,
            "restart_latency": self.restart_latency,
            "serve": self.serve,
            "tenant_serve": {
                t: self.tenant_serve[t] for t in sorted(self.tenant_serve)
            },
            "trace": self.trace,
            "pipe_schedule": self.pipe_schedule,
            "goodput": {str(r): a for r, a in self.goodput.items()},
            "hbm": {str(r): a for r, a in self.hbm.items()},
            "all_span": self.all_span,
            "pod_restart_epochs": sorted(self.pod_restart_epochs),
            "relaunches": self.relaunches,
            "serving": self.serving.state_dict(),
        }

    @classmethod
    def from_state(cls, state: dict) -> "StreamFold":
        sf = cls(state["host"], capacity=int(state["capacity"]))
        sf.events = int(state["events"])
        sf.runs = set(state["runs"])
        sf.repochs = {int(r) for r in state["repochs"]}
        sf.hosts = {int(h): dict(r) for h, r in state["hosts"].items()}
        sf.phost = {int(h): dict(a) for h, a in state["phost"].items()}
        sf.pod = dict(state["pod"])
        sf.ptable = dict(state["ptable"])
        sf.by_repoch = {
            int(r): dict(a) for r, a in state["by_repoch"].items()
        }
        sf.span_sums = dict(state["span_sums"])
        sf.anomaly_types = dict(state["anomaly_types"])
        sf.anomalies = list(state["anomalies"])
        sf.stalls = list(state["stalls"])
        sf.captures = list(state["captures"])
        sf.timeline = list(state["timeline"])
        sf.totals = dict(state["totals"])
        sf.barrier_waits = dict(state["barrier_waits"])
        sf.barrier_ts = dict(state["barrier_ts"])
        sf.restart_latency = dict(state["restart_latency"])
        sf.serve = dict(state["serve"])
        sf.tenant_serve = {
            t: dict(v) for t, v in state.get("tenant_serve", {}).items()
        }
        sf.trace = dict(state["trace"])
        sf.pipe_schedule = state.get("pipe_schedule")
        sf.goodput = {
            int(r): dict(a) for r, a in state["goodput"].items()
        }
        sf.hbm = {int(r): dict(a) for r, a in state["hbm"].items()}
        sf.all_span = list(state["all_span"])
        sf.pod_restart_epochs = {
            int(r) for r in state["pod_restart_epochs"]
        }
        sf.relaunches = int(state["relaunches"])
        sf.serving = ServingStats.from_state(state["serving"])
        return sf


class JobFold:
    """All of one job's stream folds plus the read accounting the
    O(appended-bytes) acceptance test asserts on."""

    def __init__(self, capacity: int = 4096) -> None:
        self.capacity = int(capacity)
        self.streams: dict[str, StreamFold] = {}
        # bytes THIS invocation read from the streams (tails + head
        # fingerprints); not persisted — it is the counting-reader
        self.bytes_read = 0

    @property
    def events(self) -> int:
        return sum(sf.events for sf in self.streams.values())

    def stream(self, name: str, host: int | None = None) -> StreamFold:
        sf = self.streams.get(name)
        if sf is None:
            sf = self.streams[name] = StreamFold(
                _stream_host(name) if host is None else host,
                capacity=self.capacity,
            )
        return sf

    def serving(self) -> ServingStats:
        """The job-wide serving stats: per-stream digests merged in
        stream-name order (deterministic; see obs/serving.TDigest)."""
        merged = ServingStats(self.capacity)
        for name in sorted(self.streams):
            merged.merge(self.streams[name].serving)
        return merged

    def pipe_schedule(self) -> dict | None:
        """The job's pipeline-schedule cell, merged deterministically:
        every host of a pipelined run emits the same schedule, so pick
        the newest event (ties broken by stream name) — last-wins like
        the per-stream cell."""
        best_key = None
        out = None
        for name in sorted(self.streams):
            ps = self.streams[name].pipe_schedule
            if ps is None:
                continue
            key = (ps.get("ts") or 0.0, name)
            if best_key is None or key >= best_key:
                best_key, out = key, ps
        return out

    def trace_totals(self) -> dict:
        """Job-wide causal-trace reduction: span/mark/request counts plus
        the slowest ROOT request span across every stream — `obs trace
        --slowest-request`'s selection input.  Deterministic merge: the
        per-stream cells are (dur, trace_id) maxes."""
        out = {"spans": 0, "marks": 0, "requests": 0, "slowest": None}
        for name in sorted(self.streams):
            tr = self.streams[name].trace
            out["spans"] += tr["spans"]
            out["marks"] += tr["marks"]
            out["requests"] += tr["requests"]
            cand = tr["slowest"]
            if cand is not None and (
                out["slowest"] is None
                or (cand[0], cand[1])
                > (out["slowest"][0], out["slowest"][1])
            ):
                out["slowest"] = list(cand)
        return out

    # -- in-memory construction (legacy list/stream APIs) -----------------

    @classmethod
    def from_events(cls, events: list[dict], capacity: int = 4096):
        """Fold an already-loaded event list, grouped by the events' own
        host field (the ``summarize_run(events)`` compatibility path)."""
        fold = cls(capacity)
        for e in events:
            h = e.get("host", 0)
            fold.stream(f"events-h{h:03d}.jsonl", host=h).consume(e)
        return fold

    @classmethod
    def from_streams(
        cls, streams: dict[int, list[dict]], capacity: int = 4096
    ):
        """Fold per-host event lists (the ``pod_summary(streams)``
        compatibility path; keys are authoritative host ids)."""
        fold = cls(capacity)
        for h in sorted(streams):
            sf = fold.stream(f"events-h{h:03d}.jsonl", host=h)
            for e in streams[h]:
                sf.consume(e)
        return fold


# ---------------------------------------------------------------------------
# cross-host clock-skew estimation
# ---------------------------------------------------------------------------


def estimate_clock_offsets(
    arrivals: dict[int, dict[str, float]],
) -> dict[int, float] | None:
    """Per-host clock offsets (seconds, mean-centered: positive = this
    host's clock runs ahead) fit from barrier-completion observations.

    Every host of a pod observes the same barrier complete within one
    poll interval of the same true instant, so for host ``h`` and
    barrier ``b``: ``ts[h][b] = T_b + offset_h + noise``.  Restricted to
    the (repoch, barrier) keys EVERY host reported, the least-squares
    solution under ``sum_h offset_h = 0`` is closed-form:
    ``offset_h = mean_b(ts[h][b] - mean_h'(ts[h'][b]))``.  Returns None
    when fewer than two hosts share a barrier key (nothing to fit — the
    timeline then falls back to trusting NTP, the pre-fit behavior)."""
    hosts = sorted(h for h, m in arrivals.items() if m)
    if len(hosts) < 2:
        return None
    shared = None
    for h in hosts:
        keys = set(arrivals[h])
        shared = keys if shared is None else shared & keys
    if not shared:
        return None
    keys = sorted(shared)
    centers = {
        k: statistics.fmean(arrivals[h][k] for h in hosts) for k in keys
    }
    return {
        h: statistics.fmean(arrivals[h][k] - centers[k] for k in keys)
        for h in hosts
    }


# ---------------------------------------------------------------------------
# the resumable on-disk fold
# ---------------------------------------------------------------------------

_HEAD_BYTES = 64


def _head_sig(path: Path, offset: int, fold: JobFold | None = None) -> str:
    """Fingerprint of the first ``min(offset, 64)`` bytes — bytes an
    append-only stream can never rewrite once the cursor passed them, so
    a mismatch proves the file was deleted and re-created (same name,
    possibly LARGER than the old cursor — invisible to a size check)."""
    with open(path, "rb") as f:
        head = f.read(min(offset, _HEAD_BYTES))
    if fold is not None:
        fold.bytes_read += len(head)
    return hashlib.md5(head).hexdigest()


def _fold_tail(sf: StreamFold, path: Path, offset: int, fold: JobFold) -> int:
    """Feed the complete lines appended past ``offset`` into ``sf``;
    returns the new cursor (end of the last complete line)."""
    with open(path, "rb") as f:
        f.seek(offset)
        chunk = f.read()
    fold.bytes_read += len(chunk)
    end = chunk.rfind(b"\n")
    if end < 0:
        return offset  # nothing but a torn/partial line so far
    for line in chunk[: end + 1].splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            event = json.loads(line)
        except json.JSONDecodeError:
            continue  # torn mid-file line (writer died); skip like read_events
        sf.consume(event)
    return offset + end + 1


def _load_sidecar(path: Path, capacity: int) -> dict | None:
    try:
        state = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return None
    if (
        not isinstance(state, dict)
        or state.get("version") != VERSION
        or state.get("capacity") != capacity
        or not isinstance(state.get("files"), dict)
        or not isinstance(state.get("streams"), dict)
    ):
        return None
    return state


def fold_job(
    log_dir: str | os.PathLike,
    job_id: str,
    capacity: int = 4096,
    cache: bool = True,
) -> JobFold:
    """The job's ``JobFold`` over all hosts' streams, reading only the
    bytes appended since the last invocation (``cache=True``; the
    sidecar lives beside the streams so it travels with the log dir).
    ``cache=False`` rebuilds from byte 0 and does not touch the sidecar
    — the cold reference the equivalence tests compare against."""
    from ddl_tpu.obs.report import _job_dir

    job = _job_dir(log_dir, job_id)
    files = sorted(job.glob("events-h*.jsonl"))
    sidecar = job / SIDECAR_NAME
    fold = JobFold(capacity)

    state = _load_sidecar(sidecar, capacity) if cache else None
    offsets: dict[str, int] = {}
    if state is not None:
        # rotation/truncation/re-creation guard: a stream now smaller
        # than its cursor, a consumed head whose bytes changed (deleted
        # and re-created under the same name), or a tracked stream that
        # disappeared outright all mean the accumulated state describes
        # bytes that no longer exist.  Rebuild rather than guess.
        # Cursor-0 files carry no accumulated events — no head check.
        present = {f.name for f in files}
        for f in files:
            offset = int(state["files"].get(f.name, 0))
            if f.stat().st_size < offset or (
                offset > 0
                and state.get("heads", {}).get(f.name)
                != _head_sig(f, offset, fold)
            ):
                state = None
                break
        if state is not None and not set(state["files"]) <= present:
            state = None
    if state is not None:
        # the restore must never be the crash: a JSON-valid sidecar with
        # the wrong inner shape (truncated-then-rewritten, hand-edited,
        # intra-version drift) is "corrupt" per the module contract —
        # discard and rebuild, don't traceback every summarize forever
        try:
            for f in files:
                st = state["streams"].get(f.name)
                if st is not None:
                    fold.streams[f.name] = StreamFold.from_state(st)
                offsets[f.name] = int(state["files"].get(f.name, 0))
        except (KeyError, TypeError, ValueError, IndexError):
            state = None
            fold.streams.clear()
    if state is None:
        offsets = {f.name: 0 for f in files}

    for f in files:
        offsets[f.name] = _fold_tail(
            fold.stream(f.name), f, offsets[f.name], fold
        )

    if cache and files:
        payload = json.dumps({
            "version": VERSION,
            "capacity": capacity,
            "files": offsets,
            "heads": {
                f.name: _head_sig(f, offsets[f.name])
                for f in files if offsets[f.name] > 0
            },
            "streams": {
                name: sf.state_dict() for name, sf in fold.streams.items()
            },
        })
        # pid AND thread id: concurrent folds of the same job (e.g. two
        # scrapes of `obs export --http` landing together) must not
        # interleave writes into one tmp file and install a torn sidecar
        tmp = sidecar.with_name(
            f"{SIDECAR_NAME}.tmp{os.getpid()}.{threading.get_ident()}"
        )
        try:
            tmp.write_text(payload)
            os.replace(tmp, sidecar)
            # the pre-fold serving-only cache is superseded; drop it so
            # the job dir carries one cache generation, not two.  Its
            # state is NOT loaded first — the fold needs phase/period/
            # timeline state the old sidecar never held, so the first
            # run under v3 re-reads every stream from byte 0 regardless
            (job / LEGACY_SIDECAR).unlink(missing_ok=True)
        except OSError:
            # a read-only log mount must not break summarize
            try:
                tmp.unlink(missing_ok=True)
            except OSError:
                pass
    return fold
