"""Causal distributed tracing: one request/step/incident as a linked
timeline across scheduler, engine, barriers, and hosts.

Everything else in the obs stack renders *aggregates* (percentiles,
skew tables, phase means).  When a single request's TTFT blows out or
one pod restart takes 40 seconds, the operator needs to see *that one*
request or incident — which queue it sat in, which batched dispatches
it rode, which host's barrier arrival was late — as a causally-linked
span tree.  This module assembles exactly that from the job's JSONL
streams and emits **Chrome trace-event JSON** loadable in Perfetto
(``ui.perfetto.dev``) or ``chrome://tracing``:

    ddl_tpu obs trace <job> --request ID        one serving request
    ddl_tpu obs trace <job> --slowest-request   the worst one on record
    ddl_tpu obs trace <job> --incident N        Nth incident cluster
    ddl_tpu obs trace <job> --step N            one training step

Span sources (the span model ARCHITECTURE.md documents):

* **native trace events** — ``trace_span``/``trace_mark`` kinds, emitted
  where causality is not reconstructable from aggregate events: the
  serving request path (``serve/engine.py``: request root, queue wait,
  prefill — one span per chunk under chunked prefill — every ridden
  decode dispatch; ``serve/admission.py``: shed).  Ids are
  deterministic paths (``<req>/req``, ``<req>/queue``, ``<req>/d<seq>``)
  — no RNG, so traces are reproducible.  At production request volumes
  set ``DDL_OBS_TRACE_SAMPLE=N`` to emit spans for 1-in-N requests
  (deterministic by request sequence number, not an RNG draw — a
  replay samples the same requests); ``--slowest-request`` then
  selects over the sampled subset only.
* **derived spans** — existing kinds lifted into spans by this builder:
  step phases (``span`` events: t0 = ts - dur), barrier joins
  (``coord_barrier``: arrive_ts -> completed_ts), relaunch-to-first-step
  (``restart_latency``: decision_ts + latency), stalls (age past
  deadline), with anomalies / captures / restart decisions as instants.

Rendering contract: one Perfetto *process* row per (host, unit) where
unit is trainer / supervisor / serve; serving lanes are threads of the
serve process.  Cross-host/process causality is drawn with flow arrows
(``ph: s/f`` pairs): request root -> queue -> prefill -> dispatches ->
retire, restart decision -> every host's join-barrier span -> the
relaunched child's first step, anomaly -> profile capture.  All
timestamps are **clock-offset corrected** with the PR-8 barrier fit
(``fold.estimate_clock_offsets``) before they are merged, so cross-host
ordering reflects true time even when a host's clock drifts by seconds.

Pure stdlib over the event files — no JAX — like the rest of the obs
read path.  Selection (slowest request, clock offsets) reads through
the incremental fold engine; the selected trace's spans are then pulled
with one full parse of the streams (a trace is a debugging artifact for
ONE request/incident, not a per-tick surface).
"""

from __future__ import annotations

import json
import os

__all__ = [
    "INCIDENT_GAP_S",
    "build_chrome_trace",
    "collect_incidents",
    "serve_trace_http",
    "trace_job",
]

# timeline events closer together than this (seconds, skew-corrected)
# belong to the same incident: a stall, the restart it triggers, the
# barrier joins, and the relaunched first step arrive within a few
# seconds of each other, while unrelated incidents are minutes apart
INCIDENT_GAP_S = 30.0

# narrative kinds that ANCHOR an incident cluster (barriers and run
# lifecycle ride along as context, they don't open incidents)
_INCIDENT_KINDS = (
    "anomaly", "stall", "watchdog_exit", "rollback", "profile_capture",
    "supervisor_relaunch", "pod_restart", "peer_stale",
    "restart_latency",
)

# kinds emitted by a supervisor process rather than the trainer child
_SUPERVISOR_KINDS = (
    "supervisor_start", "supervisor_relaunch", "supervisor_done",
    "pod_restart", "peer_stale", "coord_barrier",
)


def _load_streams(log_dir, job_id) -> dict[int, list[dict]]:
    from ddl_tpu.obs.pod import load_pod

    return load_pod(log_dir, job_id)


def _span(host, unit, name, t0, t1, *, tid=0, tname=None, key=None,
          cat=None, args=None):
    return {
        "host": host, "unit": unit, "tid": tid,
        "tname": tname, "name": name, "cat": cat or unit,
        "t0": float(t0), "t1": float(max(t0, t1)),
        "key": key, "args": args or {},
    }


def _mark(host, unit, name, ts, *, tid=0, tname=None, key=None,
          cat=None, args=None):
    return {
        "host": host, "unit": unit, "tid": tid,
        "tname": tname, "name": name, "cat": cat or unit,
        "ts": float(ts), "key": key, "args": args or {},
    }


def _slim_args(e: dict, drop=()) -> dict:
    skip = {
        "ts", "mono", "run", "host", "step", "kind", "stacks",
        "trace", "span", "parent", "name", "cat", "t0", "t1", *drop,
    }
    out = {}
    for k, v in e.items():
        if k in skip:
            continue
        out[k] = v if isinstance(v, (int, float, str, bool)) else str(v)
    return out


# ---------------------------------------------------------------------------
# request traces (native trace events + admit/retire marks)
# ---------------------------------------------------------------------------


def _collect_request(streams, request_id):
    """Spans/marks/flows for one serving request's trace."""
    spans, marks = [], []
    for host in sorted(streams):
        for e in streams[host]:
            kind = e.get("kind")
            if kind == "trace_span" and e.get("trace") == request_id:
                if e.get("t0") is None or e.get("t1") is None:
                    continue  # malformed/hand-written event: skip, not crash
                lane = e.get("lane")
                tid = 0 if e.get("name") in ("request", "queue") else (
                    1 + int(lane) if lane is not None else 0
                )
                tname = "request" if tid == 0 else f"lane {lane}"
                spans.append(_span(
                    host, "serve", e.get("name", "?"), e["t0"], e["t1"],
                    tid=tid, tname=tname, key=e.get("span"),
                    args=_slim_args(e),
                ))
            elif kind == "trace_mark" and e.get("trace") == request_id:
                marks.append(_mark(
                    host, "serve", e.get("name", "?"), e["ts"],
                    key=e.get("span"), args=_slim_args(e),
                ))
            elif (
                kind in ("serve_admit", "serve_retire")
                and e.get("request_id") == request_id
            ):
                marks.append(_mark(
                    host, "serve",
                    "admit" if kind == "serve_admit" else "retire",
                    e["ts"], key=f"{request_id}/{kind}",
                    args=_slim_args(e, drop=("request_id",)),
                ))

    # causal chain: queue -> prefill -> d0 -> d1 -> ... -> retire/shed.
    # The root span is the CONTAINER (it spans the whole chain), so it
    # takes no arrow — a flow from its end would point backward in time.
    by_name = {s["key"]: s for s in spans}
    chain = []
    for k in (f"{request_id}/queue", f"{request_id}/prefill"):
        if k in by_name:
            chain.append(k)
    dispatches = sorted(
        (s for s in spans if s["name"] == "decode"),
        key=lambda s: s["args"].get("dispatch", 0),
    )
    chain.extend(s["key"] for s in dispatches)
    retire = next((m for m in marks if m["name"] == "retire"), None)
    if retire is not None:
        chain.append(retire["key"])
    shed = next((m for m in marks if m["name"] == "shed"), None)
    if shed is not None:
        chain.append(shed["key"])
    flows = [
        (chain[i], chain[i + 1]) for i in range(len(chain) - 1)
    ]
    return spans, marks, flows


# ---------------------------------------------------------------------------
# step traces (derived from phase span events)
# ---------------------------------------------------------------------------


def _collect_step(streams, step):
    spans, marks = [], []
    sched = None  # (host, event) of the newest pipe_schedule on record
    for host in sorted(streams):
        for e in streams[host]:
            if e.get("kind") == "pipe_schedule":
                if sched is None or (e.get("ts") or 0.0) >= (
                    sched[1].get("ts") or 0.0
                ):
                    sched = (host, e)
                continue
            if e.get("kind") != "span" or e.get("step") != step:
                continue
            dur = float(e.get("dur", 0.0))
            ts = float(e.get("ts", 0.0))
            spans.append(_span(
                host, "trainer", e.get("name", "?"), ts - dur, ts,
                tid=int(e.get("depth", 0)),
                tname="phases" if not e.get("depth") else f"depth {e['depth']}",
                key=f"h{host}/{e.get('name')}/{len(spans)}",
                args=_slim_args(e, drop=("dur", "depth")),
            ))
    spans.extend(_schedule_lane_spans(sched, spans))
    return spans, marks, []


def _schedule_lane_spans(sched, phase_spans) -> list[dict]:
    """Per-stage F/B/W schedule lanes for a step trace: the modeled
    clock-loop schedule (``obs/schedule_model.py``), rebuilt from the
    run's ``pipe_schedule`` event and scaled into the step's measured
    phase window, one Perfetto thread per pipeline stage.  The lanes
    are a *model* of where the schedule puts each microbatch's
    forward / activation-backward / weight-backward work (every span
    carries ``modeled: true``) — the measured spans beside them stay
    the ground truth."""
    if sched is None or not phase_spans:
        return []
    from ddl_tpu.obs.schedule_model import schedule_lanes

    host, e = sched
    try:
        lanes = schedule_lanes(
            str(e.get("schedule", "gpipe")), int(e["pipe"]),
            int(e["microbatches"]), int(e.get("virtual") or 1),
        )
    except (KeyError, TypeError, ValueError):
        return []  # malformed event or unmodeled combo: lanes are a bonus
    t0 = min(s["t0"] for s in phase_spans)
    t1 = max(s["t1"] for s in phase_spans)
    makespan = max(u["t1"] for lane in lanes for u in lane)
    scale = (t1 - t0) / makespan if makespan and t1 > t0 else 1e-3
    out = []
    for si, lane in enumerate(lanes):
        for u in lane:
            out.append(_span(
                host, "pipeline", f'{u["phase"]}{u["mb"]}',
                t0 + u["t0"] * scale, t0 + u["t1"] * scale,
                tid=si, tname=f"stage {si}", cat="schedule",
                args={
                    "phase": u["phase"], "mb": u["mb"],
                    "stage": u["stage"], "modeled": True,
                },
            ))
    return out


# ---------------------------------------------------------------------------
# incident traces (derived from the narrative kinds + barriers)
# ---------------------------------------------------------------------------


def collect_incidents(streams, offsets=None) -> list[dict]:
    """Cluster the job's narrative events into incidents: consecutive
    events (skew-corrected order) closer than ``INCIDENT_GAP_S`` merge.
    Returns ``[{"t0", "t1", "events": [(adj_ts, host, event), ...]}]``
    oldest first — the index space of ``obs trace --incident N``."""
    offsets = offsets or {}
    entries = []
    for host in sorted(streams):
        off = offsets.get(host, 0.0) or 0.0
        for e in streams[host]:
            if e.get("kind") not in _INCIDENT_KINDS:
                continue
            ts = float(e.get("ts", 0.0))
            if (
                e.get("kind") == "restart_latency"
                and e.get("decision_ts") is not None
            ):
                # cluster on the DECISION instant, not the first-step
                # completion: a 40s recompile before the first step
                # must not split the restart and its relaunch span
                # into two incidents
                ts = float(e["decision_ts"])
            entries.append((ts - off, host, e))
    entries.sort(key=lambda t: (t[0], t[1]))
    incidents: list[dict] = []
    for adj, host, e in entries:
        if incidents and adj - incidents[-1]["t1"] <= INCIDENT_GAP_S:
            inc = incidents[-1]
            inc["t1"] = max(inc["t1"], adj)
            inc["events"].append((adj, host, e))
        else:
            incidents.append({"t0": adj, "t1": adj, "events": [(adj, host, e)]})
    return incidents


def _collect_incident(streams, incident, offsets):
    """Spans/marks/flows for one incident cluster, pulling in the
    barrier joins and restart-latency spans the cluster's restart
    decision causally produced."""
    offsets = offsets or {}
    spans, marks = [], []
    flows = []
    decision_keys: dict = {}  # epoch -> proposer's decision mark key
    relaunch_keys: dict = {}  # decision_ts -> single-host decision key
    last_anomaly: dict = {}  # (host, type) -> latest anomaly mark key
    n = 0

    # every host emits its own pod_restart event carrying the SAME
    # pod-wide decision (the epoch record); render the decision ONCE,
    # from the proposer's event — its decision_ts was stamped by the
    # proposer's clock, so the proposer's fitted offset is the correct
    # correction (a bystander's offset would misplace the mark by the
    # cross-host drift)
    pod_restarts: dict = {}  # epoch -> (host, event)
    for _adj, host, e in incident["events"]:
        if e["kind"] != "pod_restart":
            continue
        epoch = int(e.get("epoch", 0) or 0)
        if epoch not in pod_restarts or host == e.get("proposer"):
            pod_restarts[epoch] = (host, e)
    for epoch, (host, e) in sorted(pod_restarts.items()):
        key = f"pr/e{epoch}"
        marks.append(_mark(
            host, "supervisor", f"pod_restart:{e.get('reason')}",
            e.get("decision_ts") or e.get("ts"), key=key,
            args=_slim_args(e, drop=("decision_ts",)),
        ))
        decision_keys[epoch] = key

    for adj, host, e in incident["events"]:
        kind = e["kind"]
        n += 1
        if kind == "stall":
            age = float(e.get("age", 0.0))
            spans.append(_span(
                host, "trainer", "stall", e["ts"] - age, e["ts"],
                key=f"stall/{host}/{n}", args=_slim_args(e, drop=("age",)),
            ))
        elif kind == "restart_latency":
            dts = e.get("decision_ts")
            lat = float(e.get("latency", 0.0))
            t0 = float(dts) if dts is not None else e["ts"] - lat
            key = f"rl/{host}/{n}"
            spans.append(_span(
                host, "trainer", "relaunch->first-step", t0, t0 + lat,
                key=key, args=_slim_args(e, drop=("latency", "decision_ts")),
            ))
            repoch = int(e.get("repoch", 0) or 0)
            relaunch_keys.setdefault(("rl", repoch, host), key)
        elif kind == "pod_restart":
            continue  # rendered once above, from the proposer's event
        elif kind == "supervisor_relaunch":
            dts = e.get("decision_ts") or e.get("ts")
            key = f"sr/{host}/{n}"
            marks.append(_mark(
                host, "supervisor", f"relaunch:{e.get('reason')}", dts,
                key=key, args=_slim_args(e, drop=("decision_ts",)),
            ))
            if dts is not None:
                relaunch_keys[("sr", round(float(dts), 3))] = key
        elif kind == "anomaly":
            key = f"an/{host}/{n}"
            marks.append(_mark(
                host, "trainer", f"anomaly:{e.get('type')}", e["ts"],
                key=key, args=_slim_args(e),
            ))
            # events arrive in corrected-ts order, so this always holds
            # the LATEST preceding anomaly of its (host, type) — what a
            # later capture's flow arrow must bind to (a repeated type
            # within one incident must not re-bind earlier captures)
            last_anomaly[(host, str(e.get("type")))] = key
        elif kind == "profile_capture":
            key = f"pc/{host}/{n}"
            marks.append(_mark(
                host, "trainer", "profile_capture", e["ts"], key=key,
                args=_slim_args(e, drop=("digest",)),
            ))
            # the anomaly that armed this window, when it is in view
            src = last_anomaly.get((host, str(e.get("trigger"))))
            if src is not None:
                flows.append((src, key))
        else:
            unit = "supervisor" if kind in _SUPERVISOR_KINDS else "trainer"
            marks.append(_mark(
                host, unit, kind, e["ts"], key=f"{kind}/{host}/{n}",
                args=_slim_args(e),
            ))

    # barrier joins whose completion lands inside the incident window
    # (skew-corrected, with a small grace for the write/observe delta)
    for host in sorted(streams):
        off = offsets.get(host, 0.0) or 0.0
        for e in streams[host]:
            if e.get("kind") != "coord_barrier":
                continue
            done = e.get("completed_ts", e.get("ts", 0.0))
            if not (
                incident["t0"] - 1.0 <= float(done) - off
                <= incident["t1"] + 1.0
            ):
                continue
            arrive = e.get("arrive_ts")
            t0 = (
                float(arrive) if arrive is not None
                else float(done) - float(e.get("wait", 0.0))
            )
            bname = e.get("name", "?")
            key = f"bar/{host}/{bname}"
            spans.append(_span(
                host, "supervisor", f"barrier:{bname}", t0, done,
                key=key, args=_slim_args(
                    e, drop=("completed_ts", "arrive_ts"),
                ),
            ))
            # restart decision -> this host's join barrier
            if bname.startswith("e") and "-join" in bname:
                try:
                    epoch = int(bname[1:].split("-", 1)[0])
                except ValueError:
                    epoch = None
                src = decision_keys.get(epoch)
                if src is not None:
                    flows.append((src, key))
                    # barrier exit -> the relaunched child's FIRST
                    # STEP: the causal target is the relaunch span's
                    # END (decision + latency); binding its start
                    # would point the arrow backward to the decision
                    dst = relaunch_keys.get(("rl", epoch, host))
                    if dst is not None:
                        flows.append((key, dst, "end"))

    # single-host supervision: decision mark -> relaunch->first-step span
    for span in spans:
        if span["name"] != "relaunch->first-step":
            continue
        src = relaunch_keys.get(("sr", round(span["t0"], 3)))
        if src is not None:
            flows.append((src, span["key"]))
    return spans, marks, flows


# ---------------------------------------------------------------------------
# Chrome trace-event JSON assembly
# ---------------------------------------------------------------------------


def build_chrome_trace(
    spans, marks, flows, offsets=None, label: str = "",
) -> dict:
    """Assemble collected spans/marks/flows into a Chrome trace-event
    JSON object (Perfetto/chrome://tracing loadable).  ``offsets`` is
    the per-host clock-offset fit, SUBTRACTED from every timestamp
    before the cross-host merge; ``ts`` is microseconds from the
    earliest corrected instant (always >= 0), event list sorted by
    ``ts`` so consumers see a monotonic stream."""
    offsets = offsets or {}

    def adj(t, host):
        return float(t) - (offsets.get(host, 0.0) or 0.0)

    stamps = [adj(s["t0"], s["host"]) for s in spans]
    stamps += [adj(m["ts"], m["host"]) for m in marks]
    base = min(stamps) if stamps else 0.0

    def us(t, host):
        return max(0, round((adj(t, host) - base) * 1e6))

    pids = {}
    threads = {}
    for item in [*spans, *marks]:
        unit = (item["host"], item["unit"])
        pids.setdefault(unit, len(pids) + 1)
        tname = item.get("tname")
        if tname:
            threads.setdefault((unit, item["tid"]), tname)

    events = []
    for (host, unit), pid in sorted(pids.items(), key=lambda kv: kv[1]):
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": f"h{host} {unit}"},
        })
    for ((unit, tid), tname) in sorted(
        threads.items(), key=lambda kv: (pids[kv[0][0]], kv[0][1])
    ):
        events.append({
            "ph": "M", "name": "thread_name", "pid": pids[unit],
            "tid": tid, "ts": 0, "args": {"name": tname},
        })

    locator = {}  # span/mark key -> (pid, tid, start_us, end_us)
    body = []
    for s in spans:
        pid = pids[(s["host"], s["unit"])]
        t0, t1 = us(s["t0"], s["host"]), us(s["t1"], s["host"])
        if s["key"]:
            locator[s["key"]] = (pid, s["tid"], t0, t1)
        body.append({
            "ph": "X", "name": s["name"], "cat": s["cat"], "pid": pid,
            "tid": s["tid"], "ts": t0, "dur": max(1, t1 - t0),
            "args": s["args"],
        })
    for m in marks:
        pid = pids[(m["host"], m["unit"])]
        ts = us(m["ts"], m["host"])
        if m["key"]:
            locator[m["key"]] = (pid, m["tid"], ts, ts)
        body.append({
            "ph": "i", "s": "t", "name": m["name"], "cat": m["cat"],
            "pid": pid, "tid": m["tid"], "ts": ts, "args": m["args"],
        })
    for i, flow in enumerate(flows):
        src, dst, *rest = flow
        a, b = locator.get(src), locator.get(dst)
        if a is None or b is None:
            continue
        # the arrow leaves the source's end; it lands at the target's
        # start unless the flow names "end" (a span whose causal payoff
        # is its completion, e.g. relaunch -> FIRST STEP)
        dst_ts = b[3] if rest and rest[0] == "end" else b[2]
        body.append({
            "ph": "s", "id": i + 1, "name": "causal", "cat": "flow",
            "pid": a[0], "tid": a[1], "ts": a[3],
        })
        body.append({
            "ph": "f", "bp": "e", "id": i + 1, "name": "causal",
            "cat": "flow", "pid": b[0], "tid": b[1], "ts": dst_ts,
        })
    body.sort(key=lambda e: (e["ts"], e["ph"] != "f"))
    return {
        "displayTimeUnit": "ms",
        "otherData": {
            "tool": "ddl_tpu obs trace",
            "trace": label,
            "clock_offsets": {
                str(h): o for h, o in sorted((offsets or {}).items())
            },
            "base_ts": base,
        },
        "traceEvents": events + body,
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def trace_job(
    log_dir: str | os.PathLike,
    job_id: str,
    *,
    request: str | None = None,
    slowest: bool = False,
    incident: int | None = None,
    step: int | None = None,
    cache: bool = True,
) -> dict:
    """Build one trace for ``job_id`` (exactly one selector).  Clock
    offsets and slowest-request selection come from the incremental
    fold; the selected trace's events come from one full stream parse.
    Raises ``SystemExit`` with an actionable message when the selector
    matches nothing (the CLI surfaces it verbatim)."""
    from ddl_tpu.obs.fold import estimate_clock_offsets, fold_job

    if sum(
        (request is not None, slowest, incident is not None,
         step is not None)
    ) != 1:
        raise SystemExit(
            "obs trace takes exactly one of --request/--slowest-request/"
            "--incident/--step (or --http PORT to serve them all)"
        )
    fold = fold_job(log_dir, job_id, cache=cache)
    if not fold.events:
        raise SystemExit(f"no events for job {job_id!r} under {log_dir}")
    offsets = estimate_clock_offsets({
        sf.host: sf.barrier_ts
        for sf in fold.streams.values() if sf.host is not None
    }) or {}
    streams = _load_streams(log_dir, job_id)

    if slowest:
        cell = fold.trace_totals()["slowest"]
        if cell is None:
            raise SystemExit(
                f"job {job_id!r} carries no request trace spans — serve "
                "through an obs-enabled engine (trace_requests=True, the "
                "default) first"
            )
        request = cell[1]
    if request is not None:
        spans, marks, flows = _collect_request(streams, request)
        if not spans and not marks:
            raise SystemExit(
                f"no trace events for request {request!r} in job "
                f"{job_id!r}"
            )
        label = f"request {request}"
    elif step is not None:
        spans, marks, flows = _collect_step(streams, step)
        if not spans:
            raise SystemExit(
                f"no phase spans for step {step} in job {job_id!r} "
                "(per-step spans may be sampled — DDL_OBS_STEP_SPANS)"
            )
        label = f"step {step}"
    else:
        incidents = collect_incidents(streams, offsets)
        if not 0 <= incident < len(incidents):
            raise SystemExit(
                f"incident {incident} out of range: job {job_id!r} has "
                f"{len(incidents)} incident(s)"
            )
        spans, marks, flows = _collect_incident(
            streams, incidents[incident], offsets
        )
        label = f"incident {incident}"
    return build_chrome_trace(spans, marks, flows, offsets, label=label)


def serve_trace_http(
    log_dir: str | os.PathLike,
    job_id: str,
    port: int,
    cache: bool = True,
    max_requests: int | None = None,
) -> None:
    """``obs trace --http PORT``: serve rendered trace JSON plus a
    Perfetto deep-link index page.

    * ``GET /`` — an HTML index of the job's traceable artifacts: the
      slowest request on record, every incident cluster, and a step
      form; each row links the raw trace JSON and a
      ``ui.perfetto.dev/#!/?url=`` deep link that loads it straight
      into Perfetto (the trace endpoint sends CORS headers for exactly
      that fetch).
    * ``GET /trace.json?request=ID|slowest=1|incident=N|step=N`` — the
      same JSON ``obs trace --out`` writes, built on demand.
    * ``GET /goodput`` — the job's chip-time ledger (obs/goodput.py)
      as HTML with one ``#h<host>-e<repoch>`` anchor per incarnation
      account; each incident row on the index deep-links to the
      account of the incarnation it cost, so "what did this incident
      cost" is one click from "what happened".

    ``max_requests`` bounds the serve loop (tests)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from urllib.parse import parse_qs, quote, urlparse

    served = [0]

    def build(params) -> dict:
        kw: dict = {}
        if params.get("request"):
            kw["request"] = params["request"][0]
        elif params.get("slowest"):
            kw["slowest"] = True
        elif params.get("incident"):
            kw["incident"] = int(params["incident"][0])
        elif params.get("step"):
            kw["step"] = int(params["step"][0])
        else:
            raise SystemExit(
                "trace.json needs one of "
                "request=/slowest=1/incident=/step="
            )
        return trace_job(log_dir, job_id, cache=cache, **kw)

    def index_html(host: str) -> str:
        from ddl_tpu.obs.fold import estimate_clock_offsets, fold_job

        fold = fold_job(log_dir, job_id, cache=cache)
        offsets = estimate_clock_offsets({
            sf.host: sf.barrier_ts
            for sf in fold.streams.values() if sf.host is not None
        }) or {}
        incidents = collect_incidents(
            _load_streams(log_dir, job_id), offsets
        )
        cell = fold.trace_totals()["slowest"]

        def row(label, query):
            url = f"http://{host}/trace.json?{query}"
            deep = f"https://ui.perfetto.dev/#!/?url={quote(url, safe='')}"
            return (
                f"<li>{label} — <a href='/trace.json?{query}'>json</a>"
                f" · <a href='{deep}'>open in Perfetto</a></li>"
            )

        rows = []
        if cell is not None:
            rows.append(row(
                f"slowest request <code>{cell[1]}</code> "
                f"({cell[0]:.3f}s)", "slowest=1",
            ))
        for i, inc in enumerate(incidents):
            kinds = sorted({e["kind"] for _, _, e in inc["events"]})
            # the incarnation this incident cost: its first event's
            # (host, restart epoch) — the /goodput anchor of the
            # account that absorbed the stall/restart/rollback seconds
            _adj, ihost, ie = inc["events"][0]
            repoch = int(ie.get("repoch", 0) or 0)
            rows.append(row(
                f"incident {i}: {len(inc['events'])} event(s) "
                f"({', '.join(kinds)})", f"incident={i}",
            )[:-len("</li>")] + (
                f" · <a href='/goodput#h{ihost}-e{repoch}'>chip-time "
                f"account h{ihost}/e{repoch}</a></li>"
            ))
        body = "\n".join(rows) or "<li>(nothing traceable yet)</li>"
        return (
            "<!doctype html><html><head><meta charset='utf-8'>"
            f"<title>obs trace — {job_id}</title></head><body>"
            f"<h1>obs trace — {job_id}</h1>"
            "<p>Each link loads the clock-corrected Chrome trace JSON; "
            "the Perfetto deep link opens it in ui.perfetto.dev "
            "directly (the server sends CORS headers for that fetch). "
            "Step traces: <code>/trace.json?step=N</code>. "
            "The <a href='/goodput'>goodput ledger</a> carries one "
            "anchor per incarnation account.</p>"
            f"<ul>{body}</ul></body></html>"
        )

    def goodput_html() -> str:
        from ddl_tpu.obs.fold import fold_job
        from ddl_tpu.obs.goodput import CATEGORIES, ledger_from_fold

        fold = fold_job(log_dir, job_id, cache=cache)
        ledger = ledger_from_fold(fold)
        blocks = []
        for a in ledger["incarnations"]:
            anchor = f"h{a['host']}-e{a['repoch']}"
            ratio = f"{a['ratio']:.1%}" if a["ratio"] is not None else "n/a"
            cells = "".join(
                f"<tr><td>{c}</td><td align='right'>"
                f"{a['seconds'][c]:.2f}s</td></tr>"
                for c in CATEGORIES if a["seconds"].get(c, 0.0) > 0
            )
            blocks.append(
                f"<h2 id='{anchor}'>h{a['host']} / epoch {a['repoch']} "
                f"— {a['wall_s']:.1f}s wall, {ratio} productive</h2>"
                f"<table>{cells}</table>"
            )
        tenants = (ledger["job"].get("tenants") or {})
        if tenants:
            rows = "".join(
                f"<tr><td>{t}</td><td>{r.get('class') or '-'}</td>"
                f"<td align='right'>{r['served_s']:.2f}s</td>"
                f"<td align='right'>{r['queued_s']:.2f}s</td>"
                f"<td align='right'>{r['shed_s']:.2f}s</td></tr>"
                for t, r in sorted(tenants.items())
            )
            blocks.append(
                "<h2>per-tenant chip-seconds</h2><table>"
                "<tr><th>tenant</th><th>class</th><th>served</th>"
                f"<th>queued</th><th>shed (modeled)</th></tr>{rows}"
                "</table>"
            )
        body = "\n".join(blocks) or "<p>(no incarnation accounts)</p>"
        return (
            "<!doctype html><html><head><meta charset='utf-8'>"
            f"<title>goodput — {job_id}</title></head><body>"
            f"<h1>goodput — {job_id}</h1>"
            "<p>One account per (host, restart-epoch) incarnation — "
            "the same ledger <code>obs goodput</code> renders; "
            "<a href='/'>back to the trace index</a>.</p>"
            f"{body}</body></html>"
        )

    class Handler(BaseHTTPRequestHandler):
        def _send(self, code, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            # ui.perfetto.dev fetches the trace cross-origin
            self.send_header("Access-Control-Allow-Origin", "*")
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 (http.server API)
            served[0] += 1
            parsed = urlparse(self.path)
            try:
                if parsed.path in ("/", "/index.html"):
                    host = self.headers.get("Host") or (
                        f"localhost:{port}"
                    )
                    self._send(
                        200, index_html(host).encode(),
                        "text/html; charset=utf-8",
                    )
                elif parsed.path == "/goodput":
                    self._send(
                        200, goodput_html().encode(),
                        "text/html; charset=utf-8",
                    )
                elif parsed.path == "/trace.json":
                    trace = build(parse_qs(parsed.query))
                    self._send(
                        200, json.dumps(trace).encode(),
                        "application/json",
                    )
                else:
                    self._send(404, b"not found\n", "text/plain")
            except (SystemExit, ValueError) as e:
                # trace_job's actionable selector errors AND malformed
                # query values (incident=abc) -> 400, not a dead server
                self._send(400, f"{e}\n".encode(), "text/plain")
            except OSError as e:
                self._send(500, f"trace failed: {e}\n".encode(),
                           "text/plain")

        def log_message(self, fmt, *args):  # quiet by default
            pass

    server = ThreadingHTTPServer(("", port), Handler)
    bound = server.server_address[1]
    print(
        f"[obs trace] serving {job_id!r} on :{bound} — index at "
        f"http://localhost:{bound}/ (ctrl-c to stop)"
    )
    try:
        if max_requests is None:
            server.serve_forever()
        else:
            while served[0] < max_requests:
                server.handle_request()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()


def write_trace(trace: dict, out: str) -> str:
    from pathlib import Path

    Path(out).write_text(json.dumps(trace))
    ev = trace["traceEvents"]
    return (
        f"wrote {len(ev)} trace events "
        f"({sum(1 for e in ev if e['ph'] == 'X')} spans, "
        f"{sum(1 for e in ev if e['ph'] == 's')} flows) for "
        f"{trace['otherData']['trace']} to {out} — open in "
        "ui.perfetto.dev or chrome://tracing"
    )
