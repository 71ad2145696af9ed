"""Run inspection over the JSONL event stream.

``python -m ddl_tpu.cli obs <command>``:

    summarize <job_id>          throughput trend, phase breakdown table,
                                decode p50/p95/p99 (latency, queue delay,
                                TTFT, tok/s — obs/serving.py), profile
                                captures, anomalies, stalls, restart
                                latencies, peak HBM, per-host liveness,
                                goodput headline
    goodput <job_id> [--json]   the chip-time ledger (obs/goodput.py):
                                productive vs badput buckets per (host,
                                restart-epoch) incarnation and whole-job
                                — sums to the wall clock by construction,
                                residual reported as `untracked`
    hbm <job_id> [--json]       the device-memory ledger (obs/hbm.py):
                                params / optimizer / KV (cached vs
                                private vs free) / untracked bytes per
                                (host, restart-epoch) incarnation at its
                                peak watermark, static per-program
                                compile-time budgets (hbm_plan) for
                                plan-vs-live reconciliation, and any OOM
                                forensic dumps — categories sum to the
                                watermark by construction
    tail <job_id> [-n N]        last N events, rendered one per line
    diff <job_a> <job_b>        phase/throughput comparison of two runs
    baseline <job_id> --out F   store one run's summary as a JSON baseline
    diff <job> --baseline F     compare a run against a stored baseline;
                                --fail-slowdown 0.5 exits nonzero on a
                                >50% steps/s regression — and, when both
                                runs carry the signals, on a decode p95
                                latency / p99 TTFT / restart-latency
                                inflation or an aggregate tokens/s/chip
                                drop past the same fraction (the CI
                                gate); --fail-goodput-drop F additionally
                                gates the job-level goodput ratio;
                                --fail-hbm-growth F gates the job's peak
                                HBM watermark (obs/hbm.py) against the
                                baseline's — the leak gate;
                                --fail-slo-burn F exits nonzero when the
                                run under test's worst per-tenant SLO
                                error-budget burn rate (obs/slo.py)
                                exceeds F
    slo <job_id> [--json]       per-tenant SLO evaluation (obs/slo.py):
                                declarative per-priority-class budgets
                                (p99 TTFT, p99 latency, availability =
                                1 - shed rate) from the job's slo.json
                                (--slo FILE overrides; built-in defaults
                                otherwise), rendered as error-budget
                                burn rates with fast (newest
                                incarnation) / slow (whole job) windows
                                and page/ticket/ok alert levels
    pod <job_id>                pod-wide view over ALL hosts' streams
                                (obs/pod.py): per-host skew/straggler
                                table with barrier-fit clock offsets,
                                barrier-wait attribution, skew-corrected
                                unified restart/anomaly/capture timeline
    watch <job_id>              live terminal view, refreshed every
                                --interval seconds (obs/watch.py);
                                --once renders a single frame (CI smoke)
    export <job_id>             Prometheus text-format metrics from the
                                same fold state (obs/export.py):
                                --prom FILE writes a scrape file,
                                --http PORT serves /metrics, --once for
                                one-shot emission; decode latency/TTFT
                                additionally render as classic
                                cumulative histograms (_bucket/_sum/
                                _count) next to the quantile gauges
    trace <job_id>              one request/step/incident as causally-
                                linked Chrome trace-event JSON, clock-
                                offset corrected across hosts
                                (obs/trace.py): --request ID |
                                --slowest-request | --incident N |
                                --step N, --out trace.json; --http PORT
                                serves trace JSON + a Perfetto
                                deep-link index instead
    fleet [log_root]            rollup across ALL jobs under a log
                                root (obs/fleet.py): per-job steps/s,
                                MFU, p99 TTFT, restarts, incident
                                counts as a table / --json / --prom
                                combined per-job-labelled scrape

All commands except ``tail`` read through the incremental fold engine
(``obs/fold.py``): a resumable reducer whose sidecar makes every
invocation O(appended bytes) while rendering byte-identically to a cold
full parse (``--no-cache`` forces the cold path).  Pure stdlib + the
event files — no JAX import, so it runs anywhere the NAS/log directory
is mounted (the reference's analysis had the same property for its
CSVs; ``bench/analysis.py`` keeps that role and calls into this module
for the event-side sections).
"""

from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

from ddl_tpu.obs.events import read_events

__all__ = [
    "diff_runs",
    "load_run",
    "main",
    "render_summary",
    "summarize_from_fold",
    "summarize_run",
]


def _job_dir(log_dir: str | os.PathLike, job_id: str) -> Path:
    return Path(log_dir) / "by_job_id" / job_id


def load_run(log_dir: str | os.PathLike, job_id: str) -> list[dict]:
    """All hosts' events for a job, ordered by wall clock (cross-host
    monotonic clocks don't compare; ts is NTP-close).  Full parse — the
    ``tail`` path and external callers that want raw events; the summary
    paths go through ``obs/fold.fold_job`` instead."""
    events = []
    for f in sorted(_job_dir(log_dir, job_id).glob("events-h*.jsonl")):
        events.extend(read_events(f))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def _merge_sorted(fold, attr: str) -> list[dict]:
    """Deterministic cross-stream merge of per-stream event lists: sort
    by (ts, stream name, in-stream position) so cold and resumed folds
    render identically even under ts ties."""
    out = []
    for name in sorted(fold.streams):
        for i, e in enumerate(getattr(fold.streams[name], attr)):
            out.append((e.get("ts", 0.0), name, i, e))
    out.sort(key=lambda t: t[:3])
    return [e for _, _, _, e in out]


def summarize_from_fold(fold) -> dict:
    """Aggregate a ``JobFold`` into the summary dict the CLI renders
    (same shape ``obs baseline`` has always stored)."""
    names = sorted(fold.streams)
    runs: set[str] = set()
    for n in names:
        runs |= fold.streams[n].runs

    # -- representative-host period aggregates ---------------------------
    # Run-level totals come from ONE representative host: every host
    # emits its own period events for the same global periods, so
    # summing across hosts would report N-times-inflated steps/elapsed/
    # phase seconds on exactly the multihost runs this tool targets.
    # (The per-host section below keeps the per-host view.)
    phost: dict[int, dict] = {}
    for n in names:
        for h, agg in fold.streams[n].phost.items():
            m = phost.setdefault(h, {
                "n": 0, "steps": 0, "elapsed": 0.0, "compiles": 0,
                "hbm": None, "phases": {}, "sps": [], "sown": {},
            })
            m["n"] += agg["n"]
            m["steps"] += agg["steps"]
            m["elapsed"] += agg["elapsed"]
            m["compiles"] += agg["compiles"]
            if agg["hbm"] is not None:
                m["hbm"] = (
                    agg["hbm"] if m["hbm"] is None
                    else max(m["hbm"], agg["hbm"])
                )
            for ph, dur in agg["phases"].items():
                m["phases"][ph] = m["phases"].get(ph, 0.0) + dur
            m["sps"].extend(agg["sps"])
            m["sown"].update(agg["sown"])

    if phost:
        rep = phost[min(phost)]
        phases = dict(rep["phases"])
        periods_n, steps = rep["n"], rep["steps"]
        elapsed, compiles = rep["elapsed"], rep["compiles"]
        hbm, sps, sown = rep["hbm"], rep["sps"], rep["sown"]
    else:
        # span-only streams (e.g. decode) still get a phase breakdown
        # from top-level spans (a parent's duration already contains its
        # children's, so deeper spans would double-count)
        phases = {}
        for n in names:
            for ph, dur in fold.streams[n].span_sums.items():
                phases[ph] = phases.get(ph, 0.0) + dur
        periods_n = steps = compiles = 0
        elapsed, hbm, sps, sown = 0.0, None, [], {}

    half = len(sps) // 2
    trend = None
    if half >= 1:
        first = sum(sps[:half]) / half
        second = sum(sps[half:]) / (len(sps) - half)
        trend = {"first_half": first, "second_half": second,
                 "ratio": second / first if first else None}

    # -- per-host liveness (events' own host field) ----------------------
    # span/heartbeat steps are one global monotone counter per host, so
    # they are the straggler comparator; period events' step column is
    # the CSV 'epoch' index (a different unit for the epoch families)
    # and is used only when a host emitted no finer-grained signal.
    hosts: dict[int, dict] = {}
    for n in names:
        for h, r in fold.streams[n].hosts.items():
            m = hosts.setdefault(h, {
                "last_step": None, "last_ts": None, "stalls": 0,
                "_pstep": None, "_pstep_ts": None,
            })
            if r["last_step"] is not None:
                m["last_step"] = (
                    r["last_step"] if m["last_step"] is None
                    else max(m["last_step"], r["last_step"])
                )
            if r["last_ts"] is not None and (
                m["last_ts"] is None or r["last_ts"] > m["last_ts"]
            ):
                m["last_ts"] = r["last_ts"]
            m["stalls"] += r["stalls"]
            if r["pstep"] is not None and (
                m["_pstep_ts"] is None
                or (r["pstep_ts"] or 0.0) >= m["_pstep_ts"]
            ):
                m["_pstep"] = r["pstep"]
                m["_pstep_ts"] = r["pstep_ts"] or 0.0
    for m in hosts.values():
        if m["last_step"] is None:
            m["last_step"] = m["_pstep"]
        m.pop("_pstep")
        m.pop("_pstep_ts")

    # -- serving percentiles (per-stream digests merged) -----------------
    stats = fold.serving()
    decode = stats.summary()
    if decode is not None and decode["mean_tok_per_s"] is None:
        # no warm request at all (single-request smokes): fall back to
        # the all-request rates so the legacy mean stays populated.  A
        # rate of exactly 0.0 is present, not missing (falsy-drop bug
        # class)
        decode["mean_tok_per_s"] = (
            stats.all_rate_sum / stats.all_rate_n
            if stats.all_rate_n else None
        )

    # -- restart latency (decision -> first step, per restart epoch) -----
    # running aggregates merged across streams (bounded state however
    # many restarts a run survives)
    n = 0
    total_lat = 0.0
    mx = last = last_ts = None
    by_repoch: dict[int, list] = {}
    for name in names:
        rl = fold.streams[name].restart_latency
        if not rl["n"]:
            continue
        n += rl["n"]
        total_lat += rl["sum"]
        mx = rl["max"] if mx is None else max(mx, rl["max"])
        if last_ts is None or (rl["last_ts"] or 0.0) >= last_ts:
            last = rl["last"]
            last_ts = rl["last_ts"] or 0.0
        for rep, (ts, lat) in rl["by_repoch"].items():
            prev = by_repoch.get(int(rep))
            if prev is None or ts >= prev[0]:
                by_repoch[int(rep)] = [ts, lat]
    restart_latency = None
    if n:
        restart_latency = {
            "count": n,
            "mean": total_lat / n,
            "max": mx,
            "last": last,
            "by_repoch": {rep: v[1] for rep, v in by_repoch.items()},
        }

    counts = {
        key: sum(fold.streams[nm].totals[key] for nm in names)
        for key in ("anomalies", "stalls", "captures")
    }

    # -- serving engine counters (admits/sheds + prefix-cache economics) -
    serve = None

    def _ssum(key):
        return sum(fold.streams[nm].serve.get(key, 0) for nm in names)

    admits = _ssum("admit")
    sheds = _ssum("shed")
    # sheds alone must surface too: a pool so misconfigured that every
    # request sheds before the first admit is exactly when an operator
    # reads this section
    if admits or sheds:
        cached = _ssum("cached_tokens")
        computed = _ssum("prefill_tokens")
        total_prompt = cached + computed
        serve = {
            "admits": admits,
            "sheds": sheds,
            "retires": _ssum("retire"),
            "prefix_hits": _ssum("prefix_hits"),
            "prefix_hit_tokens": _ssum("prefix_hit_tokens"),
            "prefix_inserts": _ssum("prefix_inserts"),
            "cow_copies": _ssum("cow_copies"),
            "cached_tokens": cached,
            "prefill_tokens": computed,
            "prefix_hit_rate": (
                cached / total_prompt if total_prompt else None
            ),
        }

    # -- causal-trace reduction (obs/trace.py kinds) ---------------------
    tr = fold.trace_totals()
    trace = None
    if tr["spans"] or tr["marks"]:
        trace = {
            "spans": tr["spans"],
            "marks": tr["marks"],
            "requests": tr["requests"],
            "slowest": (
                {"request": tr["slowest"][1], "dur": tr["slowest"][0]}
                if tr["slowest"] is not None else None
            ),
        }

    # -- goodput ledger (obs/goodput.py — one fold, every surface) -------
    from ddl_tpu.obs.goodput import ledger_from_fold

    goodput = ledger_from_fold(fold)

    # -- HBM ledger (obs/hbm.py — sums-to-watermark memory account) ------
    from ddl_tpu.obs.hbm import summary_from_fold as hbm_summary_from_fold

    hbm_section = hbm_summary_from_fold(fold)

    return {
        "runs": sorted(runs),
        "events": fold.events,
        "periods": periods_n,
        "steps": steps,
        "elapsed": elapsed,
        "compiles": compiles,
        "phases": phases,
        "throughput_trend": trend,
        "anomalies": _merge_sorted(fold, "anomalies"),
        "stalls": _merge_sorted(fold, "stalls"),
        # totals keep counting past the per-stream retention cap
        # (fold.MAX_EVENTS_PER_LIST); the lists above are the retained
        # tails
        "counts": counts,
        "peak_hbm_bytes": hbm,
        "hosts": hosts,
        "decode": decode,
        "serve": serve,
        "profile_captures": _merge_sorted(fold, "captures"),
        "restart_latency": restart_latency,
        "trace": trace,
        "pipe_schedule": fold.pipe_schedule(),
        "goodput": goodput,
        "hbm": hbm_section,
        # the model's sown step counters as the latest period read them
        # (events.SOWN_COUNTERS); absent for a program that sows none
        **({"step_counters": dict(sorted(sown.items()))} if sown else {}),
    }


def summarize_run(events: list[dict], decode_stats=None) -> dict:
    """Aggregate an already-loaded event list (compatibility path for
    callers holding raw events — ``bench/analysis.py``, tests).  The CLI
    reads through ``obs/fold.fold_job`` instead, which produces the same
    summary in O(appended bytes).  ``decode_stats`` optionally overrides
    the serving section with a pre-built ``ServingStats``."""
    from ddl_tpu.obs.fold import JobFold

    fold = JobFold.from_events(events)
    summary = summarize_from_fold(fold)
    if decode_stats is not None:
        decode = decode_stats.summary()
        if decode is not None and decode["mean_tok_per_s"] is None:
            decode["mean_tok_per_s"] = (
                decode_stats.all_rate_sum / decode_stats.all_rate_n
                if decode_stats.all_rate_n else None
            )
        summary["decode"] = decode
    return summary


def _count(s: dict, key: str, list_key: str | None = None) -> int:
    """An incident total: the running count when the summary carries one
    (fold-era summaries), else the event list's length (stored baselines
    from before the retention cap)."""
    c = (s.get("counts") or {}).get(key)
    return c if c is not None else len(s.get(list_key or key) or [])


def _section_header(label: str, total: int, shown: int) -> str:
    trunc = f", last {shown} shown" if shown < total else ""
    return f"-- {label} ({total}{trunc}) --"


def render_summary(s: dict, job_id: str = "") -> str:
    lines = []
    title = f"run summary{f' — {job_id}' if job_id else ''}"
    lines.append(f"== {title} ==")
    lines.append(
        f"runs: {len(s['runs'])} | events: {s['events']} | periods: "
        f"{s['periods']} | steps: {s['steps']} | compiles: {s['compiles']}"
    )
    trend = s["throughput_trend"]
    if trend:
        lines.append(
            f"throughput: {trend['first_half']:.2f} -> "
            f"{trend['second_half']:.2f} steps/s "
            f"(x{trend['ratio']:.2f} second half vs first)"
        )
    # `is not None`, not truthiness: a legitimately-zero watermark (fresh
    # simulated device) must still print — dropping it made the summary
    # look like HBM was never measured at all
    if s["peak_hbm_bytes"] is not None:
        lines.append(f"peak HBM: {s['peak_hbm_bytes'] / 1e9:.2f} GB")
    if s.get("step_counters"):
        lines.append("step counters (latest period): " + ", ".join(
            f"{k} {v:g}" for k, v in s["step_counters"].items()))
    hb = s.get("hbm")
    if hb:
        from ddl_tpu.obs.hbm import fmt_bytes

        line = f"hbm: peak {fmt_bytes(hb['peak_bytes'])}"
        if hb.get("limit_bytes"):
            line += f" / limit {fmt_bytes(hb['limit_bytes'])}"
        if hb.get("headroom_bytes") is not None:
            line += f" | headroom {fmt_bytes(hb['headroom_bytes'])}"
        top = hb.get("top") or []
        if top:
            line += " | top: " + ", ".join(
                f"{c} {fmt_bytes(b)}" for c, b in top
            )
        if hb.get("oom_count"):
            line += f" | OOM dumps: {hb['oom_count']}"
        line += f" — `ddl_tpu obs hbm{f' {job_id}' if job_id else ''}`"
        lines.append(line)
    ps = s.get("pipe_schedule")
    if ps:
        line = (
            f"pipeline: {ps.get('schedule')} pipe={ps.get('pipe')} "
            f"microbatches={ps.get('microbatches')} "
            f"virtual={ps.get('virtual')}"
        )
        if ps.get("bubble_fraction") is not None:
            line += (
                f" | modeled bubble {ps['bubble_fraction']:.1%} of "
                f"stage-time ({ps.get('idle_units')} idle / "
                f"{ps.get('makespan')} unit makespan)"
            )
        lines.append(line)
    gp = s.get("goodput")
    if gp and gp["job"]["wall_s"] > 0:
        job = gp["job"]
        ratio = job["ratio"]
        line = (
            f"goodput: "
            + (f"{ratio:.1%}" if ratio is not None else "n/a")
            + f" of {job['wall_s']:.1f}s chip-time productive"
        )
        dom = job.get("dominant_badput")
        if dom:
            cat, sec = dom
            line += (
                f" | top badput: {cat} {sec:.1f}s "
                f"({sec / job['wall_s']:.1%})"
            )
        line += (
            f" — `ddl_tpu obs goodput{f' {job_id}' if job_id else ''}`"
        )
        lines.append(line)
        # the newest start the lowest host recorded (every host's is its
        # own; the phase table reads that host too), stage by stage
        # (setup.* spans) and compile by compile (compile.* spans before
        # the first period); the stages hold the compiles made inside them
        inc = max(
            (a for a in gp["incarnations"] if a.get("setup")),
            key=lambda a: (-a["host"], a["repoch"]), default=None,
        )
        if inc:
            su = inc["setup"]
            lines.append(
                f"set-up (h{inc['host']}/e{inc['repoch']}): "
                + ", ".join(
                    f"{k} {su[k]:.2f}s"
                    for k in ("boot", "model", "data", "plan") if k in su
                )
                + f" | compiles before the first period: trace+lower "
                f"{su.get('trace_lower', 0.0):.2f}s, backend "
                f"{su.get('backend', 0.0):.2f}s ({su.get('hits', 0)} cache "
                f"hit(s), {su.get('misses', 0)} made)"
            )
    rl = s.get("restart_latency")
    if rl:
        lines.append(
            f"restart latency: {rl['count']} restart(s), last "
            f"{rl['last']:.1f}s decision->first-step (max {rl['max']:.1f}s)"
        )
    if s["phases"]:
        total = sum(s["phases"].values()) or 1.0
        lines.append("-- phase breakdown --")
        lines.append(f"{'phase':<12} {'total_s':>10} {'share':>7}")
        for name, dur in sorted(
            s["phases"].items(), key=lambda kv: -kv[1]
        ):
            lines.append(f"{name:<12} {dur:>10.3f} {dur / total:>6.1%}")
    if s["decode"]:
        d = s["decode"]
        rate = (
            f"{d['mean_tok_per_s']:.1f} tok/s"
            if d["mean_tok_per_s"] is not None else "n/a"
        )
        cold = ""
        if d.get("cold"):
            # all-cold runs fall back to the cold rates for the mean, so
            # "excluded" would mislabel exactly what produced the number
            cold = (
                f" ({d['cold']} cold, compile included)"
                if d["cold"] >= d["requests"]
                else f" ({d['cold']} cold excluded)"
            )
        lines.append(
            f"decode: {d['requests']} requests, {d['tokens']} tokens, "
            f"{rate}{cold}"
        )
        if d.get("agg_tok_per_s") is not None:
            chips = d.get("chips", 1)
            lines.append(
                f"serving aggregate: {d['agg_tok_per_s']:.1f} tok/s over "
                f"the warm span "
                f"({d['agg_tok_per_s_per_chip']:.1f} tok/s/chip on "
                f"{chips} chip(s))"
            )
        if d.get("percentiles"):
            from ddl_tpu.obs.serving import render_percentiles

            lines.append("-- decode percentiles (warm requests) --")
            lines.extend(render_percentiles(d["percentiles"]))
        tenants = d.get("tenants") or {}
        if tenants:
            lines.append("-- per-tenant (warm requests) --")
            lines.append(
                f"{'tenant':<14}{'class':<14}{'reqs':>6}"
                f"{'p99 ttft':>10}{'p99 lat':>10}{'tokens':>8}"
            )

            def _tp99(pct: dict, metric: str) -> str:
                v = (pct.get(metric) or {}).get("p99")
                return f"{v:>10.4g}" if v is not None else f"{'n/a':>10}"

            for t in sorted(tenants):
                tb = tenants[t]
                pct = tb.get("percentiles") or {}
                lines.append(
                    f"{t:<14}{(tb.get('class') or '-'):<14}"
                    f"{tb['requests']:>6}"
                    + _tp99(pct, "ttft_s") + _tp99(pct, "latency_s")
                    + f"{tb['tokens']:>8}"
                )
    sv = s.get("serve")
    if sv:
        rate = sv.get("prefix_hit_rate")
        rate_s = f"{rate:.0%}" if rate is not None else "n/a"
        lines.append(
            f"serve: {sv['admits']} admit(s), {sv['sheds']} shed(s) | "
            f"prefix cache: {sv['prefix_hits']} hit(s), "
            f"{sv['cached_tokens']} cached / {sv['prefill_tokens']} "
            f"computed prompt tokens ({rate_s} hit rate), "
            f"{sv['cow_copies']} cow cop(ies)"
        )
    tr = s.get("trace")
    if tr and tr.get("slowest"):
        sl = tr["slowest"]
        lines.append(
            f"traced requests: {tr['requests']} | slowest: "
            f"{sl['request']} ({sl['dur']:.3f}s) — "
            f"`ddl_tpu obs trace{f' {job_id}' if job_id else ''} "
            f"--request {sl['request']}`"
        )
    captures = s.get("profile_captures") or []
    if captures:
        lines.append(_section_header(
            "profile captures",
            _count(s, "captures", "profile_captures"), len(captures),
        ))
        for c in captures:
            if not c.get("ok"):
                lines.append(
                    f"  [failed] {c.get('trigger', '?')}: {c.get('error')}"
                )
                continue
            digest = c.get("digest") or {}
            top = ", ".join(
                f"{k} {v:.1f}ms"
                for k, v in list(digest.get("ops", {}).items())[:3]
            )
            lines.append(
                f"  [{c.get('trigger')}] step {c.get('step')}: "
                f"{c.get('trace_dir')}"
                + (f" | {top}" if top else "")
                + (
                    f" | {c['suppressed']} trigger(s) absorbed"
                    if c.get("suppressed") else ""
                )
            )
    lines.append(_section_header(
        "anomalies", _count(s, "anomalies"), len(s["anomalies"]),
    ))
    for a in s["anomalies"]:
        base = (
            f" vs baseline {a['baseline']:.4g}"
            if a.get("baseline") is not None else ""
        )
        lines.append(
            f"  [{a.get('type')}] step {a.get('idx', a.get('step'))}: "
            f"value {a.get('value', float('nan')):.4g}{base}"
        )
    if s["stalls"]:
        lines.append(_section_header(
            "stalls", _count(s, "stalls"), len(s["stalls"]),
        ))
        for st in s["stalls"]:
            stacks_n = st.get("stacks_n", len(st.get("stacks") or {}))
            lines.append(
                f"  host {st.get('host')}: last step {st.get('step')}, "
                f"{st.get('age', 0):.1f}s past deadline "
                f"{st.get('deadline', 0):.1f}s "
                f"({stacks_n} thread stacks captured)"
            )
    if len(s["hosts"]) > 1:
        lines.append("-- hosts --")
        steps = {h: r["last_step"] for h, r in s["hosts"].items()}
        ahead = max((v for v in steps.values() if v is not None), default=None)
        for h, rec in sorted(s["hosts"].items()):
            behind = (
                f" (behind by {ahead - rec['last_step']})"
                if ahead is not None and rec["last_step"] is not None
                and rec["last_step"] < ahead
                else ""
            )
            lines.append(
                f"  host {h}: last step {rec['last_step']}"
                f"{behind}, stalls {rec['stalls']}"
            )
    return "\n".join(lines)


def _rate(s: dict) -> float | None:
    return s["steps"] / s["elapsed"] if s["elapsed"] else None


def diff_runs(sa: dict, sb: dict, job_a: str, job_b: str) -> str:
    lines = [f"== diff: {job_a} vs {job_b} =="]
    ra, rb = _rate(sa), _rate(sb)
    if ra and rb:
        lines.append(
            f"steps/s: {ra:.2f} vs {rb:.2f} (x{rb / ra:.2f})"
        )
    lines.append(f"{'phase':<12} {job_a[:14]:>14} {job_b[:14]:>14} {'delta':>8}")
    for name in sorted(set(sa["phases"]) | set(sb["phases"])):
        a = sa["phases"].get(name, 0.0)
        b = sb["phases"].get(name, 0.0)
        delta = f"{(b - a) / a:+.0%}" if a else "new"
        lines.append(f"{name:<12} {a:>13.3f}s {b:>13.3f}s {delta:>8}")
    lines.append(
        f"anomalies: {_count(sa, 'anomalies')} vs "
        f"{_count(sb, 'anomalies')} | "
        f"stalls: {_count(sa, 'stalls')} vs {_count(sb, 'stalls')} | "
        f"compiles: {sa['compiles']} vs {sb['compiles']}"
    )
    la, lb = _restart_latency(sa), _restart_latency(sb)
    if la is not None and lb is not None:
        lines.append(
            f"restart latency (max): {la:.1f}s vs {lb:.1f}s "
            f"(x{lb / la:.2f})" if la else
            f"restart latency (max): {la:.1f}s vs {lb:.1f}s"
        )
    ga, gb = _goodput_ratio(sa), _goodput_ratio(sb)
    if ga is not None and gb is not None:
        lines.append(
            f"goodput: {ga:.1%} vs {gb:.1%}"
            + (f" (x{gb / ga:.2f})" if ga else "")
        )
    pa, pb = _decode_percentiles(sa), _decode_percentiles(sb)
    if pa and pb:
        lines.append(
            f"{'decode':<14} {job_a[:14]:>14} {job_b[:14]:>14} {'delta':>8}"
        )
        for metric in sorted(set(pa) & set(pb)):
            for q in ("p50", "p95", "p99"):
                a, b = pa[metric].get(q), pb[metric].get(q)
                if a is None or b is None:
                    continue
                delta = f"{(b - a) / a:+.0%}" if a else "new"
                lines.append(
                    f"{metric + ':' + q:<14} {a:>14.4g} {b:>14.4g} "
                    f"{delta:>8}"
                )
    return "\n".join(lines)


def _decode_percentiles(s: dict) -> dict | None:
    """A summary's decode percentile block (None when the run — or a
    stored pre-percentile baseline — has none)."""
    d = s.get("decode")
    return d.get("percentiles") if d else None


def _restart_latency(s: dict) -> float | None:
    """A summary's max restart latency (None when the run never
    restarted, or the baseline predates the field)."""
    rl = s.get("restart_latency")
    return rl.get("max") if rl else None


def _goodput_ratio(s: dict) -> float | None:
    """A summary's job-level goodput ratio (None when the run carries
    no account, or a stored baseline predates the ledger)."""
    gp = s.get("goodput")
    return (gp.get("job") or {}).get("ratio") if gp else None


def _render_event(e: dict) -> str:
    kind = e.get("kind", "?")
    base = f"[h{e.get('host', 0)}] {kind:<10} step={e.get('step')}"
    extras = {
        k: v
        for k, v in e.items()
        if k not in ("ts", "mono", "run", "host", "step", "kind", "stacks")
    }
    body = " ".join(
        f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in extras.items()
    )
    return f"{base} {body}"


def _fold_or_exit(args):
    from ddl_tpu.obs.fold import fold_job

    fold = fold_job(
        args.log_dir, getattr(args, "job_id", None) or args.job_a,
        cache=not args.no_cache,
    )
    if not fold.events:
        job = getattr(args, "job_id", None) or args.job_a
        raise SystemExit(
            f"no events for job {job!r} under {args.log_dir} "
            f"(looked for {_job_dir(args.log_dir, job)}/events-h*.jsonl)"
        )
    return fold


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="ddl_tpu obs", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    # shared flags live on a parent so they are accepted after the
    # subcommand too (``obs summarize job --log-dir DIR``)
    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--log-dir", default="training_logs")
    common.add_argument(
        "--no-cache", action="store_true",
        help="ignore and do not write the incremental fold sidecar "
        "(cold full parse; the reference the cache must match)",
    )
    sub = ap.add_subparsers(dest="command", required=True)
    p_sum = sub.add_parser(
        "summarize", parents=[common], help="one run's summary"
    )
    p_sum.add_argument("job_id")
    p_tail = sub.add_parser(
        "tail", parents=[common], help="last N events of a run"
    )
    p_tail.add_argument("job_id")
    p_tail.add_argument("-n", type=int, default=20)
    p_diff = sub.add_parser(
        "diff", parents=[common],
        help="compare two runs, or one run against a stored baseline",
    )
    p_diff.add_argument("job_a")
    p_diff.add_argument("job_b", nargs="?")
    p_diff.add_argument(
        "--baseline",
        help="stored baseline JSON (from `obs baseline`) to diff "
        "job_a against instead of a second job",
    )
    p_diff.add_argument(
        "--fail-slowdown", type=float, default=None, metavar="FRAC",
        help="CI regression gate: exit nonzero when the run under test "
        "— job_a with --baseline, else job_b — is more than FRAC "
        "slower (steps/s) than its comparison run",
    )
    p_diff.add_argument(
        "--fail-goodput-drop", type=float, default=None, metavar="FRAC",
        help="CI goodput gate: exit nonzero when the run under test's "
        "job-level goodput ratio (productive chip-time fraction, "
        "obs/goodput.py) is more than FRAC below the comparison run's "
        "— both sides must carry a goodput account (regenerate a "
        "pre-ledger baseline first)",
    )
    p_diff.add_argument(
        "--fail-hbm-growth", type=float, default=None, metavar="FRAC",
        help="CI memory gate: exit nonzero when the run under test's "
        "peak HBM watermark (obs/hbm.py) is more than FRAC above the "
        "comparison run's — catches leaks and silent footprint "
        "regressions; both sides must carry an hbm account "
        "(regenerate a pre-ledger baseline first)",
    )
    p_diff.add_argument(
        "--fail-slo-burn", type=float, default=None, metavar="BURN",
        help="CI SLO gate: exit nonzero when the run under test's worst "
        "per-tenant error-budget burn rate (obs/slo.py; 1.0 = spending "
        "exactly the budget) exceeds BURN — the run must carry "
        "per-tenant serving data (a pre-tenant stream must not pass "
        "silently)",
    )
    p_diff.add_argument(
        "--slo", metavar="FILE", default=None,
        help="explicit SLO config for --fail-slo-burn (default: the "
        "run-under-test job dir's slo.json, else built-in defaults)",
    )
    p_slo = sub.add_parser(
        "slo", parents=[common],
        help="per-tenant SLO evaluation: error-budget burn rates per "
        "priority class from declarative budgets (obs/slo.py)",
    )
    p_slo.add_argument("job_id")
    p_slo.add_argument(
        "--json", action="store_true",
        help="emit the evaluation as JSON instead of the rendered view",
    )
    p_slo.add_argument(
        "--slo", metavar="FILE", default=None,
        help="explicit SLO config JSON (default: the job dir's "
        "slo.json, else built-in defaults)",
    )
    p_good = sub.add_parser(
        "goodput", parents=[common],
        help="end-to-end chip-time account: productive vs badput per "
        "(host, restart-epoch) incarnation and whole-job "
        "(obs/goodput.py)",
    )
    p_good.add_argument("job_id")
    p_good.add_argument(
        "--json", action="store_true",
        help="emit the ledger as JSON instead of the rendered tables",
    )
    p_hbm = sub.add_parser(
        "hbm", parents=[common],
        help="exhaustive device-memory account: params/optimizer/KV/"
        "untracked per (host, restart-epoch) incarnation, static "
        "per-program budgets, OOM forensics (obs/hbm.py)",
    )
    p_hbm.add_argument("job_id")
    p_hbm.add_argument(
        "--json", action="store_true",
        help="emit the account as JSON instead of the rendered tables",
    )
    p_base = sub.add_parser(
        "baseline", parents=[common],
        help="store one run's summary as a JSON baseline for later diffs",
    )
    p_base.add_argument("job_id")
    p_base.add_argument("--out", default="obs_baseline.json")
    p_pod = sub.add_parser(
        "pod", parents=[common],
        help="pod-wide view over all hosts' streams: skew/straggler "
        "table, barrier waits, unified timeline (obs/pod.py)",
    )
    p_pod.add_argument("job_id")
    p_pod.add_argument(
        "--timeline", type=int, default=40, metavar="N",
        help="show at most the last N timeline events (default 40)",
    )
    p_pod.add_argument(
        "--json", action="store_true",
        help="emit the pod summary as JSON instead of the rendered view",
    )
    p_watch = sub.add_parser(
        "watch", parents=[common],
        help="live terminal view over all hosts' streams, refreshed "
        "through the incremental fold engine (obs/watch.py)",
    )
    p_watch.add_argument("job_id")
    p_watch.add_argument(
        "--interval", type=float, default=2.0, metavar="S",
        help="MAXIMUM seconds between redraws (default 2); the loop "
        "polls stream sizes/mtimes and redraws as soon as anything "
        "was appended (push mode)",
    )
    p_watch.add_argument(
        "--once", action="store_true",
        help="render one frame and exit (CI smoke / scripting)",
    )
    p_exp = sub.add_parser(
        "export", parents=[common],
        help="Prometheus text-format metrics from the fold state "
        "(obs/export.py)",
    )
    p_exp.add_argument("job_id")
    p_exp.add_argument(
        "--prom", metavar="FILE", default=None,
        help="write the scrape to FILE (default: stdout)",
    )
    p_exp.add_argument(
        "--http", metavar="PORT", type=int, default=None,
        help="serve GET /metrics on PORT instead of writing a file",
    )
    p_exp.add_argument(
        "--once", action="store_true",
        help="emit one scrape and exit (with --prom or stdout)",
    )
    p_exp.add_argument(
        "--interval", type=float, default=15.0, metavar="S",
        help="rewrite interval for --prom without --once (default 15)",
    )
    p_trace = sub.add_parser(
        "trace", parents=[common],
        help="one request/step/incident as causally-linked Chrome "
        "trace-event JSON (Perfetto-loadable; obs/trace.py)",
    )
    p_trace.add_argument("job_id")
    sel = p_trace.add_mutually_exclusive_group(required=False)
    sel.add_argument(
        "--http", metavar="PORT", type=int, default=None,
        help="serve rendered trace JSON plus a Perfetto deep-link "
        "index page on PORT instead of writing one trace file: "
        "GET / lists the slowest request and every incident with "
        "ui.perfetto.dev deep links; GET /trace.json?request=ID|"
        "slowest=1|incident=N|step=N builds any trace on demand",
    )
    sel.add_argument(
        "--request", metavar="ID",
        help="trace one serving request by id",
    )
    sel.add_argument(
        "--slowest-request", action="store_true",
        help="trace the slowest request on record (fold-selected). "
        "Under trace sampling (DDL_OBS_TRACE_SAMPLE=N emits spans for "
        "1-in-N requests, deterministic by request sequence number) "
        "this is the slowest SAMPLED request — an untraced outlier is "
        "invisible here",
    )
    sel.add_argument(
        "--incident", type=int, metavar="N",
        help="trace the Nth incident cluster (0 = oldest; stalls/"
        "anomalies/restarts with their barriers and relaunch spans)",
    )
    sel.add_argument(
        "--step", type=int, metavar="N",
        help="trace one training step's phase spans across hosts",
    )
    p_trace.add_argument(
        "--out", default="trace.json", metavar="FILE",
        help="output path for the trace JSON (default trace.json)",
    )
    p_fleet = sub.add_parser(
        "fleet", parents=[common],
        help="rollup across ALL jobs under a log root: per-job steps/s, "
        "MFU, p99 TTFT, restarts, incidents (obs/fleet.py)",
    )
    p_fleet.add_argument(
        "log_root", nargs="?", default=None,
        help="log root holding by_job_id/ (default: --log-dir)",
    )
    p_fleet.add_argument(
        "--json", action="store_true",
        help="emit the fleet summary as JSON instead of the table",
    )
    p_fleet.add_argument(
        "--prom", metavar="FILE", default=None,
        help="also write one combined Prometheus scrape with per-job-"
        "labelled series (the obs export surface, across jobs)",
    )
    args = ap.parse_args(argv)

    if args.command == "summarize":
        fold = _fold_or_exit(args)
        print(render_summary(summarize_from_fold(fold), args.job_id))
    elif args.command == "goodput":
        from ddl_tpu.obs.goodput import ledger_from_fold, render_goodput

        ledger = ledger_from_fold(_fold_or_exit(args))
        if args.json:
            print(json.dumps(ledger))
        else:
            print(render_goodput(ledger, args.job_id))
    elif args.command == "hbm":
        from ddl_tpu.obs.hbm import account_from_fold, render_hbm

        account = account_from_fold(_fold_or_exit(args))
        if args.json:
            print(json.dumps(account))
        else:
            print(render_hbm(account, args.job_id))
    elif args.command == "tail":
        events = load_run(args.log_dir, args.job_id)
        for e in events[-args.n:]:
            print(_render_event(e))
    elif args.command == "diff":
        from ddl_tpu.obs.fold import fold_job

        # fold_b / job_b_id track the RUN UNDER TEST (job_a against a
        # baseline, job_b in a two-job diff) — the side the SLO burn
        # gate evaluates, which needs the fold, not just the summary
        fold_b = _fold_or_exit(args)
        sb = summarize_from_fold(fold_b)
        name_b, job_b_id = args.job_a, args.job_a
        if args.baseline:
            stored = json.loads(Path(args.baseline).read_text())
            sa = stored["summary"]
            name_a = f"baseline:{stored.get('job_id', '?')}"
        elif args.job_b:
            # two-job diff keeps its original orientation (a vs b)
            fold_b = fold_job(
                args.log_dir, args.job_b, cache=not args.no_cache,
            )
            sa, sb = sb, summarize_from_fold(fold_b)
            name_a, name_b = name_b, args.job_b
            job_b_id = args.job_b
        else:
            raise SystemExit("obs diff needs a second job id or --baseline")
        print(diff_runs(sa, sb, name_a, name_b))
        if args.fail_slowdown is not None:
            frac = args.fail_slowdown
            ra, rb = _rate(sa), _rate(sb)
            pa, pb = _decode_percentiles(sa), _decode_percentiles(sb)
            da, db = sa.get("decode") or {}, sb.get("decode") or {}
            la, lb = _restart_latency(sa), _restart_latency(sb)

            def _pct(p, metric, q):
                return (p or {}).get(metric, {}).get(q)

            lat_gate = (
                _pct(pa, "latency_s", "p95") is not None
                and _pct(pb, "latency_s", "p95") is not None
            )
            ttft_gate = (
                _pct(pa, "ttft_s", "p99") is not None
                and _pct(pb, "ttft_s", "p99") is not None
            )
            agg_gate = (
                da.get("agg_tok_per_s_per_chip") is not None
                and db.get("agg_tok_per_s_per_chip") is not None
            )
            restart_gate = la is not None and lb is not None
            if not (ra and rb) and not (
                lat_gate or ttft_gate or agg_gate or restart_gate
            ):
                # a run that emitted neither period events nor decode
                # percentiles must not pass the gate by default — that
                # is the shape of a crashed smoke
                raise SystemExit(
                    f"FAIL: cannot compute steps/s "
                    f"({name_a}: {ra}, {name_b}: {rb}) and no decode "
                    "percentiles on both sides — the regression gate "
                    "needs at least one comparable signal"
                )
            if ra and rb and rb < (1.0 - frac) * ra:
                raise SystemExit(
                    f"FAIL: {name_b} at {rb:.2f} steps/s is more than "
                    f"{frac:.0%} below {name_a} ({ra:.2f} steps/s)"
                )
            if lat_gate:
                a = _pct(pa, "latency_s", "p95")
                b = _pct(pb, "latency_s", "p95")
                if b > (1.0 + frac) * a:
                    raise SystemExit(
                        f"FAIL: {name_b} decode p95 latency {b:.4g}s is "
                        f"more than {frac:.0%} above {name_a} "
                        f"({a:.4g}s)"
                    )
            if ttft_gate:
                ta = _pct(pa, "ttft_s", "p99")
                tb = _pct(pb, "ttft_s", "p99")
                if tb > (1.0 + frac) * ta:
                    raise SystemExit(
                        f"FAIL: {name_b} p99 TTFT {tb:.4g}s is more "
                        f"than {frac:.0%} above {name_a} ({ta:.4g}s)"
                    )
            if agg_gate:
                ga = da["agg_tok_per_s_per_chip"]
                gb = db["agg_tok_per_s_per_chip"]
                if gb < (1.0 - frac) * ga:
                    raise SystemExit(
                        f"FAIL: {name_b} serving aggregate "
                        f"{gb:.4g} tok/s/chip is more than {frac:.0%} "
                        f"below {name_a} ({ga:.4g} tok/s/chip)"
                    )
            if restart_gate and la > 0 and lb > (1.0 + frac) * la:
                raise SystemExit(
                    f"FAIL: {name_b} restart latency {lb:.1f}s is more "
                    f"than {frac:.0%} above {name_a} ({la:.1f}s)"
                )
            print(
                f"OK: within the {frac:.0%} regression gate ("
                + " and ".join(
                    g for g, on in (
                        ("steps/s", ra and rb),
                        ("decode p95 latency", lat_gate),
                        ("p99 TTFT", ttft_gate),
                        ("agg tok/s/chip", agg_gate),
                        ("restart latency", restart_gate),
                    ) if on
                )
                + ")"
            )
        if args.fail_goodput_drop is not None:
            frac = args.fail_goodput_drop
            ga, gb = _goodput_ratio(sa), _goodput_ratio(sb)
            if ga is None or gb is None:
                # the flag was explicit — a side without an account must
                # not pass silently (that is the shape of a pre-ledger
                # baseline, or a run that emitted nothing)
                raise SystemExit(
                    f"FAIL: --fail-goodput-drop needs a goodput account "
                    f"on both sides ({name_a}: "
                    f"{'%.3f' % ga if ga is not None else 'none'}, "
                    f"{name_b}: "
                    f"{'%.3f' % gb if gb is not None else 'none'}) — "
                    "regenerate the baseline with a post-ledger "
                    "`obs baseline`"
                )
            if gb < (1.0 - frac) * ga:
                sb_dom = (sb.get("goodput") or {}).get("job", {}).get(
                    "dominant_badput"
                )
                dom_note = (
                    f" (dominant badput: {sb_dom[0]} {sb_dom[1]:.1f}s)"
                    if sb_dom else ""
                )
                raise SystemExit(
                    f"FAIL: {name_b} goodput {gb:.1%} is more than "
                    f"{frac:.0%} below {name_a} ({ga:.1%}){dom_note}"
                )
            print(
                f"OK: goodput within the {frac:.0%} gate "
                f"({ga:.1%} -> {gb:.1%})"
            )
        if args.fail_hbm_growth is not None:
            from ddl_tpu.obs.hbm import fmt_bytes

            frac = args.fail_hbm_growth
            ha = (sa.get("hbm") or {}).get("peak_bytes")
            hb_b = (sb.get("hbm") or {}).get("peak_bytes")
            if ha is None or hb_b is None:
                # the flag was explicit — a side without an hbm account
                # must not pass silently (a pre-ledger baseline, or a
                # run that never emitted hbm_sample)
                raise SystemExit(
                    f"FAIL: --fail-hbm-growth needs an hbm account on "
                    f"both sides ({name_a}: "
                    f"{fmt_bytes(ha) if ha is not None else 'none'}, "
                    f"{name_b}: "
                    f"{fmt_bytes(hb_b) if hb_b is not None else 'none'})"
                    " — regenerate the baseline with a post-ledger "
                    "`obs baseline`"
                )
            # (1+frac)*0 == 0, so any growth over an empty baseline
            # watermark trips the gate too — no special case needed
            if hb_b > (1.0 + frac) * ha:
                top = (sb.get("hbm") or {}).get("top") or []
                top_note = (
                    f" (top consumer: {top[0][0]} {fmt_bytes(top[0][1])})"
                    if top else ""
                )
                raise SystemExit(
                    f"FAIL: {name_b} peak HBM {fmt_bytes(hb_b)} is more "
                    f"than {frac:.0%} above {name_a} "
                    f"({fmt_bytes(ha)}){top_note}"
                )
            print(
                f"OK: peak HBM within the {frac:.0%} growth gate "
                f"({fmt_bytes(ha)} -> {fmt_bytes(hb_b)})"
            )
        if args.fail_slo_burn is not None:
            from ddl_tpu.obs.slo import evaluate_slo, load_slo

            cfg = load_slo(args.log_dir, job_b_id, path=args.slo)
            rep = evaluate_slo(fold_b, cfg)
            worst = rep.get("worst_burn")
            if not rep.get("tenants") or worst is None:
                # the flag was explicit — a run without per-tenant
                # serving data (pre-tenant stream, no serve traffic, or
                # no evaluable budget) must not pass silently
                raise SystemExit(
                    f"FAIL: --fail-slo-burn needs per-tenant serving "
                    f"data with at least one evaluable budget on "
                    f"{name_b} — pre-tenant streams and serve-free runs "
                    "do not carry the signal"
                )
            if worst > args.fail_slo_burn:
                culprit = ""
                for t in sorted(rep["tenants"]):
                    for key, obj in rep["tenants"][t]["objectives"].items():
                        if obj.get("burn") == worst:
                            culprit = f" ({t}/{key})"
                            break
                    if culprit:
                        break
                raise SystemExit(
                    f"FAIL: {name_b} worst SLO burn "
                    f"{worst:.2f}x{culprit} exceeds the "
                    f"{args.fail_slo_burn:.2f}x gate "
                    f"[alert: {rep['alert']}]"
                )
            print(
                f"OK: worst SLO burn {worst:.2f}x within the "
                f"{args.fail_slo_burn:.2f}x gate "
                f"({len(rep['tenants'])} tenant(s))"
            )
    elif args.command == "slo":
        from ddl_tpu.obs.slo import evaluate_slo, load_slo, render_slo

        fold = _fold_or_exit(args)
        cfg = load_slo(args.log_dir, args.job_id, path=args.slo)
        rep = evaluate_slo(fold, cfg)
        if args.json:
            print(json.dumps(rep))
        else:
            print(render_slo(rep, args.job_id))
    elif args.command == "baseline":
        fold = _fold_or_exit(args)
        payload = {
            "job_id": args.job_id, "summary": summarize_from_fold(fold),
        }
        Path(args.out).write_text(json.dumps(payload, indent=1))
        print(f"wrote baseline for {args.job_id!r} to {args.out}")
    elif args.command == "pod":
        from ddl_tpu.obs.pod import pod_summary_from_fold, render_pod_summary

        fold = _fold_or_exit(args)
        summary = pod_summary_from_fold(fold)
        if args.json:
            print(json.dumps(summary, default=str))
        else:
            print(
                render_pod_summary(summary, args.job_id, tail=args.timeline)
            )
    elif args.command == "watch":
        from ddl_tpu.obs.watch import watch

        watch(
            args.log_dir, args.job_id,
            interval=args.interval, once=args.once,
            cache=not args.no_cache,
        )
    elif args.command == "export":
        from ddl_tpu.obs.export import export_command

        export_command(
            args.log_dir, args.job_id,
            prom=args.prom, http_port=args.http, once=args.once,
            interval=args.interval, cache=not args.no_cache,
        )
    elif args.command == "trace":
        if args.http is not None:
            from ddl_tpu.obs.trace import serve_trace_http

            serve_trace_http(
                args.log_dir, args.job_id, args.http,
                cache=not args.no_cache,
            )
            return
        from ddl_tpu.obs.trace import trace_job, write_trace

        trace = trace_job(
            args.log_dir, args.job_id,
            request=args.request, slowest=args.slowest_request,
            incident=args.incident, step=args.step,
            cache=not args.no_cache,
        )
        print(write_trace(trace, args.out))
    elif args.command == "fleet":
        from ddl_tpu.obs.fleet import fleet_command

        fleet_command(
            args.log_root or args.log_dir,
            as_json=args.json, prom=args.prom,
            cache=not args.no_cache,
        )


if __name__ == "__main__":
    main()
