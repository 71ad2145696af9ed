"""Causal distributed tracing (obs/trace.py), the fleet rollup
(obs/fleet.py), the Prometheus histogram export, and watch push mode.

The load-bearing properties: trace output is VALID Chrome trace-event
JSON (monotonic ts, X/i/M/s/f phases only, every flow's s/f pair
matched by bind id), cross-host ordering is clock-offset corrected,
``--slowest-request`` selection is a pure function of the fold state,
and the fold stays byte-identical warm vs cold with trace kinds in the
stream.
"""

import json

import pytest

# ---------------------------------------------------------------------------
# synthetic streams
# ---------------------------------------------------------------------------


def _ev(host, kind, ts, **kw):
    e = {
        "ts": ts, "mono": ts, "run": f"r{host}", "host": host,
        "step": kw.pop("step", None), "kind": kind,
    }
    e.update(kw)
    return e


def _request_events(host, rid, t, dur, *, dispatches=2, warm=True):
    """The native trace events one served request emits (the same
    shapes serve/engine.py writes), plus its admit/retire/decode."""
    evs = [
        _ev(host, "serve_admit", t + 0.1, request_id=rid, lane=0,
            bucket=8, prompt_len=5, max_new=8, blocks=2,
            queue_delay=0.1, compiled=False),
        _ev(host, "trace_span", t + 0.1, trace=rid,
            span=f"{rid}/queue", parent=f"{rid}/req", name="queue",
            cat="serve", t0=t, t1=t + 0.1, request_id=rid),
        _ev(host, "trace_span", t + 0.2, trace=rid,
            span=f"{rid}/prefill", parent=f"{rid}/req", name="prefill",
            cat="serve", t0=t + 0.1, t1=t + 0.2, request_id=rid,
            lane=0, bucket=8, compiled=False),
    ]
    step = (dur - 0.2) / max(1, dispatches)
    for d in range(dispatches):
        t0 = t + 0.2 + d * step
        evs.append(_ev(
            host, "trace_span", t0 + step, trace=rid,
            span=f"{rid}/d{d}", parent=f"{rid}/req", name="decode",
            cat="serve", t0=t0, t1=t0 + step, request_id=rid, lane=0,
            dispatch=d, steps=4, riders=1,
        ))
    evs += [
        _ev(host, "trace_span", t + dur, trace=rid, span=f"{rid}/req",
            parent=None, name="request", cat="serve", t0=t, t1=t + dur,
            request_id=rid, lane=0, prompt_len=5, new_tokens=8,
            dispatches=dispatches, outcome="ok"),
        _ev(host, "serve_retire", t + dur, request_id=rid, lane=0,
            new_tokens=8, dur=dur, freed_blocks=2),
        _ev(host, "decode", t + dur, request_id=rid, prompt_len=5,
            new_tokens=8, batch=1, dur=dur, queue_delay=0.1, ttft=0.2,
            tok_per_s=8 / dur, warm=warm, chips=1, engine="serve"),
    ]
    return evs


def _write(log_dir, job, host, events, mode="a"):
    d = log_dir / "by_job_id" / job
    d.mkdir(parents=True, exist_ok=True)
    with open(d / f"events-h{host:03d}.jsonl", mode) as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return d


def _serve_job(log_dir, job="serve"):
    evs = [_ev(0, "run_start", 1.0, family="serve")]
    evs += _request_events(0, "c0", 10.0, 0.5)
    evs += _request_events(0, "c1", 11.0, 1.4, dispatches=3)
    evs.append(_ev(
        0, "trace_mark", 12.0, trace="c2", span="c2/shed", name="shed",
        cat="serve", request_id="c2", reason="queue_full",
        policy="reject",
    ))
    evs.append(_ev(0, "run_end", 20.0, phases={}))
    _write(log_dir, job, 0, evs)
    return job


# 3-host pod with skewed clocks: host h's wall clock shows true + OFF[h]
_OFF = {0: 0.0, 1: 5.0, 2: -3.0}


def _pod_job(log_dir, job="pod"):
    for h in range(3):
        def w(true_ts, h=h):
            return true_ts + _OFF[h]

        evs = [_ev(h, "run_start", w(1.0), family="lm")]
        for name, bt in (("start", 5.0), ("warm", 8.0)):
            evs.append(_ev(
                h, "coord_barrier", w(bt + 0.001 * h), name=name,
                wait=0.2, completed_ts=w(bt), arrive_ts=w(bt - 0.2),
            ))
        for p in range(3):
            evs.append(_ev(
                h, "period", w(10.0 + p), step=p, period=p, steps=10,
                elapsed=1.0, steps_per_sec=10.0, phases={"step": 0.8},
                compiles=0,
                rates={"mfu": 0.21, "tokens_per_sec": 100.0},
            ))
        if h == 1:
            evs.append(_ev(
                h, "stall", w(100.0), step=30, age=5.0, deadline=4.0,
                stacks={"t": "tb"},
            ))
        evs.append(_ev(
            h, "pod_restart", w(102.2 + 0.01 * h), epoch=1,
            reason="peer_stale", proposer=1, crashes=0, preemptions=1,
            delay=0.0, decision_ts=w(102.0),
        ))
        evs.append(_ev(
            h, "coord_barrier", w(103.0 + 0.002 * h), name="e1-join",
            wait=0.5, completed_ts=w(103.0),
            arrive_ts=w(102.5 + 0.1 * h),
        ))
        evs.append(_ev(
            h, "restart_latency", w(106.0), step=31, latency=4.0,
            decision_ts=w(102.0), repoch=1,
        ))
        _write(log_dir, job, h, evs)
    return job


# ---------------------------------------------------------------------------
# Chrome trace-format validity (the golden contract)
# ---------------------------------------------------------------------------


def _assert_valid_chrome_trace(trace):
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    assert all(e["ph"] in ("X", "i", "M", "s", "f") for e in evs)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "trace events not ts-monotonic"
    for e in evs:
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 1
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "i":
            assert e["s"] == "t"
    starts = sorted(e["id"] for e in evs if e["ph"] == "s")
    finishes = sorted(e["id"] for e in evs if e["ph"] == "f")
    assert starts == finishes, "unmatched flow bind ids"
    assert len(set(starts)) == len(starts)
    # every flow arrow points forward in time (Perfetto drops or
    # mangles backward s->f pairs)
    pairs = {}
    for e in evs:
        if e["ph"] in ("s", "f"):
            pairs.setdefault(e["id"], {})[e["ph"]] = e["ts"]
    for pid, pair in pairs.items():
        assert pair["s"] <= pair["f"], f"backward flow id {pid}"
    # round-trips through JSON (what --out writes)
    json.loads(json.dumps(trace))


def test_request_trace_golden(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    job = _serve_job(tmp_path)
    trace = trace_job(tmp_path, job, request="c1")
    _assert_valid_chrome_trace(trace)
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    # the acceptance shape: queue, prefill, EVERY ridden dispatch, root
    assert names.count("decode") == 3
    for required in ("request", "queue", "prefill"):
        assert required in names
    marks = [e["name"] for e in trace["traceEvents"] if e["ph"] == "i"]
    assert "admit" in marks and "retire" in marks
    # causally linked: queue -> prefill -> d0 -> d1 -> d2 -> retire
    assert sum(1 for e in trace["traceEvents"] if e["ph"] == "s") == 5
    # the root span covers the whole request
    root = next(
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e["name"] == "request"
    )
    assert root["dur"] == pytest.approx(1.4e6, rel=0.01)


def test_shed_request_trace_is_terminal_mark(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    job = _serve_job(tmp_path)
    trace = trace_job(tmp_path, job, request="c2")
    _assert_valid_chrome_trace(trace)
    marks = [e["name"] for e in trace["traceEvents"] if e["ph"] == "i"]
    assert marks == ["shed"]


def test_step_trace_spans_phases(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    for h in range(2):
        _write(tmp_path, "steps", h, [
            _ev(h, "span", 10.0 + 0.1 * h, step=7, name="step",
                dur=0.08, depth=0),
            _ev(h, "span", 10.2 + 0.1 * h, step=7, name="data_wait",
                dur=0.01, depth=0),
            _ev(h, "span", 11.0, step=8, name="step", dur=0.08,
                depth=0),
        ])
    trace = trace_job(tmp_path, "steps", step=7)
    _assert_valid_chrome_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 4  # both hosts' step+data_wait for step 7 only
    assert {e["name"] for e in xs} == {"step", "data_wait"}


def test_step_trace_renders_schedule_lanes(tmp_path):
    """With a pipe_schedule event on record, `obs trace --step` adds
    the modeled per-stage F/B/W lanes beside the measured phase spans —
    one Perfetto thread per stage, every unit marked modeled, scaled
    into the step's measured window."""
    from ddl_tpu.obs.trace import trace_job

    _write(tmp_path, "zbsteps", 0, [
        _ev(0, "pipe_schedule", 5.0, schedule="zb", pipe=2,
            microbatches=4, virtual=1),
        _ev(0, "span", 10.0, step=3, name="step", dur=0.08, depth=0),
        _ev(0, "span", 10.2, step=3, name="fence", dur=0.01, depth=0),
    ])
    trace = trace_job(tmp_path, "zbsteps", step=3)
    _assert_valid_chrome_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    lanes = [e for e in xs if e.get("args", {}).get("modeled")]
    phases = {e["args"]["phase"] for e in lanes}
    assert phases == {"F", "B", "W"}
    # every stage contributes M units of each phase
    per_stage = {}
    for e in lanes:
        per_stage.setdefault(e["tid"], []).append(e)
    assert set(per_stage) == {0, 1}
    for units in per_stage.values():
        assert len(units) == 3 * 4
    # stage threads are named and the measured spans are still there
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"stage 0", "stage 1"} <= names
    assert {"step", "fence"} <= {e["name"] for e in xs}

    # a malformed/unmodeled pipe_schedule event degrades to no lanes,
    # never a crash
    _write(tmp_path, "badsched", 0, [
        _ev(0, "pipe_schedule", 5.0, schedule="1f1b", pipe=2,
            microbatches=4, virtual=2),
        _ev(0, "span", 10.0, step=1, name="step", dur=0.05, depth=0),
    ])
    t2 = trace_job(tmp_path, "badsched", step=1)
    assert not [e for e in t2["traceEvents"]
                if e["ph"] == "X" and e.get("args", {}).get("modeled")]


def test_selector_errors_are_actionable(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    job = _serve_job(tmp_path)
    with pytest.raises(SystemExit, match="no trace events for request"):
        trace_job(tmp_path, job, request="nope")
    with pytest.raises(SystemExit, match="out of range"):
        trace_job(tmp_path, job, incident=99)
    with pytest.raises(SystemExit, match="exactly one"):
        trace_job(tmp_path, job, request="c1", step=3)
    with pytest.raises(SystemExit, match="exactly one"):
        trace_job(tmp_path, job)


# ---------------------------------------------------------------------------
# slowest-request selection (fold-side)
# ---------------------------------------------------------------------------


def test_slowest_request_selection(tmp_path):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.trace import trace_job

    job = _serve_job(tmp_path)
    fold = fold_job(tmp_path, job)
    cell = fold.trace_totals()["slowest"]
    assert cell is not None and cell[1] == "c1"
    assert cell[0] == pytest.approx(1.4)
    trace = trace_job(tmp_path, job, slowest=True)
    assert trace["otherData"]["trace"] == "request c1"

    # the summary surfaces the same selection
    from ddl_tpu.obs.report import summarize_from_fold

    s = summarize_from_fold(fold)
    assert s["trace"]["requests"] == 2
    assert s["trace"]["slowest"]["request"] == "c1"


def test_slowest_request_empty_job_errors(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    _write(tmp_path, "plain", 0, [_ev(0, "run_start", 1.0)])
    with pytest.raises(SystemExit, match="no request trace spans"):
        trace_job(tmp_path, "plain", slowest=True)


# ---------------------------------------------------------------------------
# warm == cold with trace kinds present
# ---------------------------------------------------------------------------


def test_fold_byte_identity_with_trace_kinds(tmp_path):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.pod import pod_summary_from_fold, render_pod_summary
    from ddl_tpu.obs.report import render_summary, summarize_from_fold

    job = _serve_job(tmp_path)

    def render(cache):
        fold = fold_job(tmp_path, job, cache=cache)
        return (
            render_summary(summarize_from_fold(fold), job)
            + "\n"
            + render_pod_summary(pod_summary_from_fold(fold), job)
        )

    warm1 = render(cache=True)  # builds the sidecar
    # append MORE trace events, resume the fold, compare to cold
    _write(
        tmp_path, job, 0,
        _request_events(0, "c9", 30.0, 2.0, dispatches=1),
    )
    warm2 = render(cache=True)
    cold2 = render(cache=False)
    assert warm2 == cold2
    assert warm1 != warm2  # the appended request is visible
    # the new request is now the slowest, through the resumed fold too
    fold = fold_job(tmp_path, job, cache=True)
    assert fold.trace_totals()["slowest"][1] == "c9"


# ---------------------------------------------------------------------------
# clock-offset-corrected cross-host ordering (3 synthetic hosts)
# ---------------------------------------------------------------------------


def test_incident_trace_cross_host_ordering(tmp_path):
    from ddl_tpu.obs.fold import estimate_clock_offsets, fold_job
    from ddl_tpu.obs.trace import trace_job

    job = _pod_job(tmp_path)
    fold = fold_job(tmp_path, job)
    offsets = estimate_clock_offsets({
        sf.host: sf.barrier_ts for sf in fold.streams.values()
    })
    # the fit recovers the injected skew (up to the common mean shift)
    rel = {h: offsets[h] - offsets[0] for h in offsets}
    assert rel[1] == pytest.approx(_OFF[1] - _OFF[0], abs=0.05)
    assert rel[2] == pytest.approx(_OFF[2] - _OFF[0], abs=0.05)

    trace = trace_job(tmp_path, job, incident=0)
    _assert_valid_chrome_trace(trace)
    evs = trace["traceEvents"]
    stall = next(e for e in evs if e["ph"] == "X" and e["name"] == "stall")
    decisions = [
        e for e in evs
        if e["ph"] == "i" and e["name"].startswith("pod_restart")
    ]
    bars = [
        e for e in evs
        if e["ph"] == "X" and e["name"] == "barrier:e1-join"
    ]
    relaunches = [
        e for e in evs
        if e["ph"] == "X" and e["name"] == "relaunch->first-step"
    ]
    # the pod-wide decision renders ONCE, from the proposer's event
    # (its decision_ts is in the proposer's clock domain, so only the
    # proposer's fitted offset corrects it truly)
    assert len(decisions) == 1 and len(bars) == 3 and len(relaunches) == 3
    # true order after correction: stall start < decision < barrier
    # completion; the raw clocks disagree by up to 8 seconds, so any
    # uncorrected merge would scramble this
    for d in decisions:
        assert stall["ts"] < d["ts"]
        for b in bars:
            assert d["ts"] <= b["ts"] + b["dur"]
    # all hosts observed the join complete at (nearly) one instant
    ends = sorted(b["ts"] + b["dur"] for b in bars)
    assert ends[-1] - ends[0] < 20_000  # < 20ms in us after correction
    # relaunch spans originate at the pod-wide decision instant
    for r in relaunches:
        assert abs(r["ts"] - decisions[0]["ts"]) < 250_000
    # flow arrows: decision -> each barrier, each barrier -> first step
    assert sum(1 for e in evs if e["ph"] == "s") >= 6


def test_incident_clustering_gap(tmp_path):
    from ddl_tpu.obs.trace import collect_incidents

    streams = {0: [
        _ev(0, "anomaly", 100.0, type="loss_spike", value=9.0),
        _ev(0, "profile_capture", 101.0, ok=True, trigger="loss_spike",
            trace_dir="/tmp/x"),
        _ev(0, "anomaly", 500.0, type="loss_spike", value=8.0),
    ]}
    incidents = collect_incidents(streams)
    assert len(incidents) == 2
    assert len(incidents[0]["events"]) == 2
    assert incidents[1]["t0"] == 500.0


def test_slow_restart_stays_one_incident(tmp_path):
    """A relaunch whose first step takes longer than the cluster gap
    (40s recompile) must still land in the restart's incident: the
    restart_latency event clusters on its DECISION instant."""
    from ddl_tpu.obs.trace import trace_job

    _write(tmp_path, "slow", 0, [
        _ev(0, "run_start", 1.0),
        _ev(0, "pod_restart", 100.2, epoch=1, reason="crash",
            proposer=0, crashes=1, preemptions=0, delay=0.0,
            decision_ts=100.0),
        _ev(0, "coord_barrier", 101.0, name="e1-join", wait=0.5,
            completed_ts=101.0, arrive_ts=100.5),
        # first step completes 45s after the decision — past the 30s
        # gap from the emission-ts perspective
        _ev(0, "restart_latency", 145.0, step=31, latency=45.0,
            decision_ts=100.0, repoch=1),
    ])
    trace = trace_job(tmp_path, "slow", incident=0)
    _assert_valid_chrome_trace(trace)
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert "relaunch->first-step" in names
    assert "barrier:e1-join" in names
    with pytest.raises(SystemExit, match="out of range"):
        trace_job(tmp_path, "slow", incident=1)  # no spurious second


def test_anomaly_capture_flow(tmp_path):
    from ddl_tpu.obs.trace import trace_job

    _write(tmp_path, "anom", 0, [
        _ev(0, "run_start", 1.0),
        _ev(0, "anomaly", 100.0, step=5, type="loss_spike", value=9.0,
            baseline=1.0),
        _ev(0, "profile_capture", 101.0, step=6, ok=True,
            trigger="loss_spike", trace_dir="/tmp/x",
            digest={"ops": {"dot": 1.0}}),
    ])
    trace = trace_job(tmp_path, "anom", incident=0)
    _assert_valid_chrome_trace(trace)
    assert sum(1 for e in trace["traceEvents"] if e["ph"] == "s") == 1


def test_repeated_anomaly_capture_binds_latest(tmp_path):
    """Two anomalies of the same type in one incident, each arming its
    own capture: every capture's flow must originate at the LATEST
    preceding anomaly, never point backward to a later one."""
    from ddl_tpu.obs.trace import trace_job

    _write(tmp_path, "anom2", 0, [
        _ev(0, "run_start", 1.0),
        _ev(0, "anomaly", 100.0, step=5, type="loss_spike", value=9.0),
        _ev(0, "profile_capture", 101.0, step=6, ok=True,
            trigger="loss_spike", trace_dir="/tmp/x1"),
        _ev(0, "anomaly", 110.0, step=8, type="loss_spike", value=8.0),
        _ev(0, "profile_capture", 111.0, step=9, ok=True,
            trigger="loss_spike", trace_dir="/tmp/x2"),
    ])
    trace = trace_job(tmp_path, "anom2", incident=0)
    _assert_valid_chrome_trace(trace)
    evs = trace["traceEvents"]
    assert sum(1 for e in evs if e["ph"] == "s") == 2
    # each flow's source (s) precedes its sink (f): no backward arrows
    by_id = {}
    for e in evs:
        if e["ph"] in ("s", "f"):
            by_id.setdefault(e["id"], {})[e["ph"]] = e["ts"]
    for pair in by_id.values():
        assert pair["s"] <= pair["f"]


# ---------------------------------------------------------------------------
# fleet rollup over two jobs
# ---------------------------------------------------------------------------


def test_fleet_rollup_two_jobs(tmp_path):
    from ddl_tpu.obs.fleet import (
        fleet_prometheus_text,
        fleet_summary,
        render_fleet,
    )

    _serve_job(tmp_path, "job-serve")
    _pod_job(tmp_path, "job-pod")
    s = fleet_summary(tmp_path)
    assert set(s) == {"job-serve", "job-pod"}

    pod = s["job-pod"]
    assert pod["hosts"] == 3
    assert pod["steps"] == 30  # representative host, not 3x-inflated
    assert pod["steps_per_sec"] == pytest.approx(10.0)
    assert pod["mfu"] == pytest.approx(0.21)
    # ONE pod-wide restart, though all 3 hosts emitted their own
    # pod_restart copy: distinct epochs dedupe, not per-host sums
    assert pod["restarts"] == 1
    assert pod["stalls"] == 1
    assert pod["incidents"] == pod["restarts"] + pod["anomalies"] + 1

    serve = s["job-serve"]
    assert serve["requests"] == 2
    assert serve["ttft_p99_s"] is not None
    assert serve["slowest_request"] == "c1"

    table = render_fleet(s, str(tmp_path), now=200.0)
    assert "job-serve" in table and "job-pod" in table
    assert "p99_ttft" in table and "mfu" in table

    prom = fleet_prometheus_text(tmp_path)
    assert 'job_id="job-serve"' in prom
    assert 'job_id="job-pod"' in prom
    # one header per family even with two jobs filled in
    assert prom.count("# TYPE ddl_obs_steps_total counter") == 1
    assert 'ddl_obs_mfu{host="0",job_id="job-pod",repoch="0"}' in prom


def test_fleet_cli(tmp_path, capsys):
    from ddl_tpu.obs.report import main

    _serve_job(tmp_path, "j1")
    main(["fleet", str(tmp_path), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert out["j1"]["requests"] == 2
    with pytest.raises(SystemExit, match="no jobs"):
        main(["fleet", str(tmp_path / "empty")])
    # --json --prom keeps stdout pure JSON (status goes to stderr)
    main(["fleet", str(tmp_path), "--json", "--prom",
          str(tmp_path / "f.prom")])
    captured = capsys.readouterr()
    json.loads(captured.out)
    assert "wrote" in captured.err
    assert (tmp_path / "f.prom").exists()


# ---------------------------------------------------------------------------
# Prometheus histogram export (t-digest rank)
# ---------------------------------------------------------------------------


def test_tdigest_rank_exact_regime():
    import numpy as np

    from ddl_tpu.obs.serving import TDigest

    dig = TDigest()
    vals = [0.01, 0.02, 0.02, 0.5, 1.5]
    for v in vals:
        dig.add(v)
    assert dig.rank(0.005) == 0.0
    assert dig.rank(0.02) == 3.0
    assert dig.rank(0.4) == 3.0
    assert dig.rank(2.0) == 5.0
    assert TDigest().rank(1.0) is None
    # compressed regime stays monotone and pins the extremes
    big = TDigest(compression=16, exact_max=32)
    rng = np.random.default_rng(0)
    data = sorted(rng.exponential(0.1, 500))
    for v in data:
        big.add(float(v))
    ranks = [big.rank(x) for x in (0.01, 0.05, 0.1, 0.5, 10.0)]
    assert ranks == sorted(ranks)
    assert ranks[-1] == 500.0
    # consistent with numpy's empirical CDF to a few percent
    emp = sum(1 for v in data if v <= 0.1)
    assert ranks[2] == pytest.approx(emp, rel=0.1)


def test_export_histogram_series(tmp_path):
    from ddl_tpu.obs.export import LATENCY_BUCKETS, prometheus_text
    from ddl_tpu.obs.fold import fold_job

    job = _serve_job(tmp_path)
    text = prometheus_text(fold_job(tmp_path, job), job)
    lines = text.splitlines()
    assert "# TYPE ddl_obs_decode_latency_hist_seconds histogram" in lines
    buckets = [
        float(ln.rsplit(" ", 1)[1]) for ln in lines
        if ln.startswith("ddl_obs_decode_latency_hist_seconds_bucket")
    ]
    assert len(buckets) == len(LATENCY_BUCKETS) + 1  # +Inf
    assert buckets == sorted(buckets)  # cumulative
    count = next(
        float(ln.rsplit(" ", 1)[1]) for ln in lines
        if ln.startswith("ddl_obs_decode_latency_hist_seconds_count")
    )
    assert buckets[-1] == count == 2.0  # both warm requests
    # le labels render in bound order, not lexicographic
    le_lines = [
        ln for ln in lines
        if ln.startswith("ddl_obs_decode_latency_hist_seconds_bucket")
    ]
    les = [ln.split('le="')[1].split('"')[0] for ln in le_lines]
    assert les[-1] == "+Inf"
    assert [float(x) for x in les[:-1]] == sorted(
        float(x) for x in les[:-1]
    )
    # the quantile gauges are still there, unchanged family
    assert "# TYPE ddl_obs_decode_latency_seconds gauge" in lines
    # ttft histogram too
    assert "# TYPE ddl_obs_decode_ttft_hist_seconds histogram" in lines


# ---------------------------------------------------------------------------
# watch push mode
# ---------------------------------------------------------------------------


def test_stream_signature_change_detector(tmp_path):
    from ddl_tpu.obs.report import _job_dir
    from ddl_tpu.obs.watch import stream_signature

    job = _serve_job(tmp_path)
    d = _job_dir(tmp_path, job)
    sig1 = stream_signature(d)
    assert sig1 and sig1 == stream_signature(d)  # stable when idle
    _write(tmp_path, job, 0, [_ev(0, "heartbeat", 50.0, step=1)])
    assert stream_signature(d) != sig1  # append detected
    assert stream_signature(tmp_path / "nope") == ()


def test_watch_push_redraws_on_append_before_interval(tmp_path, capsys):
    """With a huge --interval, the push loop still redraws as soon as a
    stream grows: the second frame must arrive from the appender, not
    the interval timer."""
    import threading
    import time as _time

    from ddl_tpu.obs.watch import watch

    job = _serve_job(tmp_path)

    def append_soon():
        _time.sleep(0.3)
        _write(tmp_path, job, 0, [_ev(0, "heartbeat", 50.0, step=1)])

    t = threading.Thread(target=append_soon)
    t.start()
    start = _time.monotonic()
    watch(
        tmp_path, job, interval=30.0, cache=True, max_frames=2,
        poll_s=0.05,
    )
    wall = _time.monotonic() - start
    t.join()
    assert wall < 10.0, f"push mode did not trigger (took {wall:.1f}s)"
    frames = capsys.readouterr().out
    assert frames.count("== obs watch") == 2


def test_watch_interval_is_max_wait(tmp_path, capsys):
    """No appends at all: the loop still redraws once the interval
    elapses (the age column must keep moving on an idle job)."""
    import time as _time

    from ddl_tpu.obs.watch import watch

    job = _serve_job(tmp_path)
    start = _time.monotonic()
    watch(
        tmp_path, job, interval=0.2, cache=True, max_frames=2,
        poll_s=0.05,
    )
    assert _time.monotonic() - start >= 0.2
    assert capsys.readouterr().out.count("== obs watch") == 2


# ---------------------------------------------------------------------------
# the real engine emits a traceable request path (CPU JAX e2e)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    import flax.linen as nn

    from ddl_tpu.models.transformer import LMConfig, TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec

    cfg = LMConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
        d_ff=64, compute_dtype="float32",
    )
    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    return cfg, params, LMMeshSpec()


@pytest.mark.slow
def test_engine_request_trace_e2e(tmp_path, lm):
    """A real ServeEngine run yields a loadable, causally-complete
    trace for its slowest request — the CPU half of the acceptance
    drive (the CLI half is in the verify skill)."""
    import numpy as np

    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.trace import trace_job
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    obs = EventWriter(tmp_path, "trace-e2e")
    eng = ServeEngine(
        cfg, params, spec, block_size=8, num_blocks=32, max_batch=2,
        max_steps_per_dispatch=4, obs=obs,
    )
    for i, (plen, mn) in enumerate([(5, 6), (9, 10), (3, 2)]):
        eng.submit(
            np.arange(1, plen + 1, dtype=np.int32), mn,
            request_id=f"q{i}",
        )
    eng.run()
    obs.close()

    fold = fold_job(tmp_path, "trace-e2e")
    cell = fold.trace_totals()["slowest"]
    assert cell is not None and cell[1] in ("q0", "q1", "q2")
    trace = trace_job(tmp_path, "trace-e2e", slowest=True)
    _assert_valid_chrome_trace(trace)
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert "request" in names and "prefill" in names
    assert names.count("decode") >= 1
    marks = [e["name"] for e in trace["traceEvents"] if e["ph"] == "i"]
    assert "admit" in marks and "retire" in marks
    # every submitted request is traceable, and dispatch ledgers match:
    # the root span's dispatch count equals its decode spans
    for i in range(3):
        t = trace_job(tmp_path, "trace-e2e", request=f"q{i}")
        xs = [e for e in t["traceEvents"] if e["ph"] == "X"]
        root = next(e for e in xs if e["name"] == "request")
        assert root["args"]["dispatches"] == sum(
            1 for e in xs if e["name"] == "decode"
        )


@pytest.mark.slow
def test_engine_warmup_not_traced(tmp_path, lm):
    import numpy as np

    from ddl_tpu.obs import EventWriter
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.serve.engine import ServeEngine

    cfg, params, spec = lm
    obs = EventWriter(tmp_path, "warm-e2e")
    eng = ServeEngine(
        cfg, params, spec, block_size=8, num_blocks=32, max_batch=2,
        obs=obs,
    )
    eng.warmup(8, 2)
    eng.submit(np.arange(1, 6, dtype=np.int32), 3, request_id="real")
    eng.run()
    obs.close()
    fold = fold_job(tmp_path, "warm-e2e")
    tr = fold.trace_totals()
    # only the real request traced; the warmup must not win slowest
    assert tr["requests"] == 1
    assert tr["slowest"][1] == "real"
