"""Streaming obs engine (PR 8): the incremental fold engine
(``obs/fold.py``), mergeable t-digest serving percentiles, cross-host
clock-skew estimation, ``obs watch``/``obs export``, and the
``restart_latency`` event + gate.

The load-bearing property: ``fold_job`` with its sidecar must render
``obs summarize`` and ``obs pod`` BYTE-IDENTICALLY to a cold full parse
(``cache=False``) under arbitrary append/torn-line/truncate/recreate
histories, while reading only the appended bytes.
"""

import json
import os

import numpy as np
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


def _rich_events(host, *, offset=0.0, periods=4, step_s=0.10):
    """One host's event list exercising every fold reducer: periods
    (two restart epochs), spans, barriers with completion stamps,
    warm+cold decode, serve counters, anomalies/stalls/captures, and a
    restart_latency.  ``offset`` shifts the host's clock (skew)."""
    evs = [_ev(host, "run_start", 1.0 + offset, family="lm")]
    for p in range(periods):
        repoch = 0 if p < periods - 1 else 1
        sps = 10.0 / (1 + 0.05 * host)
        evs.append(_ev(
            host, "period", 10.0 + p + offset, step=p, period=p,
            steps=10, elapsed=1.0 + 0.1 * host, steps_per_sec=sps,
            phases={"step": step_s * 10, "data_wait": 0.2, "fence": 0.01},
            compiles=1 if p == 0 else 0, hbm_peak_bytes=1e9 + host,
            loss=2.0 - 0.1 * p, **({"repoch": repoch} if repoch else {}),
        ))
    evs.append(_ev(
        host, "span", 20.0 + offset, step=40, name="dispatch", dur=0.4,
        depth=0,
    ))
    evs.append(_ev(host, "heartbeat", 21.0 + offset, step=41))
    for b, bts in (("start", 30.0), ("e1-join", 40.0)):
        evs.append(_ev(
            host, "coord_barrier", bts + offset + 0.002 * host, name=b,
            wait=0.3 * host, completed_ts=bts + offset,
        ))
    evs.append(_ev(
        host, "decode", 50.0 + offset, prompt_len=8, new_tokens=16,
        batch=1, dur=0.5, queue_delay=0.0, ttft=0.1 + 0.01 * host,
        tok_per_s=32.0, warm=False, chips=2,
    ))
    # two tenant-tagged warm decodes + one untagged (the untagged one
    # folds into the "default" tenant): the split/truncate/recreate
    # equivalence tests below exercise the v9 per-tenant layer through
    # every sidecar history for free
    tags = [
        {"tenant": "acme", "priority_class": "interactive"},
        {"tenant": "bulk", "priority_class": "batch"},
        {},
    ]
    for i in range(3):
        evs.append(_ev(
            host, "decode", 51.0 + i + offset, prompt_len=8,
            new_tokens=16, batch=1, dur=0.4 + 0.1 * i,
            queue_delay=0.01 * i, ttft=0.1, tok_per_s=30.0 + i,
            warm=True, chips=2, **tags[i],
        ))
    evs.append(_ev(
        host, "serve_admit", 55.0 + offset, request_id=1,
        tenant="acme", priority_class="interactive",
    ))
    evs.append(_ev(
        host, "serve_retire", 55.2 + offset, request_id=1,
        tenant="acme", priority_class="interactive",
    ))
    evs.append(_ev(
        host, "serve_shed", 55.5 + offset, request_id=2,
        reason="queue_full", tenant="bulk", priority_class="batch",
    ))
    evs.append(_ev(
        host, "kv_pool_stats", 56.0 + offset, num_blocks=64,
        block_size=8, free=60, used=4, high_water=8, fragmentation=0.0,
        queue_depth=0, active_lanes=1,
    ))
    # HBM ledger kinds (v10): a static plan, two samples (the second is
    # the peak — the paired max cell must carry ITS categories), and on
    # host 1 an OOM forensic dump; the sidecar-history equivalence
    # tests below exercise the hbm reducer through every fold path
    evs.append(_ev(
        host, "hbm_plan", 56.2 + offset, label="train_step",
        analysis="compiled", argument_bytes=1000, output_bytes=1000,
        temp_bytes=200, alias_bytes=900, code_bytes=50,
    ))
    evs.append(_ev(
        host, "hbm_sample", 56.4 + offset, params_bytes=600,
        opt_bytes=1200, watermark=2000, peak=2000, limit=4096,
        synthetic=True,
    ))
    evs.append(_ev(
        host, "hbm_sample", 56.6 + offset, params_bytes=600,
        opt_bytes=1200, kv_cached_bytes=64, kv_private_bytes=32,
        kv_free_bytes=128, watermark=2200 + host, peak=2300 + host,
        limit=4096, synthetic=True,
    ))
    if host == 1:
        evs.append(_ev(
            host, "hbm_oom_dump", 56.8 + offset, step=9,
            error="RESOURCE_EXHAUSTED: out of memory", watermark=4000,
            limit=4096,
            buffers=[{"shape": [64, 64], "dtype": "float32",
                      "count": 2, "bytes": 32768}],
        ))
    if host == 0:
        evs.append(_ev(
            host, "anomaly", 60.0 + offset, step=2, type="loss_spike",
            value=9.9, baseline=1.0,
        ))
        evs.append(_ev(
            host, "profile_capture", 61.0 + offset, step=2, ok=True,
            trigger="loss_spike", trace_dir="/tmp/x",
            digest={"ops": {"dot": 1.0}, "top_op": "dot.3"},
        ))
    if host == 1:
        evs.append(_ev(
            host, "stall", 62.0 + offset, step=33, age=5.0,
            deadline=4.0, stacks={"t1": "tb", "t2": "tb"},
        ))
        evs.append(_ev(
            host, "supervisor_relaunch", 63.0 + offset, reason="preempt",
            rc=75, delay=0.0,
        ))
    evs.append(_ev(
        host, "restart_latency", 70.0 + offset, step=5,
        latency=3.0 + host, decision_ts=67.0, repoch=1,
    ))
    evs.append(_ev(host, "run_end", 80.0 + offset, phases={}, anomalies=0))
    return evs


def _append(log_dir, job, host, lines, torn=None):
    d = log_dir / "by_job_id" / job
    d.mkdir(parents=True, exist_ok=True)
    with open(d / f"events-h{host:03d}.jsonl", "a") as f:
        for ln in lines:
            f.write(ln + "\n")
        if torn is not None:
            f.write(torn)
    return d / f"events-h{host:03d}.jsonl"


def _render_both(log_dir, job, cache):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.pod import pod_summary_from_fold, render_pod_summary
    from ddl_tpu.obs.report import render_summary, summarize_from_fold

    fold = fold_job(log_dir, job, cache=cache)
    return (
        render_summary(summarize_from_fold(fold), job),
        render_pod_summary(pod_summary_from_fold(fold), job),
        fold,
    )


# ---------------------------------------------------------------------------
# incremental-fold equivalence
# ---------------------------------------------------------------------------


def test_fold_equivalence_under_arbitrary_splits(tmp_path):
    """Resumed folds across arbitrary append splits (torn line included)
    render summarize AND pod byte-identically to a cold full parse at
    every intermediate state."""
    from ddl_tpu.obs.fold import SIDECAR_NAME

    job = "eq"
    lines = {
        h: [json.dumps(e) for e in _rich_events(h, offset=0.001 * h)]
        for h in range(3)
    }
    # three slices with uneven per-host boundaries; slice 1 ends in a
    # torn line that slice 2's first write completes
    torn_full = lines[1][7]
    cut = len(torn_full) // 2
    slices = [
        {0: (0, 5, None), 1: (0, 7, torn_full[:cut]), 2: (0, 3, None)},
        {0: (5, 11, None), 2: (3, 9, None)},
        {h: (None, None, None) for h in range(3)},
    ]
    done = {0: 0, 1: 7, 2: 0}
    for i, sl in enumerate(slices):
        for h, (a, b, torn) in sl.items():
            if a is None:
                a, b = done[h], len(lines[h])
            if i == 1 and h == 1:
                pass
            _append(tmp_path, job, h, lines[h][a:b], torn=torn)
            done[h] = b
        if i == 1:
            # complete host 1's torn line, then its remaining events
            _append(tmp_path, job, 1, [], torn=torn_full[cut:] + "\n")
            _append(tmp_path, job, 1, lines[1][8:])
            done[1] = len(lines[1])
        warm_s, warm_p, _ = _render_both(tmp_path, job, cache=True)
        cold_s, cold_p, _ = _render_both(tmp_path, job, cache=False)
        assert warm_s == cold_s, f"summarize diverged at slice {i}"
        assert warm_p == cold_p, f"pod view diverged at slice {i}"
    assert (tmp_path / "by_job_id" / job / SIDECAR_NAME).exists()
    # the final view saw everything
    assert "straggler" in warm_p or "skew" in warm_p
    assert "restart latency: 3 restart(s)" in warm_s


def test_fold_pipe_schedule_cell_and_byte_identity(tmp_path):
    """The pipe_schedule reducer (sidecar v7): last-wins cell, rendered
    as summarize's pipeline line, with warm==cold byte identity across
    a resume that appends a NEWER schedule event (a resumed run can
    change layout)."""
    import json as _json

    job = "sched"
    ev1 = _ev(
        0, "pipe_schedule", 5.0, schedule="1f1b", pipe=2, microbatches=4,
        virtual=1, makespan=14.0, idle_units=4.0, bubble_fraction=0.142857,
        per_stage=[{"F": 4.0, "B": 4.0, "W": 4.0, "idle": 2.0}] * 2,
    )
    _append(tmp_path, job, 0, [_json.dumps(e) for e in (ev1,)])
    _append(tmp_path, job, 0, [_json.dumps(e) for e in _rich_events(0)[:3]])
    warm, _, fold = _render_both(tmp_path, job, cache=True)
    cold, _, _ = _render_both(tmp_path, job, cache=False)
    assert warm == cold
    assert "pipeline: 1f1b pipe=2 microbatches=4" in warm
    assert "modeled bubble 14.3%" in warm
    assert fold.pipe_schedule()["schedule"] == "1f1b"

    # resume with a newer zb event: the cell flips last-wins, warm
    # (resumed sidecar) still byte-identical to cold
    ev2 = dict(ev1, ts=50.0, mono=50.0, schedule="zb", idle_units=2.0,
               bubble_fraction=0.076923, makespan=13.0)
    _append(tmp_path, job, 0, [_json.dumps(ev2)])
    warm2, _, fold2 = _render_both(tmp_path, job, cache=True)
    cold2, _, _ = _render_both(tmp_path, job, cache=False)
    assert warm2 == cold2
    assert "pipeline: zb" in warm2
    assert fold2.pipe_schedule()["schedule"] == "zb"

    # an event without modeled fields (unmodeled combo) still renders
    # the identity half of the line
    job2 = "sched2"
    _append(tmp_path, job2, 0, [_json.dumps(_ev(
        0, "pipe_schedule", 6.0, schedule="1f1b", pipe=2, microbatches=4,
        virtual=2, makespan=None, idle_units=None, bubble_fraction=None,
        per_stage=None,
    ))])
    warm3, _, _ = _render_both(tmp_path, job2, cache=True)
    assert "pipeline: 1f1b pipe=2 microbatches=4 virtual=2" in warm3
    assert "modeled bubble" not in warm3


def test_fold_reads_only_appended_bytes(tmp_path):
    """The O(appended-bytes) acceptance: a resumed fold's read volume is
    bounded by the appended tail (plus the 64-byte head fingerprints),
    not the stream size."""
    job = "bytes"
    lines = {h: [json.dumps(e) for e in _rich_events(h)] for h in range(3)}
    for h in range(3):
        _append(tmp_path, job, h, lines[h][:-2])
    _, _, fold1 = _render_both(tmp_path, job, cache=True)
    total = sum(
        (tmp_path / "by_job_id" / job / f"events-h{h:03d}.jsonl")
        .stat().st_size for h in range(3)
    )
    assert fold1.bytes_read == total  # first fold reads everything

    appended = 0
    for h in range(3):
        tail = lines[h][-2:]
        appended += sum(len(ln) + 1 for ln in tail)
        _append(tmp_path, job, h, tail)
    _, _, fold2 = _render_both(tmp_path, job, cache=True)
    # appended tails + <=64B fingerprint per stream, nothing more
    assert fold2.bytes_read <= appended + 3 * 64
    assert fold2.bytes_read >= appended

    _, _, fold3 = _render_both(tmp_path, job, cache=True)
    assert fold3.bytes_read <= 3 * 64  # nothing appended: heads only


def test_fold_truncation_and_recreation_rebuild(tmp_path):
    """A stream that shrank below its cursor, or was deleted and
    re-created under the same name (even LARGER than the old cursor),
    or disappeared outright: clean rebuild, never double/half counts."""
    job = "trunc"
    lines = [json.dumps(e) for e in _rich_events(0)]
    path = _append(tmp_path, job, 0, lines)
    warm, _, _ = _render_both(tmp_path, job, cache=True)

    # truncate below the cursor
    path.write_text("\n".join(lines[:4]) + "\n")
    warm_s, warm_p, _ = _render_both(tmp_path, job, cache=True)
    cold_s, cold_p, _ = _render_both(tmp_path, job, cache=False)
    assert warm_s == cold_s and warm_p == cold_p

    # recreate under the same name with MORE bytes but different head
    path.unlink()
    other = [json.dumps(e) for e in _rich_events(0, offset=123.0)]
    _append(tmp_path, job, 0, other + other)
    warm_s, _, _ = _render_both(tmp_path, job, cache=True)
    cold_s, _, _ = _render_both(tmp_path, job, cache=False)
    assert warm_s == cold_s

    # a second tracked stream disappearing invalidates too
    extra = _append(tmp_path, job, 1, [json.dumps(e) for e in _rich_events(1)])
    _render_both(tmp_path, job, cache=True)
    extra.unlink()
    warm_s, _, _ = _render_both(tmp_path, job, cache=True)
    cold_s, _, _ = _render_both(tmp_path, job, cache=False)
    assert warm_s == cold_s


def test_fold_corrupt_sidecar_rebuilds(tmp_path):
    """A JSON-valid sidecar with the wrong inner shape is discarded and
    rebuilt in place, not a crash on every summarize."""
    from ddl_tpu.obs.fold import SIDECAR_NAME, VERSION

    job = "corrupt"
    _append(tmp_path, job, 0, [json.dumps(e) for e in _rich_events(0)])
    _render_both(tmp_path, job, cache=True)
    sidecar = tmp_path / "by_job_id" / job / SIDECAR_NAME
    sidecar.write_text(json.dumps({
        "version": VERSION, "capacity": 4096,
        "files": {"events-h000.jsonl": 10},
        "streams": {"events-h000.jsonl": {"bogus": True}},
        "heads": {},
    }))
    warm_s, _, _ = _render_both(tmp_path, job, cache=True)
    cold_s, _, _ = _render_both(tmp_path, job, cache=False)
    assert warm_s == cold_s
    # and the rebuild repaired the sidecar
    warm2, _, fold = _render_both(tmp_path, job, cache=True)
    assert warm2 == cold_s and fold.bytes_read <= 64


def test_summarize_cli_is_incremental_and_identical(tmp_path, capsys):
    """The CLI path end to end: `obs summarize` warm == `--no-cache`
    cold, and the warm path reads only appended bytes (counted through
    the fold the CLI builds)."""
    from ddl_tpu import cli

    job = "cli"
    for h in range(2):
        _append(
            tmp_path, job, h,
            [json.dumps(e) for e in _rich_events(h)],
        )
    cli.main(["obs", "summarize", job, "--log-dir", str(tmp_path)])
    warm = capsys.readouterr().out
    cli.main([
        "obs", "summarize", job, "--log-dir", str(tmp_path), "--no-cache",
    ])
    cold = capsys.readouterr().out
    assert warm == cold
    cli.main(["obs", "pod", job, "--log-dir", str(tmp_path)])
    pod_warm = capsys.readouterr().out
    cli.main(["obs", "pod", job, "--log-dir", str(tmp_path), "--no-cache"])
    pod_cold = capsys.readouterr().out
    assert pod_warm == pod_cold
    assert "clk_off_s" in pod_warm


# ---------------------------------------------------------------------------
# clock-skew estimation
# ---------------------------------------------------------------------------


def test_clock_skew_estimator_recovers_injected_offsets():
    """Synthetic barrier completions with known per-host offsets + small
    observation noise: the least-squares fit recovers the (centered)
    offsets to well under the noise floor."""
    from ddl_tpu.obs.fold import estimate_clock_offsets

    rng = np.random.default_rng(0)
    true = {0: -1.25, 1: 0.0, 2: 2.5}
    center = sum(true.values()) / len(true)
    arrivals = {h: {} for h in true}
    for i in range(12):
        t = 100.0 * i
        for h, off in true.items():
            arrivals[h][f"0:b{i}"] = t + off + float(rng.normal(0, 0.02))
    fit = estimate_clock_offsets(arrivals)
    for h, off in true.items():
        assert fit[h] == pytest.approx(off - center, abs=0.05)

    # degenerate inputs: one host, or no shared key -> None
    assert estimate_clock_offsets({0: {"0:b": 1.0}}) is None
    assert estimate_clock_offsets(
        {0: {"0:a": 1.0}, 1: {"0:b": 2.0}}
    ) is None


def test_skew_corrects_pod_timeline_and_json(tmp_path, capsys):
    """Hosts with skewed clocks: the fitted offsets land in `obs pod
    --json` and the unified timeline re-orders by corrected time."""
    from ddl_tpu import cli
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.pod import pod_summary_from_fold

    job = "skewed"
    offsets = {0: 0.0, 1: 5.0, 2: -5.0}  # seconds of clock skew
    for h, off in offsets.items():
        _append(
            tmp_path, job, h,
            [json.dumps(e) for e in _rich_events(h, offset=off)],
        )
    s = pod_summary_from_fold(fold_job(tmp_path, job, cache=False))
    fit = s["clock_offsets"]
    center = sum(offsets.values()) / 3
    for h, off in offsets.items():
        assert fit[h] == pytest.approx(off - center, abs=0.05)
    # corrected timeline: each host's run_start happened at the same
    # true instant; adjusted stamps agree even though raw ts differ by
    # up to 10s
    starts = [
        e for e in s["timeline"] if e["kind"] == "run_start"
    ]
    assert len(starts) == 3
    raw_spread = max(e["ts"] for e in starts) - min(e["ts"] for e in starts)
    adj_spread = (
        max(e["ts_adj"] for e in starts)
        - min(e["ts_adj"] for e in starts)
    )
    assert raw_spread > 9.0 and adj_spread < 0.1

    cli.main(["obs", "pod", job, "--log-dir", str(tmp_path), "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["clock_offsets"][str(min(offsets))] == pytest.approx(
        fit[0], abs=1e-9,
    ) or parsed["clock_offsets"]["0"] == pytest.approx(fit[0], abs=1e-9)


# ---------------------------------------------------------------------------
# watch / export surfaces
# ---------------------------------------------------------------------------


def test_watch_once_renders_populated_frame(tmp_path, capsys):
    from ddl_tpu import cli

    job = "watchme"
    for h in range(3):
        _append(
            tmp_path, job, h,
            [json.dumps(e) for e in _rich_events(h)],
        )
    cli.main([
        "obs", "watch", job, "--log-dir", str(tmp_path), "--once",
    ])
    out = capsys.readouterr().out
    assert f"obs watch — {job}" in out
    assert "hosts (latest period)" in out
    assert "phase breakdown" in out
    assert "skew (means over shared periods" in out
    assert "clk_off_s" in out
    assert "requests: 12 (3 cold)" in out
    assert "restart latency: 3 restart(s)" in out
    assert "anomaly:loss_spike" in out
    assert "\x1b" not in out  # --once output is pipe-clean

    with pytest.raises(SystemExit, match="no events"):
        cli.main([
            "obs", "watch", "nosuch", "--log-dir", str(tmp_path), "--once",
        ])


def test_export_prom_golden(tmp_path, capsys):
    from ddl_tpu import cli

    job = "prom"
    for h in range(2):
        _append(
            tmp_path, job, h,
            [json.dumps(e) for e in _rich_events(h)],
        )
    cli.main(["obs", "export", job, "--log-dir", str(tmp_path), "--once"])
    out = capsys.readouterr().out
    # structural golden checks: headers once per metric, deterministic
    # label order, the core series present with the right values
    assert "# TYPE ddl_obs_steps_total counter" in out
    assert (
        f'ddl_obs_steps_total{{host="0",job_id="{job}",repoch="0"}} 30'
        in out
    )
    assert (
        f'ddl_obs_steps_total{{host="0",job_id="{job}",repoch="1"}} 10'
        in out
    )
    assert f'ddl_obs_decode_requests_total{{job_id="{job}"}} 8' in out
    assert 'quantile="0.95"' in out
    assert "ddl_obs_decode_latency_seconds{" in out
    assert (
        f'ddl_obs_restart_latency_seconds{{host="1",job_id="{job}",'
        f'repoch="1"}} 4' in out
    )
    assert f'ddl_obs_kv_free_blocks{{host="0",job_id="{job}"}} 60' in out
    assert "ddl_obs_clock_offset_seconds{" in out
    # emitting twice is identical (deterministic render, incremental fold)
    cli.main(["obs", "export", job, "--log-dir", str(tmp_path), "--once"])
    assert capsys.readouterr().out == out

    # --prom FILE writes the same scrape atomically
    target = tmp_path / "metrics.prom"
    cli.main([
        "obs", "export", job, "--log-dir", str(tmp_path), "--once",
        "--prom", str(target),
    ])
    capsys.readouterr()
    assert target.read_text() == out

    with pytest.raises(SystemExit, match="no events"):
        cli.main([
            "obs", "export", "nosuch", "--log-dir", str(tmp_path),
            "--once",
        ])


def test_export_http_serves_metrics(tmp_path):
    """--http: a real GET /metrics against the threaded server."""
    import threading
    import urllib.request

    from ddl_tpu.obs.export import prometheus_text
    from ddl_tpu.obs.fold import fold_job

    job = "http"
    _append(tmp_path, job, 0, [json.dumps(e) for e in _rich_events(0)])

    # bind port 0 ourselves to avoid collisions; reuse the handler via
    # export's internal server by calling it on a thread
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    def scrape():
        return prometheus_text(fold_job(tmp_path, job, cache=True), job)

    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = scrape().encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "ddl_obs_steps_total{" in body
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# t-digest
# ---------------------------------------------------------------------------


def test_tdigest_exact_in_singleton_regime_matches_numpy():
    from ddl_tpu.obs.serving import TDigest

    rng = np.random.default_rng(1)
    stream = [float(x) for x in rng.exponential(0.2, size=2000)]
    dig = TDigest(exact_max=4096)
    for x in stream:
        dig.add(x)
    for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert dig.quantile(q) == pytest.approx(
            float(np.quantile(stream, q)), rel=1e-12, abs=1e-12
        )
    assert dig.mean == pytest.approx(float(np.mean(stream)))


def test_tdigest_compressed_tolerance_and_determinism():
    """Past the singleton budget, quantiles stay within a few percent of
    numpy on a smooth stream; memory is bounded; two identical feeds
    summarize identically (no RNG anywhere)."""
    from ddl_tpu.obs.serving import TDigest

    rng = np.random.default_rng(2)
    stream = [float(x) for x in rng.lognormal(0.0, 0.5, size=30000)]

    def feed():
        d = TDigest(compression=256, exact_max=4096)
        for x in stream:
            d.add(x)
        return d

    a, b = feed(), feed()
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(stream, q))
        assert a.quantile(q) == pytest.approx(exact, rel=0.05), q
        assert a.quantile(q) == b.quantile(q)
    assert len(a._means) < 2000  # bounded, not the 30k stream
    assert a.count == 30000
    assert a.min == pytest.approx(min(stream))
    assert a.max == pytest.approx(max(stream))


def test_tdigest_merge_and_state_roundtrip():
    """merge() of per-stream digests approximates the single-stream
    digest; a two-operand merge is symmetric and a fixed merge order is
    fully deterministic (what the fold's sorted-stream-name render
    relies on); state round-trips exactly, including the unmerged
    buffer (resume determinism)."""
    from ddl_tpu.obs.serving import TDigest

    rng = np.random.default_rng(3)
    xs = [float(x) for x in rng.normal(10.0, 2.0, size=9000)]

    parts = [TDigest() for _ in range(3)]
    for i, x in enumerate(xs):
        parts[i % 3].add(x)

    def chain(order):
        d = TDigest()
        for i in order:
            d.merge(parts[i])
        return d

    ab, ab2, ba = chain((0, 1, 2)), chain((0, 1, 2)), chain((2, 1, 0))
    assert ab.count == ba.count == len(xs)
    # single two-operand merge is symmetric (sorted combined points)
    xy = TDigest(); xy.merge(parts[0]); xy.merge(parts[1])
    yx = TDigest(); yx.merge(parts[1]); yx.merge(parts[0])
    assert xy.quantile(0.95) == yx.quantile(0.95)
    for q in (0.5, 0.95, 0.99):
        assert ab.quantile(q) == ab2.quantile(q)  # same order: identical
        exact = float(np.quantile(xs, q))
        assert ab.quantile(q) == pytest.approx(exact, rel=0.05)
        assert ba.quantile(q) == pytest.approx(exact, rel=0.05)

    # round-trip: partially-filled buffer preserved verbatim
    d = TDigest()
    for x in xs[:700]:
        d.add(x)
    rt = TDigest.from_state(json.loads(json.dumps(d.state_dict())))
    assert rt.state_dict() == d.state_dict()
    for x in xs[700:1400]:
        d.add(x)
        rt.add(x)
    assert rt.quantile(0.95) == d.quantile(0.95)


def test_tdigest_migrates_reservoir_state():
    """A reservoir-era (QuantileAccumulator) sidecar state loads
    transparently: distribution, count, total, min/max preserved."""
    from ddl_tpu.obs.serving import QuantileAccumulator, TDigest

    acc = QuantileAccumulator(capacity=64)
    xs = [float(x) for x in np.random.default_rng(4).uniform(0, 1, 50)]
    for x in xs:
        acc.add(x)
    dig = TDigest.from_state(acc.state_dict())
    assert dig.count == 50
    assert dig.mean == pytest.approx(acc.mean)
    for q in (0.5, 0.95, 0.99):
        assert dig.quantile(q) == pytest.approx(acc.quantile(q))

    # ServingStats.from_state with reservoir acc blocks (old sidecar)
    from ddl_tpu.obs.serving import ServingStats

    old = {
        "acc": {
            name: QuantileAccumulator(capacity=16).state_dict()
            for name in ("latency_s", "queue_delay_s", "ttft_s", "tok_per_s")
        },
        "requests": 3, "cold": 1, "tokens": 48, "prompt_tokens": 24,
        "spans": {"decode": [32, 1.0, 2.0]}, "chips": 2,
    }
    stats = ServingStats.from_state(old)
    assert stats.requests == 3 and stats.chips == 2
    assert stats.summary()["agg_tok_per_s"] == pytest.approx(32.0)


def test_serving_spans_are_per_engine_and_per_run():
    """Two decode smokes from different processes (same engine-less
    events, different run ids) minutes apart must not share one span —
    the multi-smoke CI stream regression (ROADMAP carry-over)."""
    from ddl_tpu.obs.serving import ServingStats

    def dec(ts, run, engine=None):
        return {
            "kind": "decode", "ts": ts, "run": run, "new_tokens": 8,
            "batch": 1, "dur": 0.2, "warm": True, "tok_per_s": 40.0,
            **({"engine": engine} if engine else {}),
        }

    events = [
        dec(10.0, "runA"), dec(10.2, "runA"),      # smoke 1: [9.8, 10.2]
        dec(310.0, "runB"), dec(310.2, "runB"),    # smoke 2, 5 min later
        dec(600.0, "runC", engine="serve"),
        dec(600.4, "runC", engine="serve"),
    ]
    s = ServingStats.from_events(events).summary()
    # 48 tokens over 0.4 + 0.4 + 0.6 seconds of ACTIVITY, not ~590s
    assert s["agg_tok_per_s"] == pytest.approx(48 / 1.4)


def test_incident_lists_bounded_with_running_totals(tmp_path):
    """The sidecar must stay bounded on a run with thousands of
    incidents: retained lists cap at MAX_EVENTS_PER_LIST, totals keep
    counting, renders say how many are shown — and warm stays
    byte-identical to cold through the truncation."""
    from ddl_tpu.obs.fold import MAX_EVENTS_PER_LIST, SIDECAR_NAME, fold_job
    from ddl_tpu.obs.report import summarize_from_fold

    job = "flood"
    n = MAX_EVENTS_PER_LIST + 300
    evs = [
        _ev(0, "anomaly", 10.0 + i, step=i, type="loss_spike", value=9.9)
        for i in range(n)
    ]
    evs.append(_ev(0, "period", 5000.0, step=0, period=0, steps=10,
                   elapsed=1.0, steps_per_sec=10.0, phases={"step": 1.0}))
    _append(tmp_path, job, 0, [json.dumps(e) for e in evs[: n // 2]])
    _render_both(tmp_path, job, cache=True)
    _append(tmp_path, job, 0, [json.dumps(e) for e in evs[n // 2:]])
    warm_s, warm_p, fold = _render_both(tmp_path, job, cache=True)
    cold_s, cold_p, _ = _render_both(tmp_path, job, cache=False)
    assert warm_s == cold_s and warm_p == cold_p
    s = summarize_from_fold(fold)
    assert s["counts"]["anomalies"] == n
    assert len(s["anomalies"]) == MAX_EVENTS_PER_LIST
    assert f"anomalies ({n}, last {MAX_EVENTS_PER_LIST} shown)" in warm_s
    # the sidecar holds the capped tail, not the flood
    sidecar = json.loads(
        (tmp_path / "by_job_id" / job / SIDECAR_NAME).read_text()
    )
    stream = sidecar["streams"]["events-h000.jsonl"]
    assert len(stream["anomalies"]) == MAX_EVENTS_PER_LIST
    assert stream["totals"]["anomalies"] == n
    # re-fold of nothing stays O(heads)
    _, _, fold3 = _render_both(tmp_path, job, cache=True)
    assert fold3.bytes_read <= 64


# ---------------------------------------------------------------------------
# restart_latency
# ---------------------------------------------------------------------------


def test_steptrace_emits_restart_latency_once(tmp_path, monkeypatch):
    import ddl_tpu.obs.steptrace as st_mod
    from ddl_tpu.obs import EventWriter, read_events
    from ddl_tpu.obs.steptrace import StepTrace

    import time as _time

    origin = _time.time() - 2.5
    monkeypatch.setenv("DDL_RELAUNCH_TS", repr(origin))
    monkeypatch.setattr(st_mod, "_relaunch_consumed", False)

    w = EventWriter(tmp_path, "rl", host=0)
    trace = StepTrace(w, emit_step_spans=0)
    trace.begin_period()
    for step in range(3):
        with trace.phase("data_wait", step=step):
            pass
        with trace.phase("step", step=step):
            pass
    trace.end_period(0, 0, elapsed=0.1, steps=3)
    w.close()

    events = read_events(tmp_path / "by_job_id" / "rl" / "events-h000.jsonl")
    rls = [e for e in events if e["kind"] == "restart_latency"]
    assert len(rls) == 1  # once, on the FIRST completed step
    assert rls[0]["step"] == 0
    assert rls[0]["latency"] == pytest.approx(2.5, abs=2.0)
    assert rls[0]["decision_ts"] == pytest.approx(origin)

    # a second StepTrace in the same process must NOT re-measure
    w2 = EventWriter(tmp_path, "rl", host=0)
    t2 = StepTrace(w2, emit_step_spans=0)
    with t2.phase("step", step=0):
        pass
    w2.close()
    events = read_events(tmp_path / "by_job_id" / "rl" / "events-h000.jsonl")
    assert len(
        [e for e in events if e["kind"] == "restart_latency"]
    ) == 1


def test_steptrace_failed_first_step_does_not_emit(tmp_path, monkeypatch):
    """A first step that RAISES must not consume the measurement: the
    restart didn't succeed, and a decision->crash latency would pollute
    the gate.  The next completed step owns it instead."""
    import time as _time

    import ddl_tpu.obs.steptrace as st_mod
    from ddl_tpu.obs import EventWriter, read_events
    from ddl_tpu.obs.steptrace import StepTrace

    monkeypatch.setenv("DDL_RELAUNCH_TS", repr(_time.time() - 1.0))
    monkeypatch.setattr(st_mod, "_relaunch_consumed", False)

    w = EventWriter(tmp_path, "rlf", host=0)
    trace = StepTrace(w, emit_step_spans=0)
    with pytest.raises(RuntimeError):
        with trace.phase("step", step=0):
            raise RuntimeError("mid-compile crash")
    events = read_events(
        tmp_path / "by_job_id" / "rlf" / "events-h000.jsonl"
    )
    assert not [e for e in events if e["kind"] == "restart_latency"]
    with trace.phase("step", step=1):
        pass
    w.close()
    events = read_events(
        tmp_path / "by_job_id" / "rlf" / "events-h000.jsonl"
    )
    rls = [e for e in events if e["kind"] == "restart_latency"]
    assert len(rls) == 1 and rls[0]["step"] == 1


def test_restart_latency_summarized_and_gated(tmp_path, capsys):
    """restart_latency flows into summarize and the diff gate: an
    inflated restart latency past --fail-slowdown FAILS; matching ones
    pass with the gate named on the OK line."""
    from ddl_tpu import cli

    def mk(job, latency):
        evs = [
            _ev(0, "period", 10.0 + p, step=p, period=p, steps=10,
                elapsed=1.0, steps_per_sec=10.0,
                phases={"step": 0.5}) for p in range(4)
        ]
        evs.append(_ev(
            0, "restart_latency", 20.0, step=5, latency=latency,
            decision_ts=15.0, repoch=1,
        ))
        _append(tmp_path, job, 0, [json.dumps(e) for e in evs])

    mk("rla", 2.0)
    mk("rlb", 2.1)
    mk("rlc", 9.0)

    cli.main(["obs", "summarize", "rla", "--log-dir", str(tmp_path)])
    assert "restart latency: 1 restart(s), last 2.0s" in (
        capsys.readouterr().out
    )

    cli.main([
        "obs", "diff", "rla", "rlb", "--log-dir", str(tmp_path),
        "--fail-slowdown", "0.5",
    ])
    out = capsys.readouterr().out
    assert "OK:" in out and "restart latency" in out

    with pytest.raises(SystemExit, match="restart latency"):
        cli.main([
            "obs", "diff", "rla", "rlc", "--log-dir", str(tmp_path),
            "--fail-slowdown", "0.5",
        ])


def test_pod_supervisor_stamps_relaunch_ts(tmp_path):
    """supervise_pod_command's spawn env: attempt 0 carries no
    DDL_RELAUNCH_TS (and strips an inherited one); after a restart the
    epoch record's decision stamp rides into the child env."""
    from ddl_tpu.supervisor import supervise_command

    seen = {}

    class FakeProc:
        def __init__(self, rc):
            self.rc = rc

        def poll(self):
            return self.rc

    calls = []

    def fake_call(argv, env=None):
        calls.append(dict(env))
        return 75 if len(calls) == 1 else 0

    import ddl_tpu.supervisor as sup_mod

    orig = sup_mod.subprocess.call
    sup_mod.subprocess.call = fake_call
    try:
        rc = supervise_command(
            ["prog"], max_restarts=2,
            env={"DDL_RELAUNCH_TS": "stale", "DDL_LOG_DIR": str(tmp_path)},
            sleep=lambda s: None, log=lambda m: None,
        )
    finally:
        sup_mod.subprocess.call = orig
    assert rc == 0
    assert "DDL_RELAUNCH_TS" not in calls[0]  # stale value stripped
    assert "DDL_RELAUNCH_TS" in calls[1]  # relaunch carries the decision
    float(calls[1]["DDL_RELAUNCH_TS"])  # parseable
    assert seen == {}
