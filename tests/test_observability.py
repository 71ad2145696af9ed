"""Observability: the shared MetricLogger CSV suite (reference row
schema, single.py:260-269) AND the structured event stream
(``ddl_tpu/obs/``) — per-step phase spans, watchdog stall dumps,
anomaly detectors, and the ``ddl_tpu obs`` run-inspection CLI.
"""

import sys
import time

import numpy as np


def _run_main(module, argv):
    old = sys.argv
    sys.argv = [module.__name__] + argv
    try:
        module.main()
    finally:
        sys.argv = old


def test_train_lm_writes_metric_csvs(tmp_path, capsys):
    import examples.train_lm as train_lm

    from ddl_tpu.bench.analysis import (
        epoch_time_per_job,
        phase_breakdown_per_job,
        throughput_per_job,
    )
    from ddl_tpu.utils.csv_logger import read_metric_csv

    log_dir = tmp_path / "logs"
    _run_main(train_lm, [
        "--steps", "12", "--batch", "4", "--seq-len", "16",
        "--d-model", "32", "--layers", "2",
        "--log-dir", str(log_dir), "--job-id", "lm-test",
    ])
    job_dir = log_dir / "by_job_id" / "lm-test"
    for metric in ("loss", "ce", "steps_per_sec", "tokens_per_sec", "epoch_time"):
        rows = read_metric_csv(job_dir / f"{metric}.csv")
        assert rows and all(np.isfinite(r["value"]) for r in rows), metric
    # analysis aggregates the LM job like any other
    assert "lm-test" in epoch_time_per_job(log_dir)
    rates = throughput_per_job(log_dir)["lm-test"]
    assert rates["tokens_per_sec"] > 0

    # ---- the same run's structured event stream (ddl_tpu/obs/) ----
    from ddl_tpu.obs import read_events
    from ddl_tpu.obs.events import events_path

    events = read_events(events_path(log_dir, "lm-test", 0))
    kinds = {e["kind"] for e in events}
    assert {"run_start", "span", "period", "run_end"} <= kinds
    # every event carries the shared envelope
    for e in events:
        assert {"ts", "mono", "run", "host", "step", "kind"} <= set(e)

    # per-step phase spans exist for the in-loop phases
    span_names = {e["name"] for e in events if e["kind"] == "span"}
    assert {"data_wait", "step", "fence", "logging"} <= span_names
    # the fence's children: the first metric's copy says the device has
    # drained, the others' copies follow it; a later period's first phases
    # begin with the device known idle
    spans = [e for e in events if e["kind"] == "span"]
    at = next(i for i, e in enumerate(spans) if e["name"] == "fence.drain")
    assert [e["name"] for e in spans[at:at + 3]] == ["fence.drain", "fence.d2h", "fence"]
    assert all(e["parent"] == "fence" for e in spans[at:at + 2])
    assert "data_wait.idle" in span_names

    periods = [e for e in events if e["kind"] == "period"]
    assert sum(p["steps"] for p in periods) == 12
    for p in periods:
        # in-loop phases can't exceed the measured period wall (eval/
        # checkpoint/logging phases run after it); small slack for timer
        # granularity
        inner = sum(
            p["phases"].get(k, 0.0) for k in ("data_wait", "h2d", "step", "fence")
        )
        assert inner <= p["elapsed"] * 1.05 + 0.05

    # the period events and the CSV rows describe the same measurements
    csv_by_step = {
        r["epoch"]: r["value"] for r in read_metric_csv(job_dir / "window_time.csv")
    }
    for p in periods:
        if p["step"] in csv_by_step:
            assert abs(csv_by_step[p["step"]] - p["elapsed"]) < 1e-6
    sps_by_step = {
        r["epoch"]: r["value"] for r in read_metric_csv(job_dir / "steps_per_sec.csv")
    }
    for p in periods:
        if p["step"] in sps_by_step:
            assert abs(sps_by_step[p["step"]] - p["steps_per_sec"]) < 1e-6

    # bench.analysis reads the event stream alongside the CSVs
    breakdown = phase_breakdown_per_job(log_dir)["lm-test"]
    assert breakdown["step"] > 0 and "data_wait" in breakdown

    # ---- `ddl_tpu obs summarize` renders the run from the events ----
    from ddl_tpu import cli

    capsys.readouterr()
    cli.main(["obs", "summarize", "lm-test", "--log-dir", str(log_dir)])
    out = capsys.readouterr().out
    assert "phase breakdown" in out
    assert "steps: 12" in out
    for name in ("step", "data_wait", "fence"):
        assert name in out
    assert "anomalies (0)" in out

    cli.main(["obs", "tail", "lm-test", "--log-dir", str(log_dir), "-n", "3"])
    out = capsys.readouterr().out
    assert "run_end" in out


def test_event_writer_span_nesting(tmp_path):
    from ddl_tpu.obs import EventWriter, read_events

    w = EventWriter(tmp_path, "job", host=0, run_id="r1")
    with w.span("outer"):
        with w.span("inner", step=4):
            pass
    w.emit("custom", step=3, foo=1.5)
    w.close()
    events = read_events(w.path)
    spans = {e["name"]: e for e in events if e["kind"] == "span"}
    assert spans["inner"]["parent"] == "outer" and spans["inner"]["depth"] == 1
    assert spans["outer"]["parent"] is None and spans["outer"]["depth"] == 0
    assert spans["inner"]["step"] == 4
    assert spans["outer"]["dur"] >= spans["inner"]["dur"] >= 0
    (custom,) = [e for e in events if e["kind"] == "custom"]
    assert custom["step"] == 3 and custom["foo"] == 1.5 and custom["run"] == "r1"


def test_step_span_sampler_one_in_n(tmp_path, monkeypatch):
    """`emit_step_spans` as an integer N emits phase spans for 1-in-N
    steps only; period totals still accumulate every step."""
    from ddl_tpu.obs import EventWriter, StepTrace, read_events

    w = EventWriter(tmp_path, "job", host=0)
    trace = StepTrace(w, emit_step_spans=4)
    trace.begin_period()
    for step in range(10):
        with trace.phase("step", step=step):
            pass
    # period-boundary phases are ONE write per period (and the
    # preemption checkpoint span is incident-review gold): never thinned,
    # even though the loop tags them with the boundary step
    with trace.phase("checkpoint", step=7):
        pass
    trace.end_period(0, 0, elapsed=1.0, steps=10)
    w.close()
    events = read_events(w.path)
    spans = [e for e in events if e["kind"] == "span"]
    assert [e["step"] for e in spans if e["name"] == "step"] == [0, 4, 8]
    assert [e["step"] for e in spans if e["name"] == "checkpoint"] == [7]
    (period,) = [e for e in events if e["kind"] == "period"]
    assert period["steps"] == 10  # totals cover every step regardless

    # bool settings keep their round-6 meaning; env parses integers
    assert StepTrace(w, emit_step_spans=False).emit_step_spans == 0
    assert StepTrace(w, emit_step_spans=True).emit_step_spans == 1
    monkeypatch.setenv("DDL_OBS_STEP_SPANS", "100")
    t = StepTrace.create(tmp_path, "job2", "lm", host=0)
    assert t.emit_step_spans == 100
    t.writer.close()
    monkeypatch.setenv("DDL_OBS_STEP_SPANS", "off")
    t = StepTrace.create(tmp_path, "job3", "lm", host=0)
    assert t.emit_step_spans == 0
    t.writer.close()


def test_event_writer_stamps_pod_restart_epoch(tmp_path, monkeypatch):
    from ddl_tpu.obs import EventWriter, read_events

    monkeypatch.setenv("DDL_RESTART_EPOCH", "3")
    w = EventWriter(tmp_path, "job-re", host=0)
    w.emit("heartbeat")
    w.close()
    (e,) = read_events(w.path)
    assert e["repoch"] == 3
    monkeypatch.delenv("DDL_RESTART_EPOCH")
    w = EventWriter(tmp_path, "job-re2", host=0)
    w.emit("heartbeat")
    w.close()
    (e,) = read_events(w.path)
    assert "repoch" not in e  # no noise outside pod mode


def test_watchdog_stall_dumps_stacks(tmp_path):
    from ddl_tpu.obs import EventWriter, Watchdog, read_events

    w = EventWriter(tmp_path, "job", host=0)
    with Watchdog(w, deadline_s=0.15, interval_s=0.03) as wd:
        wd.beat(7)
        time.sleep(0.6)  # the deliberately stalled "step"
    w.close()
    events = read_events(w.path)
    assert any(e["kind"] == "heartbeat" for e in events)
    stalls = [e for e in events if e["kind"] == "stall"]
    assert stalls, "a stalled step must produce a stack-dump event"
    assert len(stalls) == 1, "one dump per stall, not one per poll"
    st = stalls[0]
    assert st["step"] == 7 and st["age"] > 0.15
    # this (stalled) thread's stack is in the dump, showing the sleep
    assert any("time.sleep" in s or "sleep(" in s for s in st["stacks"].values())


def test_watchdog_quiet_while_beating(tmp_path):
    from ddl_tpu.obs import EventWriter, Watchdog, read_events

    w = EventWriter(tmp_path, "job", host=0)
    with Watchdog(w, deadline_s=0.2, interval_s=0.03) as wd:
        for i in range(10):
            wd.beat(i)
            time.sleep(0.03)
    w.close()
    events = read_events(w.path)
    assert not [e for e in events if e["kind"] == "stall"]
    beats = [e for e in events if e["kind"] == "heartbeat"]
    assert beats and beats[-1]["step"] is not None


def test_anomaly_detector_units():
    from ddl_tpu.obs import (
        HBMGrowthDetector,
        LossSpikeDetector,
        ThroughputRegressionDetector,
    )

    spike = LossSpikeDetector(window=10, sigma=4.0, min_points=5)
    assert all(spike.observe(1.0 + 0.01 * i) is None for i in range(8))
    a = spike.observe(5.0)
    assert a and a["type"] == "loss_spike" and a["value"] == 5.0

    reg = ThroughputRegressionDetector(window=10, drop=0.3, min_points=5)
    assert all(reg.observe(100.0) is None for i in range(8))
    assert reg.observe(95.0) is None  # within tolerance
    a = reg.observe(10.0)
    assert a and a["type"] == "throughput_regression"

    hbm = HBMGrowthDetector(window=4, min_growth=0.05)
    assert all(hbm.observe(1e9) is None for _ in range(6))  # flat: fine
    growth = HBMGrowthDetector(window=4, min_growth=0.05)
    vals = [1e9, 1.1e9, 1.2e9, 1.4e9]
    results = [growth.observe(v) for v in vals]
    assert results[-1] and results[-1]["type"] == "hbm_growth"
    assert growth.observe(None) is None  # no stats backend: degrade


def test_anomaly_monitor_emits_events(tmp_path):
    from ddl_tpu.obs import AnomalyMonitor, EventWriter, read_events

    w = EventWriter(tmp_path, "job", host=0)
    mon = AnomalyMonitor(w)
    for i in range(8):
        mon.observe_period(i, loss=1.0, steps_per_sec=50.0)
    found = mon.observe_period(8, loss=9.0, steps_per_sec=5.0)
    assert {a["type"] for a in found} == {
        "loss_spike", "throughput_regression"
    }
    w.close()
    events = read_events(w.path)
    assert len([e for e in events if e["kind"] == "anomaly"]) == 2
    assert len(mon.summary_lines()) == 2


def test_decode_emits_request_events(tmp_path):
    """Per-request decode telemetry: a decode event with tokens/s plus
    the request span with dispatch/wait children."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.infer import make_lm_generator
    from ddl_tpu.models.transformer import LMConfig, TransformerLM
    from ddl_tpu.obs import EventWriter, read_events

    cfg = LMConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, compute_dtype="float32", attn_impl="dense", remat=False,
    )
    import flax.linen as nn

    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(0), jnp.zeros((2, 4), jnp.int32)
        )["params"]
    )
    w = EventWriter(tmp_path, "decode-job", host=0)
    gen = make_lm_generator(
        cfg, prompt_len=4, max_new=3, batch=2, obs=w
    )
    toks = gen(params, jnp.zeros((2, 4), jnp.int32))
    assert toks.shape == (2, 3)
    toks = gen(params, jnp.ones((2, 4), jnp.int32))
    w.close()
    events = read_events(w.path)
    decodes = [e for e in events if e["kind"] == "decode"]
    assert len(decodes) == 2
    for d in decodes:
        assert d["tok_per_s"] > 0 and d["new_tokens"] == 3 and d["batch"] == 2
    spans = [e for e in events if e["kind"] == "span"]
    by_name = {e["name"]: e for e in spans}
    assert by_name["dispatch"]["parent"] == "decode_request"
    assert by_name["wait"]["parent"] == "decode_request"
    assert by_name["decode_request"]["parent"] is None

    # the summary aggregates decode telemetry
    from ddl_tpu.obs.report import load_run, summarize_run

    s = summarize_run(load_run(tmp_path, "decode-job"))
    assert s["decode"]["requests"] == 2
    assert s["decode"]["tokens"] == 12
    assert s["decode"]["mean_tok_per_s"] > 0


def test_train_lm_corpus_eval_writes_val_metrics(tmp_path):
    import examples.train_lm as train_lm

    from ddl_tpu.utils.csv_logger import read_metric_csv

    # tiny corpus: enough windows for a train/eval split at seq-len 16
    corpus = tmp_path / "corpus.npy"
    rng = np.random.default_rng(0)
    np.save(corpus, rng.integers(0, 255, 4096).astype(np.uint16))
    log_dir = tmp_path / "logs"
    _run_main(train_lm, [
        "--steps", "4", "--batch", "4", "--seq-len", "16",
        "--d-model", "32", "--layers", "2",
        "--corpus", str(corpus), "--eval-every", "2", "--eval-frac", "0.2",
        "--log-dir", str(log_dir), "--job-id", "lm-ev",
    ])
    job_dir = log_dir / "by_job_id" / "lm-ev"
    for metric in ("val_loss", "val_ppl"):
        rows = read_metric_csv(job_dir / f"{metric}.csv")
        assert rows and all(np.isfinite(r["value"]) for r in rows), metric


def test_train_vit_writes_metric_csvs(tmp_path):
    import examples.train_vit as train_vit

    from ddl_tpu.bench.analysis import final_epoch_quality, throughput_per_job
    from ddl_tpu.utils.csv_logger import read_metric_csv

    log_dir = tmp_path / "logs"
    _run_main(train_vit, [
        "--epochs", "2", "--batch", "8", "--image-size", "16", "--patch", "4",
        "--d-model", "32", "--layers", "2",
        "--num-train", "24", "--num-test", "13",  # odd test size: padding path
        "--log-dir", str(log_dir), "--job-id", "vit-test",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    job_dir = log_dir / "by_job_id" / "vit-test"
    for metric in (
        "loss", "epoch_time", "img_per_sec", "val_loss", "val_accuracy", "qwk"
    ):
        rows = read_metric_csv(job_dir / f"{metric}.csv")
        assert [r["epoch"] for r in rows] == [0, 1], metric
        assert all(np.isfinite(r["value"]) for r in rows), metric
    quality = final_epoch_quality(log_dir)
    assert "val_accuracy" in quality["vit"] or "val_loss" in quality["vit"]
    assert throughput_per_job(log_dir)["vit-test"]["img_per_sec"] > 0

    # event stream: ViT rides the same loop instrumentation (per-step
    # data_wait/h2d/step/fence spans, period events with eval phase)
    from ddl_tpu.obs import read_events
    from ddl_tpu.obs.events import events_path

    events = read_events(events_path(log_dir, "vit-test", 0))
    span_names = {e["name"] for e in events if e["kind"] == "span"}
    assert {"data_wait", "h2d", "step", "fence", "eval"} <= span_names
    periods = [e for e in events if e["kind"] == "period"]
    assert [p["period"] for p in periods] == [0, 1]
    for p in periods:
        assert p["phases"]["step"] > 0
        inner = sum(
            p["phases"].get(k, 0.0)
            for k in ("data_wait", "h2d", "step", "fence")
        )
        assert inner <= p["elapsed"] * 1.05 + 0.05
