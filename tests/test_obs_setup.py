"""Set-up's spans (``obs/steptrace.stage``, ``utils/compile_cache.CompileLog``):
a trainer's start is written stage by stage and compile by compile, what
is heard before a stream is open is kept and written with its true times,
a recompile is a named span inside the step that paid for it, and the
operator's readers (``obs goodput``'s ``startup`` bucket, ``obs
summarize``'s set-up line) read them through the fold.  All on the CPU.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from ddl_tpu.obs import EventWriter, StepTrace, read_events
from ddl_tpu.obs import steptrace as st_mod
from ddl_tpu.utils.compile_cache import CompileLog, compile_log

STAGES = {"setup.boot", "setup.model", "setup.data", "setup.plan"}
COMPILES = {"compile.trace", "compile.lower", "compile.backend"}
EPS = 1e-3  # JAX's clock and the stage's are both time.time(); float noise


@pytest.fixture
def new_process(monkeypatch):
    """What a process's first trainer sees: no boot span written yet and
    nothing kept from the tests that ran before in this worker."""
    monkeypatch.setattr(st_mod, "_booted", False)
    compile_log().kept.clear()


def _tiny_lm(tmp_path, log=True):
    from test_obs_goodput import _tiny_lm as build

    # widths no other test uses, so the step is traced here
    return build(tmp_path, "setup-lm", steps=6, log_every=2,
                 checkpoint_dir=None, **({} if log else {"log_dir": None}))


def _tiny_cnn(tmp_path, log=True):
    from test_obs_idle import _tiny_trainer

    return _tiny_trainer(tmp_path, log)


def _spans(trainer):
    trainer.obs.writer.close()
    return [e for e in read_events(trainer.obs.writer.path) if e["kind"] == "span"]


def _interval(e):
    return e["ts"] - e["dur"], e["ts"]


@pytest.mark.parametrize("family", ["lm", "cnn"])
def test_a_trainer_writes_its_start_stage_by_stage_and_compile_by_compile(
        tmp_path, new_process, family):
    trainer = (_tiny_lm if family == "lm" else _tiny_cnn)(tmp_path)
    trainer.run_period(0)
    spans = _spans(trainer)
    names = {e["name"] for e in spans}
    assert STAGES <= names and COMPILES <= names
    assert all(e.get("fn") for e in spans if e["name"] in COMPILES)
    boot = [e for e in spans if e["name"] == "setup.boot"]
    assert len(boot) == 1  # once a process
    first_stage = min(_interval(e)[0] for e in spans
                      if e["name"] in STAGES - {"setup.boot"})
    # from the process's start (long before this test) to the first stage
    assert boot[0]["ts"] == pytest.approx(first_stage, abs=EPS)
    assert boot[0]["dur"] > 1.0
    plan = next(e for e in spans if e["name"] == "setup.plan")
    assert plan["label"] == "train_step"
    # what the plan compiles is inside it and says so: the CNN's step is
    # lowered and compiled a second time (its state's placement differs
    # after the first step); the LM's comes out of JAX's in-memory caches
    # and only the traces of eval_shape are left
    in_plan = {e["name"] for e in spans if e.get("parent") == "setup.plan"}
    assert in_plan >= ({"compile.lower", "compile.backend"} if family == "cnn"
                       else {"compile.trace"})
    # a compile that a stage or a phase caused lies inside that span's
    # times; the parent is the nearest enclosing span of that name
    caused = [e for e in spans if e["name"] in COMPILES and e.get("parent")]
    assert caused
    for e in caused:
        lo, hi = _interval(e)
        holders = [p for p in spans if p["name"] == e["parent"]
                   and _interval(p)[0] - EPS <= lo and hi <= _interval(p)[1] + EPS]
        assert holders, (e["name"], e["fn"], e["parent"])
        assert e["depth"] == holders[0]["depth"] + 1
    # the first step's trace, lowering and compile are inside the step phase
    step0 = next(e for e in spans if e["name"] == "step")
    inside = [e for e in spans if e.get("parent") == "step"]
    assert {e["name"] for e in inside} == COMPILES
    # lowered and compiled once, in the first step (the second call's
    # signature differs and JAX looks the trace up again: a span of 0 s)
    assert all(e["step"] == step0["step"] for e in inside
               if e["name"] != "compile.trace" or e["dur"] > EPS)
    backend = [e for e in spans if e["name"] == "compile.backend"]
    assert all(isinstance(e["cache_hit"], bool) and e["cache_load_s"] >= 0 for e in backend)
    assert all(e["cache_load_s"] <= e["dur"] + EPS for e in backend)


def test_a_second_trainer_of_the_process_writes_no_second_boot(tmp_path, new_process):
    first = _tiny_cnn(tmp_path / "a")
    second = _tiny_cnn(tmp_path / "b")
    assert [e["name"] for e in _spans(first)].count("setup.boot") == 1
    assert "setup.boot" not in {e["name"] for e in _spans(second)}
    assert {"setup.model", "setup.data"} <= {e["name"] for e in _spans(second)}


def test_what_is_heard_before_a_stream_opens_is_written_with_its_true_times_in_order(tmp_path):
    log = CompileLog()  # one of its own: nothing registered with JAX
    t0 = time.time() - 100.0
    log.record("setup.boot", t0, t0 + 5.0)
    with_stage = log.stages()
    with_stage.append("setup.model")
    log._on_start("/jax/core/compile/backend_compile_duration", t0 + 6.0)
    log._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    log._on_span("/jax/core/compile/backend_compile_duration", t0 + 6.0, t0 + 7.0,
                 fun_name="jit(init)")
    with_stage.pop()
    log.record("setup.model", t0 + 5.0, t0 + 8.0)
    log._on_start("/jax/core/compile/backend_compile_duration", t0 + 8.5)
    log._on_span("/jax/core/compile/backend_compile_duration", t0 + 8.5, t0 + 9.0,
                 fun_name="jit(made)")
    log._on_span("/jax/not/a/compile", t0, t0 + 50.0)
    assert [k[0] for k in log.kept] == [
        "setup.boot", "compile.backend", "setup.model", "compile.backend"]
    assert (log.count, log.secs) == (2, pytest.approx(1.5))

    writer = EventWriter(tmp_path, "kept", host=0)
    writer.emit("run_start")

    class Sink:
        def heard(self, name, start, end, fields):
            writer.span_at(name, start, end, **fields)

    sink = Sink()
    log.attach(sink)
    assert not log.kept
    log.record("setup.plan", t0 + 20.0, t0 + 21.0, label="train_step")  # open: at once
    log.detach(sink)
    log.record("setup.plan", t0 + 30.0, t0 + 31.0)  # closed: kept again
    assert len(log.kept) == 1
    writer.close()
    events = read_events(writer.path)
    spans = [e for e in events if e["kind"] == "span"]
    assert [e["name"] for e in spans] == [
        "setup.boot", "compile.backend", "setup.model", "compile.backend", "setup.plan"]
    assert [round(e["ts"] - t0, 6) for e in spans] == [5.0, 7.0, 8.0, 9.0, 21.0]
    assert [round(e["dur"], 6) for e in spans] == [5.0, 1.0, 3.0, 0.5, 1.0]
    hit, made = spans[1], spans[3]
    assert (hit["fn"], hit["cache_hit"], hit["cache_load_s"]) == ("jit(init)", True, 0.75)
    assert (hit["parent"], hit["depth"]) == ("setup.model", 1)
    assert (made["cache_hit"], made["cache_load_s"], made["parent"], made["depth"]) == (
        False, 0.0, None, 0)
    # mono moves back with ts: durations within the host stay exact
    assert events[0]["mono"] - spans[0]["mono"] == pytest.approx(
        events[0]["ts"] - spans[0]["ts"], abs=1e-3)
    # the list is bounded: the newest are kept
    for i in range(CompileLog.KEEP + 10):
        log.record("compile.trace", t0, t0 + i)
    assert len(log.kept) == CompileLog.KEEP


def test_a_trace_inside_a_trace_or_a_lowering_is_a_part_not_a_span():
    log = CompileLog()
    trace, lower = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
    log._on_start(trace, 0.0)      # the step's trace
    log._on_start(trace, 1.0)      # a jitted function it calls
    log._on_span(trace, 1.0, 2.0, fun_name="inner")
    log._on_span(trace, 0.0, 3.0, fun_name="step")
    log._on_start(lower, 3.0)      # its lowering
    log._on_start(trace, 3.5)      # a rule traced while lowering
    log._on_span(trace, 3.5, 3.6, fun_name="rule")
    log._on_span(lower, 3.0, 4.0, fun_name="jit(step)")
    assert [(k[0], k[3]["fn"]) for k in log.kept] == [
        ("compile.trace", "step"), ("compile.lower", "jit(step)")]


def test_a_new_input_shape_is_one_named_backend_span_and_the_period_counts_it_as_before(
        tmp_path):
    from jax import monitoring

    old = {"count": 0, "secs": 0.0}  # the counter this listener replaced

    def on_duration(event, duration, **kw):
        if "backend_compile" in event:
            old["count"] += 1
            old["secs"] += duration

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        @jax.jit
        def setup_probe_fn(x):
            return jnp.tanh(x) * 3.0

        trace = StepTrace.create(tmp_path, "recompile", "probe", host=0)
        writer = trace.writer
        inputs = [jnp.ones((n,), jnp.float32) for n in (5, 5, 7)]  # the third: a new shape
        for period, x in enumerate(inputs):
            trace.begin_period()
            before = dict(old)
            with trace.phase("step", step=period):
                setup_probe_fn(x).block_until_ready()
            trace.end_period(period, period, elapsed=0.1, steps=1)
            seen = {k: old[k] - before[k] for k in old}
            event = [e for e in read_events(writer.path) if e["kind"] == "period"][-1]
            assert event["compiles"] == seen["count"] == (0 if period == 1 else 1)
            assert event["compile_s"] == pytest.approx(seen["secs"], abs=1e-6)
        trace.finish(verbose=False)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    spans = [e for e in read_events(writer.path) if e["kind"] == "span"]
    backend = [e for e in spans if e["name"] == "compile.backend" and e["step"] is not None]
    assert [(e["fn"], e["step"], e["parent"]) for e in backend] == [
        ("jit(setup_probe_fn)", 0, "step"), ("jit(setup_probe_fn)", 2, "step")]
    step2 = next(e for e in spans if e["name"] == "step" and e["step"] == 2)
    lo, hi = _interval(backend[1])
    assert _interval(step2)[0] - EPS <= lo and hi <= _interval(step2)[1] + EPS
    # the closed stream takes no more: what compiles now is kept
    kept = len(compile_log().kept)
    setup_probe_fn(jnp.ones((9,), jnp.float32)).block_until_ready()
    assert len(compile_log().kept) > kept


def test_without_a_log_dir_nothing_is_kept_beyond_the_bounded_list(tmp_path, new_process):
    trainer = _tiny_cnn(tmp_path, log=False)
    trainer.run_period(0)
    assert trainer.obs is None
    assert not (tmp_path / "logs").exists()
    kept = compile_log().kept
    assert 0 < len(kept) <= CompileLog.KEEP == kept.maxlen
    assert {"setup.boot", "setup.model", "setup.data"} <= {k[0] for k in kept}
    assert "setup.plan" not in {k[0] for k in kept}  # no stream, no plan


def test_with_step_spans_off_none_is_written(tmp_path, new_process, monkeypatch):
    monkeypatch.setenv("DDL_OBS_STEP_SPANS", "0")
    trainer = _tiny_cnn(tmp_path)
    trainer.run_period(0)
    trainer.obs.writer.close()
    events = read_events(trainer.obs.writer.path)
    assert not [e for e in events if e["kind"] == "span"]
    assert [e for e in events if e["kind"] == "hbm_plan"]  # the plan itself is made
    assert not compile_log().kept  # taken by the stream, and dropped there


# ------------------------------------------------------ the operator's side


def test_goodput_books_the_stages_to_startup_and_still_sums_to_the_wall(tmp_path, new_process):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.goodput import ledger_from_fold, render_goodput
    from ddl_tpu.obs.report import render_summary, summarize_from_fold

    trainer = _tiny_lm(tmp_path)
    trainer.train()
    spans = [e for e in read_events(trainer.obs.writer.path) if e["kind"] == "span"]
    stages = [e for e in spans if e["name"] in STAGES]
    fold = fold_job(tmp_path / "logs", "setup-lm", cache=False)
    ledger = ledger_from_fold(fold)
    (inc,) = ledger["incarnations"]
    sec = inc["seconds"]
    assert sec["startup"] == pytest.approx(sum(e["dur"] for e in stages))
    # the window begins where setup.boot does, so the boot is inside the
    # wall it is carved from and nothing goes negative
    boot = next(e for e in stages if e["name"] == "setup.boot")
    assert inc["start_ts"] == pytest.approx(boot["ts"] - boot["dur"])
    assert sum(sec.values()) == pytest.approx(inc["wall_s"], abs=1e-6)
    assert sec["untracked"] >= -1e-6 and sec["untracked"] < sec["startup"]
    job = ledger["job"]
    assert sum(job["seconds"].values()) == pytest.approx(job["wall_s"], abs=1e-6)
    assert "startup" in render_goodput(ledger)
    setup = inc["setup"]
    assert setup["boot"] == pytest.approx(boot["dur"])
    assert set(setup) >= {"boot", "model", "data", "plan", "trace_lower", "backend"}
    made = [e for e in spans if e["name"] == "compile.backend"]
    first_period = next(e["ts"] for e in read_events(trainer.obs.writer.path)
                        if e["kind"] == "period")
    early = [e for e in made if e["ts"] <= first_period]
    assert setup.get("hits", 0) + setup.get("misses", 0) == len(early)
    assert setup["backend"] == pytest.approx(sum(e["dur"] for e in early))
    line = next(l for l in render_summary(summarize_from_fold(fold)).splitlines()
                if l.startswith("set-up"))
    for word in ("boot", "model", "data", "plan", "trace+lower", "backend", "hit"):
        assert word in line
    # the sidecar carries it: a warm fold reads the same account
    warm = ledger_from_fold(fold_job(tmp_path / "logs", "setup-lm"))
    assert warm == ledger_from_fold(fold_job(tmp_path / "logs", "setup-lm")) == ledger


def test_a_relaunched_process_takes_its_boot_back_from_the_restart_gap(tmp_path):
    """Two processes of one incarnation: the dead time between them is
    restart gap, less what the second one's stages account for before it
    opened its stream (the second between its last stage and its
    ``run_start`` has no span and stays in the gap, as all of it did)."""
    from ddl_tpu.obs.fold import JobFold
    from ddl_tpu.obs.goodput import ledger_from_fold

    def ev(ts, kind, **f):
        return {"ts": ts, "mono": ts, "run": f.pop("run", "a"), "host": 0,
                "step": None, "kind": kind, **f}

    def span(name, start, end, **f):
        return ev(end, "span", name=name, dur=end - start, parent=None, depth=0, **f)

    events = [
        ev(100.0, "run_start"),
        span("setup.boot", 90.0, 98.0), span("setup.model", 98.0, 99.5),
        ev(110.0, "period", period=0, steps=2, offset=0, elapsed=5.0,
           phases={"step": 4.0, "fence": 1.0}, compile_s=0.0, compiles=0),
        # the process dies at 110; its successor starts at 130, opens its
        # stream at 140 and writes what it kept
        ev(140.0, "run_start", run="b"),
        span("setup.boot", 130.0, 137.0, run="b"), span("setup.data", 137.0, 139.0, run="b"),
        span("setup.plan", 141.0, 142.0, run="b", label="train_step"),
        ev(150.0, "period", run="b", period=1, steps=2, offset=0, elapsed=5.0,
           phases={"step": 4.0, "fence": 1.0}, compile_s=0.0, compiles=0),
    ]
    ledger = ledger_from_fold(JobFold.from_events(events))
    (inc,) = ledger["incarnations"]
    sec = inc["seconds"]
    assert inc["start_ts"] == 90.0 and inc["wall_s"] == 60.0
    assert sec["startup"] == pytest.approx(8.0 + 1.5 + 7.0 + 2.0 + 1.0)
    assert sec["restart_gap"] == pytest.approx(21.0)  # 110 -> 130 and 139 -> 140
    assert sec["productive"] == pytest.approx(10.0)
    assert sum(sec.values()) == pytest.approx(60.0)
    assert sec["untracked"] == pytest.approx(60.0 - 19.5 - 21.0 - 10.0)
