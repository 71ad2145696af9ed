"""The program's own account of the idle device and of the step's device
time (obs/steptrace.py, obs/scope.py, obs/hbm.py, bench/xprof.py).

* ``StepTrace``'s idle flag against a stub array with a settable
  ``is_ready()``: ``<phase>.idle`` child spans of the phases' own extent,
  set by the fence's drain, cleared by the next dispatch, never from an
  array that cannot say; nothing new in the period's phase totals, and
  ``obs goodput`` still sums to its total.
* ``Trainer.run_period`` at a tiny size: ``fence`` with ``fence.drain``
  and ``fence.d2h`` as its children, one ``collate`` span a batch from the
  loader's thread, the same losses as an untraced trainer.
* The scope-table reducer on a recorded HLO text, ``plan_program``'s
  table and its degradation, ``op_digest`` by tag and module by module
  (in process and from the run's files), ``obs hbm``'s line for the
  event's fields, and ``opcode_of`` against the benchmark's cases.
"""

import threading

import pytest

from ddl_tpu.obs import EventWriter, read_events
from ddl_tpu.obs.steptrace import StepTrace


class _Out:
    """A step output whose readiness the test sets."""

    def __init__(self, ready=False):
        self.ready = ready
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def _spans(tmp_path, job):
    events = read_events(tmp_path / "by_job_id" / job / "events-h000.jsonl")
    return [e for e in events if e["kind"] == "span"]


def _step(trace, i, out):
    with trace.phase("data_wait", step=i):
        pass
    with trace.phase("h2d", step=i):
        pass
    with trace.phase("step", step=i):
        pass
    trace.note_dispatch(out)


def test_no_idle_child_while_the_output_is_never_ready(tmp_path):
    trace = StepTrace(EventWriter(tmp_path, "busy", host=0))
    out = _Out(ready=False)
    for i in range(3):
        _step(trace, i, out)
    names = [s["name"] for s in _spans(tmp_path, "busy")]
    assert names == ["data_wait", "h2d", "step"] * 3
    assert out.asked == 4  # data_wait and h2d of each step after the first dispatch


def test_idle_children_have_the_phases_own_extent_once_the_output_is_ready(tmp_path):
    trace = StepTrace(EventWriter(tmp_path, "idle", host=0))
    out = _Out(ready=False)
    _step(trace, 0, out)
    out.ready = True
    _step(trace, 1, _Out(ready=False))
    spans = _spans(tmp_path, "idle")
    names = [s["name"] for s in spans]
    assert names == ["data_wait", "h2d", "step",
                     "data_wait.idle", "data_wait", "h2d.idle", "h2d",
                     "step"]  # a dispatch onto an idle device gets no child
    by = {(s["name"], s["step"]): s for s in spans}
    for phase, kid in (("data_wait", "data_wait.idle"), ("h2d", "h2d.idle")):
        child, parent = by[(kid, 1)], by[(phase, 1)]
        assert child["parent"] == phase and child["depth"] == 1
        assert parent["depth"] == 0
        # the same extent: the child opens and closes inside its parent
        assert 0 <= parent["dur"] - child["dur"] < 5e-3
    assert out.asked == 1  # once seen ready, not asked again


def test_the_drain_covers_the_next_periods_first_input_and_a_dispatch_clears_it(tmp_path):
    trace = StepTrace(EventWriter(tmp_path, "drain", host=0))
    _step(trace, 0, _Out(ready=False))
    with trace.phase("fence", step=0):
        with trace.child("fence.drain", step=0):
            pass
        trace.device_drained()
        with trace.child("fence.d2h", step=0):
            pass
    _step(trace, 1, _Out(ready=False))  # the next period's first step
    _step(trace, 2, _Out(ready=False))
    spans = _spans(tmp_path, "drain")
    names = [s["name"] for s in spans]
    assert names == [
        "data_wait", "h2d", "step",
        "fence.drain", "fence.d2h", "fence",
        "data_wait.idle", "data_wait", "h2d.idle", "h2d", "step",
        "data_wait", "h2d", "step",
    ]
    kids = [s for s in spans if s["name"].startswith("fence.")]
    assert all(s["parent"] == "fence" and s["depth"] == 1 for s in kids)


def test_an_array_without_is_ready_reads_as_unknown_never_idle(tmp_path):
    trace = StepTrace(EventWriter(tmp_path, "unknown", host=0))
    _step(trace, 0, object())
    _step(trace, 1, object())
    assert not [s for s in _spans(tmp_path, "unknown") if s["name"].endswith(".idle")]


@pytest.mark.parametrize("setting,expect", [
    (0, []),  # no per-step span of any name, and no is_ready() call
    (2, ["data_wait.idle", "data_wait", "h2d.idle", "h2d", "step", "collate"]),
])
def test_step_span_setting_governs_the_children_too(tmp_path, setting, expect):
    trace = StepTrace(EventWriter(tmp_path, "dial", host=0), emit_step_spans=setting)
    out = _Out(ready=True)
    trace.note_dispatch(out)
    hook = trace.collate_hook(0)
    for i in (1, 2):  # step 1 is thinned at 1-in-2, step 2 is written
        _step(trace, i, out)
        if hook is not None:
            with hook(i):
                pass
    with trace.phase("fence", step=3):
        with trace.child("fence.drain", step=3):
            pass
    assert [s["name"] for s in _spans(tmp_path, "dial")] == expect
    if setting == 0:
        assert hook is None and out.asked == 0


def test_children_stay_out_of_the_phase_totals_and_goodput_still_sums(tmp_path):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.goodput import ledger_from_fold
    from ddl_tpu.obs.steptrace import PHASES

    trace = StepTrace(EventWriter(tmp_path, "sum", host=0))
    trace.writer.emit("run_start", family="cnn", job_id="sum")
    for period in range(2):
        trace.begin_period()
        out = _Out(ready=True)
        for i in range(3):
            _step(trace, 3 * period + i, out)
        with trace.phase("fence", step=3 * period + 2):
            with trace.child("fence.drain", step=3 * period + 2):
                pass
            trace.device_drained()
            with trace.child("fence.d2h", step=3 * period + 2):
                pass
        phases = trace.end_period(period, period, elapsed=0.05, steps=3)
        assert set(phases) <= set(PHASES)
    trace.finish(verbose=False)
    events = read_events(tmp_path / "by_job_id" / "sum" / "events-h000.jsonl")
    assert {e["name"] for e in events if e["kind"] == "span"} >= {
        "data_wait.idle", "h2d.idle", "fence.drain", "fence.d2h"}
    assert set(next(e for e in events if e["kind"] == "run_end")["phases"]) <= set(PHASES)
    ledger = ledger_from_fold(fold_job(tmp_path, "sum", cache=False))
    for inc in ledger["incarnations"]:
        assert sum(inc["seconds"].values()) == pytest.approx(inc["wall_s"], abs=1e-9)
        assert inc["seconds"].get("other", 0.0) == 0.0
    job = ledger["job"]
    assert sum(job["seconds"].values()) == pytest.approx(job["wall_s"], abs=1e-9)


# ------------------------------------------------------------ the trainer


def _tiny_trainer(tmp_path, log: bool):
    from test_trainer import _datasets, _tiny_cfg

    from ddl_tpu.config import MeshConfig
    from ddl_tpu.train import Trainer

    cfg = _tiny_cfg(tmp_path, "single", MeshConfig(1, 1))
    if not log:
        cfg.train.log_dir = ""
    return Trainer(cfg, datasets=_datasets(cfg))


def test_run_period_emits_fence_children_and_collate_and_the_same_losses(tmp_path):
    traced = _tiny_trainer(tmp_path / "a", log=True)
    bare = _tiny_trainer(tmp_path / "b", log=False)
    assert bare.obs is None
    m_traced, steps = traced.run_period(0)
    m_bare, steps_bare = bare.run_period(0)
    assert steps == steps_bare == 4
    assert m_traced == m_bare  # no arithmetic and no order changed
    assert bare.train_loader.on_collate is None  # untraced: none of the new calls
    traced.obs.writer.close()
    spans = [e for e in read_events(traced.obs.writer.path) if e["kind"] == "span"]
    names = [s["name"] for s in spans]
    at = names.index("fence.drain")
    assert names[at:at + 3] == ["fence.drain", "fence.d2h", "fence"]
    assert all(spans[at + k]["parent"] == "fence" for k in (0, 1))
    collate = [s for s in spans if s["name"] == "collate"]
    assert [s["step"] for s in collate] == [0, 1, 2, 3]
    assert all(s["parent"] is None and s["depth"] == 0 for s in collate)
    # the first step of a run finds the device idle: nothing dispatched yet
    # reads as unknown, so the children start after the first fence's drain
    m2, _ = traced.run_period(1)
    traced.obs.writer.close()
    spans = [e for e in read_events(traced.obs.writer.path) if e["kind"] == "span"]
    first_of_second = [s["name"] for s in spans if s["step"] == 4]
    assert {"data_wait.idle", "h2d.idle"} <= set(first_of_second)


def test_collate_hook_runs_on_the_producer_thread():
    import numpy as np

    from ddl_tpu.data.loader import DataLoader

    class Rows:
        labels = np.arange(8) % 5

        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.zeros((2, 2, 3), np.uint8), int(self.labels[i])

    seen = []

    class Hook:
        def __init__(self, b):
            self.b = b

        def __enter__(self):
            seen.append((self.b, threading.current_thread() is threading.main_thread()))

        def __exit__(self, *exc):
            return False

    loader = DataLoader(Rows(), 2, shuffle=False, num_workers=0, on_collate=Hook)
    loader.set_start_batch(1)
    assert len(list(loader)) == 3
    assert seen == [(1, False), (2, False), (3, False)]


# -------------------------------------------------------- the scope table

# An optimized module as the TPU compiler prints it, cut to what the
# reducer reads: a fused computation before ENTRY, a forward fusion, a
# tuple-typed Pallas custom call (named and not; the first with the
# ``metadata`` a kernel may carry, which the compiler prints as JSON over
# several unindented lines), a backward fusion, a prefetch without
# metadata whose consumer is forward, and the update.
_HLO = '''HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,128]) -> bf16[8,128] {
  %param_0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %tanh.9 = bf16[8,128]{1,0:T(8,128)(2,1)} tanh(%param_0), metadata={op_name="jit(train_step)/jvp()/tanh"}
}

ENTRY %main.7 (p.1: f32[128,128], x.1: bf16[8,128]) -> (f32[128,128], f32[]) {
  %p.1 = f32[128,128]{1,0:T(8,128)} parameter(0), metadata={op_name="p"}
  %x.1 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="x"}
  %copy-start.3 = (f32[128,128]{1,0:T(8,128)S(1)}, f32[128,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%p.1)
  %copy-done.3 = f32[128,128]{1,0:T(8,128)S(1)} copy-done(%copy-start.3)
  %fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(%x.1, %copy-done.3), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp()/dot_general" stack_frame_id=7}
  %jvp_flash_fwd_.2 = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, f32[8,1]{1,0:T(1,128)}) custom-call(%fusion.12), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"tiles_computed":"1920",
"tiles_masked":"768",
"tiles_row_steps":"196608",
"tiles_total":"3072"
}}, metadata={op_name="jit(train_step)/jvp(flash_fwd)/pallas_call" stack_frame_id=37}, backend_config={"custom_call_config":{"body":"TUzvUgFN(%notanoperand)"}}
  %get-tuple-element.4 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%jvp_flash_fwd_.2), index=0
  %attn.45 = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8,128]{1,0:T(8,128)(2,1)}) custom-call(%get-tuple-element.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(jit(_flash_lse)))/pallas_call" stack_frame_id=35}
  %get-tuple-element.5 = bf16[8,128]{1,0:T(8,128)(2,1)} get-tuple-element(%attn.45), index=0
  %transpose_jvp_flash_bwd_dkv__.2 = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8,128]{1,0:T(8,128)(2,1)}) custom-call(%get-tuple-element.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(flash_bwd_dkv))/pallas_call" stack_frame_id=35}
  %get-tuple-element.6 = bf16[8,128]{1,0:T(8,128)(2,1)} get-tuple-element(%transpose_jvp_flash_bwd_dkv__.2), index=0
  %custom-call.37 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(%get-tuple-element.6), custom_call_target="ConcatBitcast"
  %fusion.30 = f32[128,128]{1,0:T(8,128)} fusion(%x.1, %custom-call.37), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(train_step)/transpose(jvp())/dot_general" stack_frame_id=7}
  %fusion.235 = f32[128,128]{1,0:T(8,128)} fusion(%p.1, %fusion.30), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/add" stack_frame_id=9}
  %copy.8 = f32[]{:T(128)} copy(%fusion.235)
  ROOT %tuple.1 = (f32[128,128]{1,0:T(8,128)}, f32[]{:T(128)}) tuple(%fusion.235, %copy.8)
}
'''


def test_scope_table_on_a_recorded_text():
    from ddl_tpu.obs.scope import scope_table, tag_counts

    table = scope_table(_HLO)
    assert table == {
        "copy-start.3": "fwd",   # no metadata: its first consumer's
        "copy-done.3": "fwd",
        "fusion.12": "fwd",
        "jvp_flash_fwd_.2": "kernel/flash_fwd",
        "attn.45": "kernel/_flash_lse",  # an unnamed kernel: its scope
        "transpose_jvp_flash_bwd_dkv__.2": "kernel/flash_bwd_dkv",
        "custom-call.37": "bwd",  # a kernel hands its direction down, not its name
        "fusion.30": "bwd",
        "fusion.235": "update",
        "copy.8": "update",      # no metadata and no consumer that has
    }
    assert "tanh.9" not in table and "p.1" not in table  # ENTRY's own ops only
    assert tag_counts(table) == {
        "bwd": 2, "fwd": 3, "kernel/_flash_lse": 1, "kernel/flash_bwd_dkv": 1,
        "kernel/flash_fwd": 1, "update": 2}
    assert scope_table("no entry here") == {}


def test_kernel_tiles_on_a_recorded_text():
    """The kernels' own account of their sub-tiles, summed by kernel; a
    kernel without metadata, and a text without kernels, give nothing."""
    from ddl_tpu.obs.scope import kernel_tiles

    one = {"calls": 1, "computed": 1920, "masked": 768, "row_steps": 196608, "total": 3072}
    assert kernel_tiles(_HLO) == {"flash_fwd": one}
    again = _HLO.replace("%jvp_flash_fwd_.2 = ", "%jvp_flash_fwd_.3 = ")
    entry = _HLO.index("ENTRY")
    kernel = again[again.index("  %jvp_flash_fwd_.3"):again.index("  %get-tuple-element.4")]
    twice = _HLO[:entry] + _HLO[entry:].replace("  %get-tuple-element.4", kernel + "  %get-tuple-element.4", 1)
    assert kernel_tiles(twice) == {"flash_fwd": {k: 2 * v for k, v in one.items()}}
    assert kernel_tiles("no entry here") == {}


def test_plan_program_keeps_the_table_and_writes_it_beside_the_events(tmp_path, monkeypatch):
    import json

    import jax
    import jax.numpy as jnp

    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.scope import load_tables

    monkeypatch.setattr(hbm, "_recent_plans", {})  # this process's, not an earlier test's
    w = EventWriter(tmp_path, "scope", host=0)

    def loss(p, x):
        return jnp.sum(jnp.tanh(x @ p) ** 2)

    @jax.jit
    def step(p, x):
        l, g = jax.value_and_grad(loss)(p, x)
        return p - 0.1 * g, l

    event = hbm.plan_program(w, "tiny_step", step, (jnp.ones((8, 8)), jnp.ones((4, 8))))
    table = hbm.scope_table("tiny_step")
    assert table and set(table.values()) == {"fwd", "bwd", "update"}
    assert "scope" not in event and len(json.dumps(event)) < 2000
    assert event["scope_counts"] == {t: list(table.values()).count(t) for t in sorted(set(table.values()))}
    # counts and the file's name, nothing else new in the event
    assert set(event) - set(hbm.PLAN_FIELDS) == {
        "ts", "mono", "run", "host", "step", "kind", "label"}
    on_disk = json.load(open(w.path.parent / event["scope_file"]))
    assert on_disk["tags"] == table and on_disk["label"] == "tiny_step"
    # the table goes with its module's name, in process and on disk
    assert on_disk["module"] == "jit_step"
    assert hbm.scope_tables() == {"jit_step": table}
    assert load_tables(w.path.parent / "xprof" / "h000" / "capture-1") == {"jit_step": table}
    assert load_tables(tmp_path) == {}  # above the run's directory: none
    # the forensic dump carries the budgets, never the table
    dump = hbm.dump_oom(w, RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert "scope" not in dump["plans"]["tiny_step"]
    # no executable, no table: the aval budget alone
    hbm.plan_program(w, "tiny_aval", step, (jnp.ones((8, 8)), jnp.ones((4, 8))), mode="aval")
    assert hbm.scope_table("tiny_aval") is None and hbm.scope_table("never") is None


def test_plan_program_degrades_to_no_table_without_raising(tmp_path, monkeypatch):
    from ddl_tpu.obs import hbm

    monkeypatch.setattr(hbm, "_recent_plans", {})

    class NoText:
        def memory_analysis(self):
            return None

        def as_text(self):
            raise RuntimeError("a loaded executable without its module")

    class Fn:
        def lower(self, *a, **k):
            return self

        def compile(self):
            return NoText()

        def __call__(self, x):
            return x

    w = EventWriter(tmp_path, "degrade", host=0)
    event = hbm.plan_program(w, "no_text", Fn(), (1.0,))
    assert event["kind"] == "hbm_plan" and "scope_file" not in event
    assert hbm.scope_table("no_text") is None


_FWD = "%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(%x.1), kind=kOutput"
_UPD = "%fusion.235 = f32[128,128]{1,0:T(8,128)} fusion(%p.1), kind=kLoop"
_KERNEL = "%flash_fwd.2 = (bf16[8,128]{1,0}, f32[8,1]{1,0}) custom-call(%fusion.12)"
_COPY = "%copy.99 = f32[] copy(%a)"
_STEP_TABLE = {"fusion.12": "fwd", "fusion.235": "update", "flash_fwd.2": "kernel/flash_fwd"}


def _device_planes():
    """A train step (0-10 ms) and an eval step (20-26 ms) on one device:
    the eval step has a ``fusion.12`` of its own."""
    return [("/device:TPU:0", [
        ("XLA Modules", [("jit_train_step(123)", 0.0, 10.0), ("jit_eval_step(77)", 20.0, 6.0)]),
        ("XLA Ops", [(_FWD, 0.0, 3.0), (_KERNEL, 3.0, 4.0), (_UPD, 7.0, 2.0), (_COPY, 9.0, 1.0),
                     (_FWD, 20.0, 5.0), (_COPY, 25.0, 1.0)]),
    ])]


def test_op_digest_joins_each_op_with_the_table_of_its_own_module(monkeypatch):
    from ddl_tpu.bench import xprof

    monkeypatch.setattr(xprof, "read_trace", lambda d: _device_planes())
    by_tag = xprof.op_digest("unused", scope={"jit_train_step": _STEP_TABLE})
    assert by_tag["by"] == "scope" and by_tag["module_ms"] == 16.0
    # the eval step's fusion.12 is not the train step's forward
    assert by_tag["ops"] == {"other (fusion:Output)": 5.0, "kernel/flash_fwd": 4.0, "fwd": 3.0,
                             "update": 2.0, "other (copy)": 2.0}
    both = xprof.op_digest("unused", scope={"jit_train_step": _STEP_TABLE,
                                            "jit_eval_step": {"fusion.12": "eval"}})
    assert both["ops"]["eval"] == 5.0 and both["ops"]["fwd"] == 3.0
    # analyze sums an op's time over the modules, as it did
    per_op, counts, _, module_ms = xprof.analyze("unused")
    assert per_op[_FWD] == 8.0 and counts[_FWD] == 2 and module_ms == 16.0


@pytest.mark.parametrize("planes,scope", [
    (_device_planes(), {}),                                    # no table at hand
    (_device_planes(), {"jit_other": _STEP_TABLE}),            # a table of another program
    ([("/host:CPU", [("tf_XLAEigen/1", [(_FWD, 0.0, 8.0), (_KERNEL, 8.0, 4.0), (_UPD, 12.0, 2.0),
                                         (_COPY, 14.0, 2.0)])])],
     {"jit_train_step": _STEP_TABLE}),                         # a CPU trace names no module
])
def test_op_digest_groups_by_opcode_when_no_op_finds_a_tag(monkeypatch, planes, scope):
    from ddl_tpu.bench import xprof

    monkeypatch.setattr(xprof, "read_trace", lambda d: planes)
    digest = xprof.op_digest("unused", scope=scope)
    assert digest["by"] == "opcode"
    assert digest["ops"]["custom call (Pallas)"] == 4.0
    assert digest["ops"]["conv/matmul fusion (+fused elementwise)"] == 8.0


def test_op_digest_finds_its_tables_in_process_or_in_the_runs_files(tmp_path, monkeypatch):
    """What ``profile_capture`` does in the training process, and what
    ``ddl_tpu bench digest`` does in another over the stored capture."""
    from ddl_tpu.bench import xprof
    from ddl_tpu.obs import hbm
    from ddl_tpu.obs.scope import write_table

    monkeypatch.setattr(xprof, "read_trace", lambda d: _device_planes())
    capture = tmp_path / "job" / "xprof" / "h000" / "step-40"
    capture.mkdir(parents=True)
    monkeypatch.setattr(hbm, "_recent_plans", {})
    assert xprof.op_digest(str(capture))["by"] == "opcode"  # nothing planned, no file
    name = write_table(tmp_path / "job", 0, "train_step", "jit_train_step", _STEP_TABLE)
    assert name == "scope-h000-train_step.json"
    (tmp_path / "job" / "scope-h000-torn.json").write_text("{")  # skipped, not raised
    from_files = xprof.op_digest(str(capture))
    assert from_files["by"] == "scope" and from_files["ops"]["kernel/flash_fwd"] == 4.0
    # this process's own tables win over the files
    monkeypatch.setattr(hbm, "_recent_plans", {"train_step": {
        "scope": {"fusion.12": "bwd"}, "scope_module": "jit_train_step"}})
    assert xprof.op_digest(str(capture))["ops"]["bwd"] == 3.0


def test_obs_hbm_prints_the_events_scope_counts_and_file(tmp_path):
    from ddl_tpu.obs.fold import fold_job
    from ddl_tpu.obs.hbm import account_from_fold, render_hbm

    w = EventWriter(tmp_path, "plan", host=0)
    w.emit("hbm_plan", label="train_step", analysis="memory_analysis", argument_bytes=4096,
           output_bytes=4096, temp_bytes=512, alias_bytes=0, code_bytes=64,
           scope_counts={"bwd": 7, "fwd": 5, "kernel/flash_fwd": 1, "update": 2},
           scope_file="scope-h000-train_step.json",
           kernel_tiles={"flash_fwd": {"calls": 12, "computed": 23040, "masked": 9216, "total": 36864,
                                       "row_steps": 2359296},
                         "flash_bwd_dkv": {"calls": 12, "computed": 23040, "masked": 9216, "total": 36864},
                         "moe_rows_gather": {"calls": 4, "total": 2048, "floor": 32},
                         "moe_rows_combine": {"calls": 4, "total": 2176, "floor": 160, "passes": 4},
                         "moe_rows_combine_bwd": {"calls": 4, "total": 2048, "floor": 32, "passes": 12}})
    w.emit("hbm_plan", label="eval_step", analysis="aval", argument_bytes=64, output_bytes=8)
    w.emit("hbm_sample", params_bytes=600, watermark=2000, peak=2000, limit=4096, synthetic=True)
    w.close()
    out = render_hbm(account_from_fold(fold_job(tmp_path, "plan", cache=False)), "plan")
    assert ("scope (scope-h000-train_step.json): bwd 7, fwd 5, kernel/flash_fwd 1, update 2"
            in out)
    assert out.count("scope (") == 1  # a plan without a table gets no such line
    # a flash kernel's sub-tiles and the (row, K step) pairs of its walk; a
    # record from before the count was carried prints without it
    assert ("tiles flash_fwd: 12 call(s), 23040 of 36864 sub-tiles computed (62.5%), 9216 masked, "
            "2359296 row-steps\n" in out)
    assert ("tiles flash_bwd_dkv: 12 call(s), 23040 of 36864 sub-tiles computed (62.5%), 9216 masked\n"
            in out)
    # a kernel whose steps the routing decides says its grid's two ends
    # (a record from before the row kernels counted their products: no more)
    assert "tiles moe_rows_gather: 4 call(s), 2048 grid steps at most, 32 at least\n" in out
    # and its MXU products a pair, ``passes`` over ``calls``: 1 in bf16, 3
    # for a float32's addends
    assert ("tiles moe_rows_combine: 4 call(s), 2176 grid steps at most, 160 at least, "
            "1 product(s) a pair\n" in out)
    assert ("tiles moe_rows_combine_bwd: 4 call(s), 2048 grid steps at most, 32 at least, "
            "3 product(s) a pair" in out)
    assert out.count("    tiles ") == 5


# the benchmark's cases (tests/benchmark: test_trace_reduction_on_a_hand_built_trace)
@pytest.mark.parametrize("name,opcode", [
    ("%attn.45 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, f32[192,1,1024]{2,1,0:T(1,128)}) "
     "custom-call(%bitcast.1, %bitcast.2), custom_call_target=\"tpu_custom_call\"", "custom-call"),
    ("%fusion.12 = bf16[16,1024,768]{2,1,0:T(8,128)(2,1)S(1)} fusion(%p.1, %p.2), kind=kOutput, "
     "calls=%fused_computation.12", "fusion:Output"),
    ("%copy.5 = f32[768]{0:T(1024)} copy(%fusion.3)", "copy"),
    ("%copy-done.30 = f32[768,2304]{1,0:T(8,128)S(1)} copy-done(%copy-start.30)", "copy-done"),
    ("%convert.1 = bf16[8]{0} convert(%fusion.9), metadata={op_name=\"a/fusion\"}", "convert"),
    ("fusion.123", "fusion"),
    ("copy-start.4", "copy-start"),
])
def test_opcode_of_reads_tuple_typed_ops(name, opcode):
    from benchmark import trace as bench_trace

    from ddl_tpu.bench.xprof import opcode_of, own_name

    assert opcode_of(name) == opcode == bench_trace.opcode_of(name)
    assert own_name(name) == bench_trace.own_name(name)
