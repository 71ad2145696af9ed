"""The per-layer metrics that read the program's own account (PR 25):
``program_scope.scope_ms`` on a hand-built trace and table, and the span
sums' parameter files read through ``program_span.phase_share_pct`` on
hand-built spans.  No chip, no topology."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, trace as tr  # noqa: E402
from benchmark.readers import program_scope, program_span  # noqa: E402

SPAN_SUMS = ["idle_seen_pct.train", "input_exposed_pct.train",
             "period_end_exposed_pct.train", "loader_busy_pct.train"]
SCOPE_MS = ["fwd_ms.train", "bwd_ms.train", "update_ms.train", "flash_kernels_ms.train"]
LM, DN = "gpt2s.train_b16_t1024", "densenet121.train_b120"


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


# two traced steps on one chip: a forward fusion, the three flash kernels
# (tuple-typed, as the profiler prints them), a backward fusion, the update
_OPS = [
    ("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(%x.1), kind=kOutput", 0.000, 0.010),
    ("%jvp_flash_fwd_.2 = (bf16[8,128]{1,0}, f32[8,1]{1,0:T(1,128)}) custom-call(%fusion.12)", 0.010, 0.014),
    ("%transpose_jvp_flash_bwd_dq__.2 = bf16[8,128]{1,0} custom-call(%a)", 0.014, 0.017),
    ("%transpose_jvp_flash_bwd_dkv__.2 = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) custom-call(%a)", 0.017, 0.022),
    ("%fusion.30 = f32[128,128]{1,0:T(8,128)} fusion(%x.1), kind=kOutput", 0.022, 0.042),
    ("%fusion.235 = f32[128,128]{1,0:T(8,128)} fusion(%p.1), kind=kLoop", 0.042, 0.046),
]
_TABLE = {
    "fusion.12": "fwd", "jvp_flash_fwd_.2": "kernel/flash_fwd",
    "transpose_jvp_flash_bwd_dq__.2": "kernel/flash_bwd_dq",
    "transpose_jvp_flash_bwd_dkv__.2": "kernel/flash_bwd_dkv",
    "fusion.30": "bwd", "fusion.235": "update",
}


def _ctx(ops=_OPS, steps=2):
    return {"trace": tr.Trace(ops={"/device:TPU:0": list(ops)}, modules={}),
            "traced": {"steps": steps}, "notes": {}}


@pytest.fixture
def table(monkeypatch):
    from ddl_tpu.obs import hbm

    plans = {"train_step": {"analysis": "memory_analysis", "scope": dict(_TABLE)}}
    monkeypatch.setattr(hbm, "_recent_plans", plans)
    return plans["train_step"]["scope"]


def test_scope_ms_joins_the_trace_with_the_table_by_instruction_name(table):
    ctx = _ctx()
    got = {name: program_scope.scope_ms(ctx, _spec(name)["params"]) for name in SCOPE_MS}
    assert got == pytest.approx({"fwd_ms.train": 5.0, "bwd_ms.train": 10.0,
                                 "update_ms.train": 2.0, "flash_kernels_ms.train": 6.0})
    # the parts are the whole: what device_step_ms.train reads from the same trace
    assert sum(got.values()) == pytest.approx(1e3 * ctx["trace"].busy_s() / 2)
    note = ctx["notes"]["scope.train_step"]
    assert note["coverage"] == pytest.approx(1.0)
    assert note["ms_per_step"]["kernel/flash_bwd_dkv"] == pytest.approx(2.5)


def test_scope_ms_gives_no_number_under_95_percent_coverage(table):
    # 0.0022 s of 0.046 s unknown to the table: 95.2% named, a number
    just = _ctx(_OPS + [("%fusion.999 = f32[] fusion(%q), kind=kLoop", 0.046, 0.0482)])
    assert program_scope.scope_ms(just, {"label": "train_step", "tags": ["fwd"]}) == pytest.approx(5.0)
    # 0.004 s unknown: 92%, no number, and the coverage says why
    short = _ctx(_OPS + [("%fusion.999 = f32[] fusion(%q), kind=kLoop", 0.046, 0.050)])
    assert program_scope.scope_ms(short, {"label": "train_step", "tags": ["fwd"]}) is None
    assert short["notes"]["scope.train_step"]["coverage"] == pytest.approx(0.92)


def test_scope_ms_is_none_never_zero_when_there_is_nothing_to_read(table, monkeypatch):
    from ddl_tpu.obs import hbm

    params = {"label": "train_step", "tags": ["fwd"]}
    # a kernel the program does not have (an unnamed one from an older cache entry)
    assert program_scope.scope_ms(_ctx(), {"label": "train_step", "tags": ["kernel/other"]}) is None
    assert program_scope.scope_ms(_ctx(), {"label": "eval_step", "tags": ["fwd"]}) is None
    assert program_scope.scope_ms({"trace": None, "traced": {"steps": 2}}, params) is None
    assert program_scope.scope_ms(_ctx(steps=0), params) is None
    monkeypatch.setattr(hbm, "_recent_plans", {"train_step": {"analysis": "aval"}})
    assert program_scope.scope_ms(_ctx(), params) is None  # DDL_HBM_PLAN=aval: no table
    monkeypatch.delattr(hbm, "scope_table")  # a program older than the table
    assert program_scope.scope_ms(_ctx(), params) is None


def test_scope_ms_never_sums_the_kernels_that_are_left_when_one_is_gone(table):
    """A flash kernel renamed, fused away or split keeps a ``kernel/`` tag
    of some name, so the coverage rule cannot see it: the metric must."""
    params = _spec("flash_kernels_ms.train")["params"]
    table["transpose_jvp_flash_bwd_dq__.2"] = "kernel/flash_bwd_dq_v2"
    ctx = _ctx()
    assert program_scope.scope_ms(ctx, params) is None
    assert ctx["notes"]["scope.train_step.missing"] == ["kernel/flash_bwd_dq"]
    assert ctx["notes"]["scope.train_step"]["coverage"] == pytest.approx(1.0)
    # a direction that took no device time is 0, and the others still read
    for name in [n for n, t in table.items() if t == "update"]:
        table[name] = "bwd"
    ctx = _ctx()
    assert program_scope.scope_ms(ctx, _spec("update_ms.train")["params"]) == 0.0
    assert program_scope.scope_ms(ctx, _spec("bwd_ms.train")["params"]) == pytest.approx(12.0)


# the window 100..110 s; a period's first input with the device drained,
# a later one with the device busy, the fence and its children, the loader
_SPANS = [
    ("data_wait", 100.0, 100.4), ("data_wait.idle", 100.0, 100.4),
    ("h2d", 100.4, 100.5), ("h2d.idle", 100.4, 100.5),
    ("step", 100.5, 100.6),
    ("data_wait", 100.6, 101.6), ("h2d", 101.6, 101.7), ("step", 101.7, 101.8),
    ("fence", 105.0, 106.0), ("fence.drain", 105.0, 105.7), ("fence.d2h", 105.7, 106.0),
    ("collate", 99.0, 101.0), ("collate", 101.0, 104.0),
]
_WINDOW = {"wall_start": 100.0, "wall_end": 110.0}


@pytest.mark.parametrize("name,expect", [
    ("idle_seen_pct.train", 8.0),            # 0.4 + 0.1 + 0.3 of 10 s
    ("input_exposed_pct.train", 5.0),        # 0.4 + 0.1
    ("period_end_exposed_pct.train", 3.0),   # fence.d2h alone
    ("loader_busy_pct.train", 40.0),         # 1 s of the first collate, 3 s of the second
])
def test_span_sums_read_the_new_spans_through_the_accepted_reader(name, expect):
    spec = _spec(name)
    assert spec["reader"] == "program_span.phase_share_pct"
    ctx = {"window": _WINDOW, "spans": _SPANS}
    assert program_span.phase_share_pct(ctx, spec["params"]) == pytest.approx(expect)
    # the exposed share never passes the older metric that sums the whole phases
    older = program_span.phase_share_pct(ctx, _spec("input_wait_pct.train")["params"])
    assert older == pytest.approx(16.0)
    # a cell without such a span (the parent's program): None, not 0
    plain = [s for s in _SPANS if "." not in s[0] and s[0] != "collate"]
    assert program_span.phase_share_pct({"window": _WINDOW, "spans": plain}, spec["params"]) is None


@pytest.mark.parametrize("name", SPAN_SUMS + SCOPE_MS)
def test_every_new_per_layer_entry_has_its_file_and_loads(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    spec = _spec(name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert spec.get("workloads") == entry.get("workloads")
    assert entry["moves"] == "train_steps_per_s"
    cells = entry.get("workloads", [LM, DN])
    for cell in (LM, DN):
        loaded = {m["name"]: m for m in harness.load_cell(ROOT, cell)["per_layer"]}
        assert (name in loaded) == (cell in cells)
        if name in loaded:
            assert loaded[name]["reader"] == spec["reader"]
            assert loaded[name]["params"] == spec["params"]
