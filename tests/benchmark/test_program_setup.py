"""The set-up reader (``benchmark/readers/program_setup.py``) on hand-built
spans, and the five metrics' files.  No chip, no JAX."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.readers import program_setup  # noqa: E402

NEW = ("boot_s", "trace_lower_s", "backend_compile_s", "hbm_plan_s", "setup_seen_pct")
LAYER = "launch (launch.py, utils/compile_cache.py)"


def spec(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def read(name: str, ctx: dict):
    s = spec(name)
    mod, fn = s["reader"].split(".")
    assert mod == "program_setup"
    return getattr(program_setup, fn)(ctx, s["params"])


# A start of 100 s on the spans' clock: the process begins at 1000, the
# window's first call is at 1100.
SPANS = [
    ("setup.boot", 1000.0, 1020.0),
    ("compile.backend", 1015.0, 1016.0),    # the caller's, inside the boot
    ("setup.model", 1020.0, 1040.0),
    ("compile.trace", 1021.0, 1029.0),      # inside the stage: once
    ("compile.lower", 1029.0, 1033.0),
    ("compile.backend", 1033.0, 1039.0),
    ("setup.data", 1040.0, 1041.0),
    ("compile.trace", 1045.0, 1047.0),      # between stages (the caller's weights)
    ("data_wait", 1050.0, 1051.0),
    ("step", 1051.0, 1071.0),
    ("compile.trace", 1051.0, 1058.0),
    ("compile.lower", 1058.0, 1063.0),
    ("compile.backend", 1063.0, 1070.0),
    ("setup.plan", 1071.0, 1085.0),
    ("compile.trace", 1072.0, 1075.0),
    ("compile.lower", 1075.0, 1078.0),
    ("compile.backend", 1078.0, 1080.0),
    ("fence", 1085.0, 1086.0),
    ("step", 1090.0, 1110.0),               # across the window's start: cut
    ("compile.lower", 1095.0, 1105.0),      # likewise
    ("compile.backend", 1200.0, 1210.0),    # after it: not set-up's
]


def ctx_of(spans, wall_start=1100.0):
    return {"window": {"wall_start": wall_start, "wall_end": wall_start + 10.0},
            "spans": list(spans), "notes": {}}


def test_each_metric_on_hand_built_spans():
    ctx = ctx_of(SPANS)
    assert read("boot_s", ctx) == pytest.approx(20.0)
    # 8 + 4 in the model stage, 2 between stages, 7 + 5 in the step,
    # 3 + 3 in the plan, and 5 of the lowering that crosses the window's start
    assert read("trace_lower_s", ctx) == pytest.approx(8 + 4 + 2 + 7 + 5 + 3 + 3 + 5)
    assert read("backend_compile_s", ctx) == pytest.approx(1 + 6 + 7 + 2)
    assert read("hbm_plan_s", ctx) == pytest.approx(14.0)
    # covered: 1000-1041 (boot, model, data), 1045-1047, 1050-1086
    # (data_wait, step, plan, fence), 1090-1100 (the cut step)
    assert read("setup_seen_pct", ctx) == pytest.approx(41 + 2 + 36 + 10)
    assert ctx["notes"]["setup_from_boot_s"] == pytest.approx(100.0)


def test_nested_and_overlapping_spans_count_once():
    spans = [("setup.boot", 0.0, 10.0),
             ("compile.trace", 10.0, 20.0), ("compile.trace", 12.0, 15.0),   # nested
             ("compile.lower", 18.0, 25.0),                                  # overlapping
             ("compile.trace", 30.0, 31.0)]
    assert read("trace_lower_s", ctx_of(spans, 40.0)) == pytest.approx(15.0 + 1.0)
    assert read("setup_seen_pct", ctx_of(spans, 40.0)) == pytest.approx(100 * 26 / 40)


def test_no_boot_span_gives_no_number_under_any_name():
    """A program from before these spans (the parent, under this PR's
    benchmark files) is read without a number and without an error."""
    old = [s for s in SPANS if not s[0].startswith(("setup.", "compile."))]
    for ctx in (ctx_of(old), ctx_of([]), {"window": None, "spans": None}, {},
                ctx_of([("setup.boot", 2000.0, 2001.0)])):  # a window before the boot
        for name in NEW:
            assert read(name, ctx) is None


def test_a_start_without_a_plan_reads_zero_not_nothing():
    spans = [s for s in SPANS if s[0] != "setup.plan"]
    assert read("hbm_plan_s", ctx_of(spans)) == 0.0


@pytest.mark.parametrize("seed", range(8))
def test_coverage_cannot_pass_100_nor_the_parts_the_whole(seed):
    import random

    rng = random.Random(seed)
    names = ["setup.model", "setup.data", "setup.plan", "compile.trace", "compile.lower",
             "compile.backend", "data_wait", "h2d", "step", "fence", "collate"]
    spans = [("setup.boot", 50.0, 50.0 + rng.uniform(0, 30))]
    for _ in range(200):
        s = rng.uniform(0.0, 200.0)  # before the boot and past the window too
        spans.append((rng.choice(names), s, s + rng.expovariate(0.2)))
    ctx = ctx_of(spans, 150.0)
    seen = read("setup_seen_pct", ctx)
    assert 0.0 < seen <= 100.0 + 1e-9
    for name in NEW[:-1]:
        assert 0.0 <= read(name, ctx) <= 100.0 + 1e-9  # set-up is 100 s long


def test_the_five_metrics_are_in_the_benchmark_and_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(json.dumps(bench)) < 64 * 1024
    tail = bench["per_layer"][-len(NEW):]
    assert tuple(m["name"] for m in tail) == NEW  # appended, in this order
    for m in tail:
        s = spec(m["name"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert (m["source"], m["layer"], m["moves"]) == ("program_span", LAYER, "setup_s")
        assert m["better"] == ("higher" if m["name"] == "setup_seen_pct" else "lower")
        assert m["unit"] == ("%" if m["name"] == "setup_seen_pct" else "s")
        assert all(s[k] == m[k] for k in m) and len(s["description"]) > 100
    # the accepted launch metric stays as it was
    old = next(m for m in bench["per_layer"] if m["name"] == "compile_s")
    assert old["layer"] == LAYER and spec("compile_s")["reader"] == "program_counter.compile_s"
    for cell in bench["workloads"]:
        listed = {m["name"] for m in harness.load_cell(ROOT, cell["name"])["per_layer"]}
        assert set(NEW) <= listed, cell["name"]
