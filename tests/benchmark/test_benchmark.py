"""The benchmark's own tests: the files are well formed and found by name,
the reductions and the arithmetic are right on hand-built inputs, the
plain references agree with the program at a tiny size, the control and
the planted faults come out as not correct.  No chip, no topology.
"""

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, metrics, trace as tr  # noqa: E402
from benchmark.work import cnn as work_cnn, lm as work_lm  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY = {
    "lm": {
        "model": dict(vocab_size=512, d_model=32, n_layers=2, n_heads=2, head_dim=16,
                      d_ff=64, flash=False),
        "workload": dict(batch=4, seq_len=16, period_steps=2,
                         reference={"row_block": 2}),
        "data": dict(windows=32),
        # the bf16 reference and the program read 0.0018-0.0036 here on the
        # worst leaf's gradient, the fp8 control 0.048-0.10 (5 seeds each)
        "limits": dict(grad_norm_gap=0.012),
    },
    "cnn": {
        # float32 compute: at 16 rows of 32x32 a bf16 BatchNorm stack reads
        # a tenth on its worst leaf, which says nothing about the program
        "model": dict(growth_rate=8, block_config=[2, 2], num_init_features=16,
                      bn_size=2, image_size=32, compute_dtype="float32"),
        "workload": dict(batch=16, period_steps=2),
        "data": dict(num_train=32, num_test=8, image_size=32),
    },
}
# limits for the tiny cells, between what the sound program reads there
# (the LM in bf16 against the f32 reference: about 3e-3; the CNN in f32:
# 1e-4) and what the faults and the fp8 control read (the CNN's control
# 0.45 and more on the worst leaf's gradient; the LM's has its own limit)
TINY_LIMITS = {
    "loss_gap_step1": 0.01, "loss_gap_step2": 0.01, "loss_gap_step3": 0.01,
    "grad_norm_gap": 0.05, "delta_norm_gap": 0.05,
    "nonfinite_losses": 0, "compiles_in_window": 0,
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_root(tmp_path, family: str) -> tuple[str, str]:
    """A copy of the data directories with one of the real cells cut to a
    size the CPU holds; returns (root, cell name)."""
    b = bench()
    root = str(tmp_path / "root")
    pkg = os.path.join(root, "benchmark")
    os.makedirs(pkg)
    for d in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d), os.path.join(pkg, d))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), pkg)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for c in b["configs"]:
        path = os.path.join(root, c["file"])
        cfg = json.load(open(path))
        if cfg["family"] != family:
            continue
        cfg["model"].update(TINY[family]["model"])
        json.dump(cfg, open(path, "w"))
        cell = next(w["name"] for w in b["workloads"] if w["config"] == c["name"])
        wpath = os.path.join(pkg, "workloads", f"{cell}.json")
        w = json.load(open(wpath))
        w.update(TINY[family]["workload"])
        w["data"].update(TINY[family]["data"])
        w["limits"] = dict(TINY_LIMITS, **TINY[family].get("limits", {}))
        json.dump(w, open(wpath, "w"))
        return root, cell
    raise AssertionError(f"no configuration of family {family}")


# ------------------------------------------------------------ the files


def test_benchmark_json_is_well_formed():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "benchmark", "workloads", w["name"] + ".json"))
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "reference", cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(ROOT, "benchmark", "work", cfg["family"] + ".py"))
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        spec = json.load(open(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".json")))
        for k in ("unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        mod, fn = spec["reader"].split(".")
        assert hasattr(__import__(f"benchmark.readers.{mod}", fromlist=[fn]), fn)
        for cell in m.get("workloads", cells):
            mover = e2e[m["moves"]]
            assert "workloads" not in mover or cell in mover["workloads"]
    for name in cells:  # every cell: setup_s, another end-to-end, a per-layer
        spec = harness.load_cell(ROOT, name)
        assert len(spec["end_to_end"]) >= 2 and len(spec["per_layer"]) >= 1


def test_new_cell_metric_and_configuration_are_found_by_name(tmp_path):
    """Files dropped in plus one entry each: nothing that was there is edited."""
    root, _ = tiny_root(tmp_path, "lm")
    pkg = os.path.join(root, "benchmark")
    before = {p: open(os.path.join(pkg, d, p)).read()
              for d in ("configs", "workloads", "layer_metrics")
              for p in os.listdir(os.path.join(pkg, d))}
    json.dump({"name": "new-model", "family": "lm", "source": "a paper", "reduced": [],
               "model": {"d_model": 8}}, open(os.path.join(pkg, "configs", "new-model.json"), "w"))
    json.dump({"name": "new.cell", "driver": "train", "batch": 2, "why": "x"},
              open(os.path.join(pkg, "workloads", "new.cell.json"), "w"))
    json.dump({"name": "new_metric.train", "unit": "ms", "better": "lower",
               "source": "device_trace", "layer": "device", "moves": "train_steps_per_s",
               "reader": "device_trace.device_step_ms", "params": {}},
              open(os.path.join(pkg, "layer_metrics", "new_metric.train.json"), "w"))
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["configs"].append({"name": "new-model", "source": "a paper",
                         "file": "benchmark/configs/new-model.json", "reduced": [], "why": "x"})
    b["workloads"].append({"name": "new.cell", "config": "new-model", "traffic": "cell",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric.train", "unit": "ms", "better": "lower",
                           "source": "device_trace", "layer": "device",
                           "moves": "train_steps_per_s", "workloads": ["new.cell"]})
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    spec = harness.load_cell(root, "new.cell")
    assert spec["config"]["model"] == {"d_model": 8} and spec["workload"]["batch"] == 2
    names = [m["name"] for m in spec["per_layer"]]
    assert "new_metric.train" in names and "flash_roofline.train" not in names
    old = harness.load_cell(root, b["workloads"][0]["name"])
    assert "new_metric.train" not in [m["name"] for m in old["per_layer"]]
    for p, text in before.items():
        d = next(d for d in ("configs", "workloads", "layer_metrics")
                 if os.path.exists(os.path.join(pkg, d, p)))
        assert open(os.path.join(pkg, d, p)).read() == text


# ------------------------------------------------- the trace reduction

OPS = [  # (name, start, end) on one device: two steps with a gap between
    ("%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 1.0, 1.4),
    ("%custom-call.7 = bf16[8] custom-call(%q)", 1.4, 1.5),
    ("%fusion.2 = bf16[8] fusion(%a), kind=kOutput", 1.45, 1.9),  # overlaps
    ("%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 3.0, 3.4),
    ("%custom-call.7 = bf16[8] custom-call(%q)", 3.4, 3.5),
]
SPANS = [("data_wait", 0.9, 1.0), ("step", 1.0, 1.1), ("fence", 1.1, 2.5),
         ("data_wait", 2.5, 2.9), ("step", 2.9, 3.0), ("fence", 3.0, 4.0)]


def hand_trace():
    return tr.Trace(ops={"/device:TPU:0": OPS}, modules={}, anchor=(1.0, 4.0), anchor_wall=101.0)


@pytest.mark.parametrize("what", ["busy_union", "idle_share", "gaps", "kernel_by_name",
                                  "clock", "top_ops"])
def test_trace_reduction_on_a_hand_built_trace(what):
    t = hand_trace()
    if what == "busy_union":
        assert tr.busy_union([(s, e) for _, s, e in OPS]) == pytest.approx(0.9 + 0.5)
        assert t.busy_s() == pytest.approx(1.4) and t.window_s() == pytest.approx(3.0)
    elif what == "idle_share":
        from benchmark.readers import device_trace as rd, host_clock as rh

        # 2 steps traced, 0.7 s busy each; the untraced window ran 1 step/s
        ctx = {"trace": t, "traced": {"steps": 2}, "window": {"steps": 10, "elapsed": 10.0}}
        assert rd.device_step_ms(ctx, {}) == pytest.approx(700.0)
        assert rh.device_idle_pct(ctx, {}) == pytest.approx(100 * (1 - 0.7 * 1.0))
        assert rh.device_idle_pct({"trace": tr.Trace(ops={}, modules={})}, {}) is None
        ctx.update(work=work_lm, chips=1, peak={"bf16_flops_per_s": 1e12},
                   shapes=dict(vocab_size=64, d_model=8, n_layers=1, n_heads=1, head_dim=8,
                               d_ff=16, batch=1, seq_len=4))
        # the device's own share of the peak: work over busy time, so a host
        # stall (a slower untraced window) leaves it where it was
        mfu = 100 * work_lm.train_step_flops(ctx["shapes"]) / 0.7 / 1e12
        assert rd.step_mfu(ctx, {}) == pytest.approx(mfu)
        ctx["window"] = {"steps": 10, "elapsed": 20.0}
        assert rd.step_mfu(ctx, {}) == pytest.approx(mfu)
        assert rh.device_idle_pct(ctx, {}) == pytest.approx(100 * (1 - 0.7 * 0.5))
    elif what == "gaps":
        idle = tr.gaps(t.intervals("/device:TPU:0"), 1.0, 4.0)
        assert idle == [pytest.approx((1.9, 3.0)), pytest.approx((3.5, 4.0))]
        totals, per_gap = tr.attribute_gaps(idle, SPANS)
        assert totals["fence"] == pytest.approx(0.6 + 0.5)
        assert totals["data_wait"] == pytest.approx(0.4) and totals["step"] == pytest.approx(0.1)
        assert per_gap[0][0] == "fence" and per_gap[0][1] == pytest.approx(1.1)
        assert tr.attribute_gaps([(10.0, 11.0)], SPANS)[0] == {"untraced": pytest.approx(1.0)}
    elif what == "kernel_by_name":
        got = tr.match_events(t.all_ops(), "custom-call")
        assert len(got) == 2 and sum(e - s for _, s, e in got) == pytest.approx(0.2)
        from benchmark.readers import device_trace as rd

        ctx = {"trace": t, "traced": {"steps": 2}, "window": {"steps": 2, "elapsed": 3.0},
               "work": work_lm,
               "shapes": dict(batch=1, n_heads=1, head_dim=64, seq_len=128, n_layers=1),
               "peak": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}, "notes": {}}
        need = work_lm.flash_attention_train(ctx["shapes"])
        share = rd.kernel_roofline(ctx, {"match": "custom-call", "work": "flash_attention_train"})
        assert share == pytest.approx(100 * max(need["flops"], need["bytes"]) / 1e9 / 0.1)
        assert rd.kernel_roofline(ctx, {"match": "no-such-op", "work": "flash_attention_train"}) is None
    elif what == "clock":
        assert t.to_trace_clock(101.5) == pytest.approx(1.5)
        from benchmark.readers import program_span as rd

        wall = [(n, s + 100.0, e + 100.0) for n, s, e in SPANS]
        ctx = {"trace": t, "traced": {"steps": 2}, "spans": wall,
               "window": {"steps": 2, "wall_start": 100.9, "wall_end": 104.0}}
        # a step owes 0.7 s of device work.  Fence 1 starts 0.2 s into its
        # loop: 0.5 s still owed of its 1.4 s; fence 2 starts 0.5 s in: 0.2
        # owed of 1.0
        assert rd.fence_after_idle_pct(ctx, {}) == pytest.approx(100 * (0.9 + 0.8) / 3.1)
        assert rd.phase_share_pct(ctx, {"phases": ["data_wait", "h2d"]}) == pytest.approx(100 * 0.5 / 3.1)
        assert rd.phase_share_pct(dict(ctx, spans=[]), {"phases": ["h2d"]}) is None
    else:
        top = dict(tr.top_ops(t.all_ops()))
        assert top["fusion:Loop"] == pytest.approx(0.8) and top["custom-call"] == pytest.approx(0.2)


def test_a_stall_moves_train_steps_per_s():
    steady = [(i * 1.0, i * 1.0 + 1.0, 10) for i in range(10)]
    assert metrics.steps_per_s(steady) == pytest.approx(10.0)
    stalled = steady[:5] + [(s + 2.0, e + 2.0, n) for s, e, n in steady[5:]]
    assert metrics.steps_per_s(stalled) == pytest.approx(100 / 12.0)
    slow_period = steady[:9] + [(9.0, 13.0, 10)]
    assert metrics.steps_per_s(slow_period) == pytest.approx(100 / 13.0)
    with pytest.raises(ValueError):
        metrics.steps_per_s([])


def test_judge_holds_every_limit_of_the_file_against_its_number():
    numbers = {"a": 0.5, "b": 2.0, "a_reading": 9.0}
    ok, table = metrics.judge(numbers, {"a": 1.0, "b": 3.0})
    assert ok is True and set(table) == {"a", "b"}  # no limit: read, not compared
    assert metrics.judge(numbers, {"a": 1.0, "b": 1.0})[0] is False
    assert metrics.judge(numbers, {"a": 1.0, "c": 1.0})[0] is False  # c: no number
    assert metrics.judge(numbers, {})[0] is False  # no limits: not proven
    assert metrics.judge({"a": float("nan")}, {"a": 1.0})[0] is False


def test_run_on_the_cpu_exits_nonzero_without_a_device_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(harness.REHEARSE_ENV, None)
    cell = bench()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


# ------------------------------------------------------ work and peaks


@pytest.mark.parametrize("which", ["lm", "lm_flash", "cnn", "peaks"])
def test_required_work_against_hand_computed_values(which):
    if which == "lm":
        s = dict(vocab_size=50304, d_model=768, n_layers=12, n_heads=12, head_dim=64,
                 d_ff=3072, batch=16, seq_len=1024)
        per_tok = 12 * (2 * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * 1024 * 768) + 2 * 768 * 50304
        assert work_lm.forward_flops_per_token(s) == pytest.approx(per_tok)
        assert work_lm.train_step_flops(s) == pytest.approx(3 * per_tok * 16384)
        assert 13.0e12 < work_lm.train_step_flops(s) < 13.2e12  # PERF_HISTORY: 13.13
        assert work_lm.param_count(s) == 2 * 50304 * 768 + 12 * (4 * 768 * 768 + 2 * 768 * 3072 + 1536) + 768
    elif which == "lm_flash":
        s = dict(batch=2, n_heads=3, head_dim=8, seq_len=16, n_layers=5, compute_dtype="bfloat16")
        one = 2 * 16 * 8 * 8 * 2 * 3  # 2*T*(T/2)*dh per head, all heads and rows
        need = work_lm.flash_attention_train(s)
        assert need["flops"] == pytest.approx(5 * 6 * one) and need["calls"] == 15
        tensor, stats = 2 * 3 * 16 * 8 * 2, 2 * 3 * 16 * 4
        assert need["bytes"] == 5 * (12 * tensor + 3 * stats)
    elif which == "cnn":
        s = dict(growth_rate=32, block_config=[6, 12, 24, 16], num_init_features=64,
                 bn_size=4, num_classes=5, image_size=224, batch=30)
        # the published figure for DenseNet-121 is 2.87 G multiply-adds an
        # image at 224 (with the 1000-way head; 5 classes take 2 M off)
        assert 2 * 2.80e9 < work_cnn.forward_flops_per_image(s) < 2 * 2.90e9
        # executed FLOPs of the program's bs-30 step by XLA's count: 0.51
        # TFLOP (bench.py, PERF.md PR 22); required leaves out BatchNorm,
        # pooling and Adam, which XLA counts, so it is a little lower
        assert 0.95 * 0.51e12 < work_cnn.train_step_flops(s) < 1.02 * 0.51e12
        assert 6.9e6 < work_cnn.param_count(s) < 7.0e6  # 7.98 M with ImageNet's head
    else:
        peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
        assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
        with pytest.raises(SystemExit):
            harness._peaks(ROOT, "benchmark", "TPU v9")


# ------------------- the references, the control and the planted faults


def _measure(root, cell, after_setup=None, seed=2147483659, trace=False):
    import time

    spec = harness.load_cell(root, cell)
    return harness.measure(
        spec, seed=seed, seconds=0.2, trace=trace, root=root, on_chip=False,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t_start=time.perf_counter(), log=lambda *a: None, after_setup=after_setup,
    )


def _unchanged_state(driver):
    """The step returns its state as it got it (the loss still comes out)."""
    import jax
    import jax.numpy as jnp

    t = driver.cell.trainer
    fns = getattr(t, "fns", None) or t.step_fns
    sound = fns.train

    def broken(state, *batch):
        keep = jax.tree.map(jnp.copy, state)
        out = sound(state, *batch)
        return (keep, *out[1:])

    _swap_train(t, broken)


def _half_batch(driver):
    """Half of the batch left out, the mean taken over the rest."""
    t = driver.cell.trainer
    fns = getattr(t, "fns", None) or t.step_fns
    sound = fns.train

    def broken(state, a, b):
        n = a.shape[0] // 2
        out = sound(state, a[:n], b[:n])
        if len(out) == 3:  # the CNN step also returns its predictions
            import jax.numpy as jnp

            out = (out[0], out[1], jnp.concatenate([out[2], out[2]]))
        return out

    _swap_train(t, broken)


def _wrong_feed(driver):
    """The feed reads its rows in another order than it documents."""
    t = driver.cell.trainer
    sampler = t._batches.sampler if hasattr(t, "_batches") else t.train_loader.sampler
    sampler.seed += 1


def _swap_train(trainer, fn):
    if hasattr(trainer, "fns"):
        trainer.fns = trainer.fns._replace(train=fn)
    else:
        trainer.step_fns = trainer.step_fns._replace(train=fn)


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    return {f: tiny_root(tmp_path_factory.mktemp(f), f) for f in ("lm", "cnn")}


@pytest.mark.parametrize("family", ["lm", "cnn"])
def test_reference_agrees_with_the_program_at_a_tiny_size(roots, family):
    root, cell = roots[family]
    r = _measure(root, cell, trace=(family == "lm"))
    assert r["correct"] is True, r["compared"]
    for name, row in r["compared"].items():
        assert math.isfinite(row["value"]) and row["value"] <= row["limit"], (name, row)
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) >= ({"compile_s"} if family == "lm" else {"train_steps_per_s", "setup_s"})


@pytest.mark.parametrize("family", ["lm", "cnn"])
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch, _wrong_feed])
def test_a_planted_fault_comes_out_not_correct(roots, family, fault):
    root, cell = roots[family]
    r = _measure(root, cell, after_setup=fault)
    assert r["correct"] is False
    failed = [n for n, row in r["compared"].items() if not row["value"] <= row["limit"]]
    assert failed and set(failed) <= {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
                                      "grad_norm_gap", "delta_norm_gap"}
    if fault is _unchanged_state:
        assert r["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("family", ["lm", "cnn"])
def test_the_control_in_the_precision_below_comes_out_not_correct(roots, family):
    """The reference put in the program's place, computed in fp8 (the
    nearest precision below the stated bf16), against itself in f32: it
    has to fail the cell's limits, which the same reference in the cell's
    own precision passes."""
    import importlib

    import jax
    import numpy as np

    from benchmark import traffic
    from benchmark.reference import common

    root, cell = roots[family]
    spec = harness.load_cell(root, cell)
    model, w = spec["config"]["model"], spec["workload"]
    ref = importlib.import_module(f"benchmark.reference.{family}")
    params = ref.init_params(jax.random.key(7), model)
    if family == "lm":
        toks = traffic.generate(w["data"], 7, vocab_size=model["vocab_size"], seq_len=w["seq_len"])
        rows = toks[: 12 * w["seq_len"] + 1]
        batches = []
        for i in range(3):
            blk = np.stack([rows[(4 * i + r) * w["seq_len"]:][: w["seq_len"] + 1] for r in range(4)])
            batches.append((blk[:, :-1].astype(np.int32), blk[:, 1:].astype(np.int32)))
    else:
        images, labels = traffic.generate(w["data"], 7, split="train")
        n = w["batch"]
        batches = [(images[(i * n // 2):(i * n // 2) + n],
                    labels[(i * n // 2):(i * n // 2) + n].astype(np.int32)) for i in range(3)]
    run = lambda precision: metrics.training_numbers(  # noqa: E731
        common.three_steps(ref, model, w["optimizer"], params, batches, precision=precision),
        f32)["numbers"]
    f32 = common.three_steps(ref, model, w["optimizer"], params, batches, precision="f32")
    # against the tiny cell's own limits its own precision passes and the
    # control fails, as calibrate.py judges both on the chip at the real size
    sound = run({"bfloat16": "bf16", "float32": "f32"}[model["compute_dtype"]])
    control = run(spec["config"]["control_precision"])
    limits = {k: v for k, v in w["limits"].items() if k in sound}
    assert len(limits) >= 5 and metrics.judge(sound, limits)[0] is True, sound
    ok, table = metrics.judge(control, limits)
    assert ok is False and any(not r["value"] <= r["limit"] for r in table.values()), table
