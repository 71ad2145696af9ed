"""The afmoe family's cell (ISSUE 27), the three checks that
``test_benchmark.py`` runs for the families it parametrises over: the
plain reference agrees with the program at a tiny size through the
harness's own ``measure``, the planted faults come out not correct, and
so does the control in the precision below.  No chip, no topology.
"""

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, metrics  # noqa: E402
from tests.benchmark import test_benchmark as tb  # noqa: E402
from tests.benchmark.test_benchmark import (  # noqa: E402
    _half_batch, _measure, _unchanged_state, _wrong_feed,
)

CELL = "trinity-mini.train_b2_t4096"
# d 64, 4 x 16 heads, 2 K/V heads, 8 experts top-2 of which a share of 2 is
# held, 1 shared, window 8, T 32; the layer pattern is the committed file's
TINY_MODEL = dict(
    vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=192,
    moe_d_ff=32, num_experts=8, experts_held=2, expert_share_index=1, expert_top_k=2,
    sliding_window=8, flash=False,
)
# Limits between what the sound side reads here and what the control and
# the faults read (CPU, 6 seeds each and the control test's own, PR 27).  Worst leaf's gradient: the
# program in bf16 0.013-0.058, the reference in bf16 0.008-0.112, the fp8
# control 0.150-0.352, half the batch 0.52-0.79.  Parameters' change: sound
# 0.006-0.024, half the batch 0.20-0.29, a state left unchanged 1 (the
# control's 0.017-0.037 does not separate here, nor do the losses: sound
# up to 0.004, the control up to 0.010).  Top-2 choices that flip on
# near-ties between bf16 and float32 are in the sound readings: at 64
# tokens one flipped choice is 1.6% of a layer's rows, so the worst leaf
# reads far higher here than at the cell's 8192 tokens.
tb.TINY["afmoe"] = {
    "model": TINY_MODEL,
    "workload": dict(batch=2, seq_len=32, period_steps=2),
    "data": dict(windows=32),
    "limits": dict(grad_norm_gap=0.13, delta_norm_gap=0.12),
}


def tiny_afmoe_root(tmp_path):
    """``test_benchmark.tiny_root`` for the afmoe family: a copy of the
    data directories with its cell cut to a size the CPU holds."""
    return tb.tiny_root(tmp_path, "afmoe")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_afmoe_root(tmp_path_factory.mktemp("afmoe"))


def test_the_cell_is_found_by_name_with_its_metrics():
    spec = harness.load_cell(ROOT, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"moe_ms.train", "moe_shuffle_ms.train", "attn_ms.train", "moe_gmm_roofline.train",
            "flash_mixed_roofline.train", "step_mfu.train", "fwd_ms.train",
            "device_idle_pct.train"} <= names
    assert "flash_roofline.train" not in names and "loader_busy_pct.train" not in names
    assert spec["cell"]["chips"] == 1 and spec["config"]["family"] == "afmoe"
    assert {m["name"] for m in spec["end_to_end"]} == {"train_steps_per_s", "setup_s"}
    for other in ("gpt2s.train_b16_t1024", "densenet121.train_b120"):
        assert not {"moe_ms.train", "attn_ms.train"} & {
            m["name"] for m in harness.load_cell(ROOT, other)["per_layer"]}


def test_reference_agrees_with_the_program_at_a_tiny_size(tiny):
    root, cell = tiny
    r = _measure(root, cell, trace=True)
    assert r["correct"] is True, r["compared"]
    for name, row in r["compared"].items():
        assert math.isfinite(row["value"]) and row["value"] <= row["limit"], (name, row)
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert "compile_s" in r["metrics"]
    # no chip: nothing read from a device trace, under any name
    assert not any(k.endswith("_ms.train") or "roofline" in k or "mfu" in k for k in r["metrics"])


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch, _wrong_feed])
def test_a_planted_fault_comes_out_not_correct(tiny, fault):
    root, cell = tiny
    r = _measure(root, cell, after_setup=fault)
    assert r["correct"] is False
    failed = [n for n, row in r["compared"].items() if not row["value"] <= row["limit"]]
    assert failed and set(failed) <= {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
                                      "grad_norm_gap", "delta_norm_gap"}, r["compared"]
    if fault is _unchanged_state:
        assert r["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_the_control_in_the_precision_below_comes_out_not_correct(tiny):
    """The reference in fp8 against itself in float32 fails the tiny
    cell's limits, which the same reference in bf16 passes."""
    import jax
    import numpy as np

    from benchmark import traffic
    from benchmark.reference import afmoe as ref
    from benchmark.reference import common

    root, cell = tiny
    spec = harness.load_cell(root, cell)
    model, w = spec["config"]["model"], spec["workload"]
    t, n = w["seq_len"], w["batch"]
    params = ref.init_params(jax.random.key(7), model)
    toks = traffic.generate(w["data"], 7, vocab_size=model["vocab_size"], seq_len=t)
    rows = np.stack([toks[r * t: r * t + t + 1] for r in range(3 * n)]).astype(np.int32)
    batches = [(rows[i * n:(i + 1) * n, :-1], rows[i * n:(i + 1) * n, 1:]) for i in range(3)]
    run = lambda precision: common.three_steps(  # noqa: E731
        ref, model, w["optimizer"], params, batches, precision=precision)
    f32 = run("f32")
    sound = metrics.training_numbers(run("bf16"), f32)["numbers"]
    control = metrics.training_numbers(run(spec["config"]["control_precision"]), f32)["numbers"]
    limits = {k: v for k, v in w["limits"].items() if k in sound}
    assert len(limits) >= 5 and metrics.judge(sound, limits)[0] is True, sound
    ok, table = metrics.judge(control, limits)
    assert ok is False and any(not r["value"] <= r["limit"] for r in table.values()), table
