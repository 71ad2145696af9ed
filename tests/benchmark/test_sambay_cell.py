"""The SambaY family's cell (ISSUE 31): its files load by name, the work
functions agree with hand counts and with the built trainer's leaves, each
new per-layer metric names a reader and tags that exist, and the three
checks that ``test_benchmark.py`` runs for the families it parametrises
over (the plain reference agrees with the program at a tiny size through
the harness's own ``measure``, the planted faults come out not correct,
and so does the control in the precision below).  No chip, no topology.
"""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, metrics  # noqa: E402
from benchmark.work import sambay as work  # noqa: E402
from tests.benchmark import test_benchmark as tb  # noqa: E402
from tests.benchmark.test_benchmark import (  # noqa: E402
    _half_batch, _measure, _unchanged_state, _wrong_feed,
)

CELL = "phi4-mini-flash.train_b2_t4096"
NEW_METRICS = ("ssm_ms.train", "gmu_ms.train", "diff_attn_ms.train",
               "ssm_scan_roofline.train", "flash_diff_roofline.train")
# d 32, 8 x 8 heads on 4 K/V heads, d_inner 64, state 4, rank 2, window 8,
# T 32; the layer pattern and the published indices are the committed file's
TINY_MODEL = dict(
    vocab_size=512, d_model=32, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=64,
    sliding_window=8, ssm_state=4, ssm_dt_rank=2, flash=False,
)
# Limits between what the sound side reads here and what the control and
# the faults read (CPU, 3 seeds a side, PR 31).  Worst leaf's gradient: the
# program in bf16 0.043-0.132, the reference in bf16 0.024-0.133, the fp8
# control 0.52-0.61, half the batch 0.40-1.08, a feed in another order
# 0.44.  Parameters' change: sound 0.041-0.062, the control 0.26-0.34, half
# the batch 0.24-0.36, another order 0.42, a state left unchanged 1.  The
# losses separate nothing here (sound up to 2e-4, the control from 7e-5).
# At 64 tokens and 32 channels a leaf's gradient is a sum of few terms:
# the sound side reads ten times what it reads at the cell's size.
tb.TINY["sambay"] = {
    "model": TINY_MODEL,
    "workload": dict(batch=2, seq_len=32, period_steps=2),
    "data": dict(windows=32),
    "limits": dict(grad_norm_gap=0.25, delta_norm_gap=0.13),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tb.tiny_root(tmp_path_factory.mktemp("sambay"), "sambay")


def published_shapes() -> dict:
    spec = harness.load_cell(ROOT, CELL)
    w = spec["workload"]
    return dict(spec["config"]["model"], batch=w["batch"], seq_len=w["seq_len"])


# ------------------------------------------------------------ the files


def test_the_cell_is_found_by_name_with_its_metrics():
    spec = harness.load_cell(ROOT, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_METRICS) | {"step_mfu.train", "fwd_ms.train", "device_step_ms.train",
                               "device_idle_pct.train", "compile_s"} <= names
    assert not {"flash_roofline.train", "attn_ms.train", "moe_ms.train",
                "flash_mixed_roofline.train", "loader_busy_pct.train"} & names
    assert spec["cell"]["chips"] == 1 and spec["config"]["family"] == "sambay"
    assert {m["name"] for m in spec["end_to_end"]} == {"train_steps_per_s", "setup_s"}
    for other in ("gpt2s.train_b16_t1024", "densenet121.train_b120",
                  "trinity-mini.train_b2_t4096"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in harness.load_cell(ROOT, other)["per_layer"]}
    w = spec["workload"]
    assert (w["batch"], w["seq_len"], w["period_steps"]) == (2, 4096, 5)
    assert w["data"] == {"kind": "zipf_tokens", "windows": 4096, "exponent": 1.0, "shift": 8.0}
    assert {"grad_norm_gap_p90", "grad_norm_gap_global", "delta_norm_gap", "loss_gap_step2",
            "nonfinite_losses", "compiles_in_window"} <= set(w["limits"])


def test_the_configuration_keeps_every_published_width():
    c = harness.load_cell(ROOT, CELL)["config"]
    catalog = {  # the catalog's ``config`` of Phi-4-mini-flash-reasoning
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
        "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
    }
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in catalog.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["layers_published"]) == (5, 32)
    assert c["vocab_size"] * 8 == c["vocab_published"] == 200064
    m = c["model"]
    assert (m["d_model"], m["d_ff"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (
        2560, 10240, 40, 20, 64)
    assert (m["ssm_state"], m["ssm_conv"], m["ssm_expand"], m["ssm_dt_rank"]) == (16, 4, 2, 160)
    assert m["sliding_window"] == 512 and m["norm_eps"] == 1e-5
    assert m["layer_indices"] == c["layers_published_indices"] == [15, 16, 17, 18, 19]
    assert m["layer_types"] == ["sliding_attention", "mamba", "full_attention", "gmu",
                                "cross_attention"]
    for key in ("ssm_sizes", "layout", "head_pairs", "biases", "lam0", "lambda_init",
                "remat", "precision"):
        assert key in c["assumed"], key


# -------------------------------------------------------------- the work


def test_param_count_is_the_issue_s_and_the_built_model_s():
    import jax
    import jax.numpy as jnp

    from benchmark.families.sambay import lm_config
    from benchmark.reference import sambay as ref
    from ddl_tpu.models.transformer import TransformerLM, count_lm_params

    s = published_shapes()
    assert work.param_count(s) == 577_199_232  # ISSUE 31's 577.2 M
    assert work.param_count(s) == sum(math.prod(v) for v in ref._shapes(s).values())
    small = dict(s, **dict(TINY_MODEL, vocab_size=96))
    abs_params = jax.eval_shape(
        lambda: TransformerLM(lm_config(small)).init(
            jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"])
    assert work.param_count(small) == count_lm_params(abs_params)


def test_work_functions_against_hand_counts():
    s = published_shapes()
    tokens, d, d_in, n = 8192, 2560, 5120, 16
    assert work.tokens_per_step(s) == tokens
    sliding = (512 * 513 / 2 + (4096 - 512) * 512) / 4096
    assert [work.visible_keys(s, i) for i in range(5)] == [sliding, 2048.5, 2048.5, 2048.5, 2048.5]
    mlp = 3 * d * 10240
    attn, cross = 2 * d * d + 2 * d * 1280, 2 * d * d
    mamba = 2 * d * d_in + d_in * 192 + 160 * d_in + d_in * d
    gmu = 2 * d * d_in
    per_token = (2 * (5 * mlp + 2 * attn + cross + mamba + gmu + d * 25008)
                 + 6 * 2560 * (sliding + 2 * 2048.5) + 8 * d_in + 7 * d_in * n)
    assert work.forward_flops_per_token(s) == pytest.approx(per_token)
    assert work.train_step_flops(s) == pytest.approx(3 * per_token * tokens)
    assert 29e12 < work.train_step_flops(s) < 31e12
    scan = work.selective_scan_train(s)
    assert scan["calls"] == 2 and scan["flops"] == 21 * d_in * n * tokens
    row, col, fixed = 4 * d_in * tokens, 4 * n * tokens, 4 * (d_in * n + d_in)
    assert scan["bytes"] == 8 * row + 6 * col + 3 * fixed
    flash = work.flash_diff_train(s)
    assert flash["calls"] == 6
    # per pair and visible key 6 * head_dim multiply-adds forward, twice
    # that back: 18 * 64 * 2 FLOPs, 20 pairs, every token
    assert flash["flops"] == pytest.approx(
        18 * 64 * 2 * 20 * tokens * (sliding + 2 * 2048.5))
    qo, kv, stats = 2 * 40 * 4096 * 64 * 2, 2 * 20 * 4096 * 64 * 2, 2 * 40 * 4096 * 4
    assert flash["bytes"] == 3 * (6 * qo + 6 * kv + 3 * stats)


def test_every_new_metric_names_a_reader_and_tags_that_exist():
    import importlib

    from ddl_tpu.train.lm_steps import STEP_PARTS

    bench = tb.bench()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "train_steps_per_s"
        assert {k: spec[k] for k in listed[name]} == listed[name]
        mod, fn = spec["reader"].split(".")
        assert callable(getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn))
        if fn == "scope_ms":
            assert spec["params"]["label"] == "train_step.parts"
            assert set(spec["params"]["tags"]) <= set(STEP_PARTS.values())
        else:
            assert callable(getattr(work, spec["params"]["work"]))
            import re

            kernels = {"ssm_scan_roofline.train": ("ssm_scan_fwd.3", "ssm_scan_bwd.1"),
                       "flash_diff_roofline.train": ("flash_fwd.7", "flash_bwd_dkv.2")}[name]
            assert all(re.search(spec["params"]["match"], k) for k in kernels)


# ------------------------------------------------------ through the harness


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
    from benchmark.reference import common
    from benchmark.reference import sambay as ref

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
