"""The lfm2 family's cell: its files load by name beside the others, the
configuration keeps every published width, the work functions agree with
hand counts and with the built model's leaves, each new per-layer metric
names a reader and tags that exist, and the three checks that
``test_benchmark.py`` runs for the families it parametrises over (the
plain reference agrees with the program at a tiny size through the
harness's own ``measure`` in a CPU rehearsal, the planted faults come out
not correct, and so does the control in the precision below).  No chip,
no topology.
"""

import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, metrics  # noqa: E402
from benchmark.work import lfm2 as work  # noqa: E402
from tests.benchmark import test_benchmark as tb  # noqa: E402
from tests.benchmark.test_benchmark import (  # noqa: E402
    _half_batch, _measure, _unchanged_state, _wrong_feed,
)

CELL = "lfm2-8b.train_b2_t4096"
NEW_METRICS = ("conv_mixer_ms.train", "conv_gating_ms.train", "moe_gmm_1792_roofline.train")
PATTERN = ["conv", "full_attention", "conv", "conv", "conv"]
# d 64, 4 x 16 heads on 2 K/V heads, dense MLP 96, 8 experts 32 wide top-4
# of which a share of 2 is held, 3 taps, T 32; the layer pattern is the
# committed file's
TINY_MODEL = dict(
    vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
    moe_d_ff=32, num_experts=8, experts_held=2, expert_share_index=1, expert_top_k=4,
    flash=False,
)
# Limits between what the sound side reads here and what the control and
# the faults read (CPU, 3 to 6 seeds a side).  Worst leaf's gradient: the
# program in bf16 0.009-0.049 (top-4 choices of 64 tokens that flip on
# near-ties between bf16 and float32), the reference in bf16 0.011-0.023,
# the fp8 control 0.13-0.25, half the batch 0.55-0.77, a feed in another
# order 0.20-0.31.  Parameters' change: sound 0.005-0.010, the control
# 0.022-0.045, half the batch 0.028-0.107, another order 0.035-0.046, a
# state left unchanged 1.
tb.TINY["lfm2"] = {
    "model": TINY_MODEL,
    "workload": dict(batch=2, seq_len=32, period_steps=2),
    "data": dict(windows=32),
    "limits": dict(grad_norm_gap=0.09, delta_norm_gap=0.02),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tb.tiny_root(tmp_path_factory.mktemp("lfm2"), "lfm2")


def metric_spec(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def published_shapes() -> dict:
    spec = harness.load_cell(ROOT, CELL)
    w = spec["workload"]
    return dict(spec["config"]["model"], batch=w["batch"], seq_len=w["seq_len"])


# ------------------------------------------------------------ the files


def test_the_cell_is_found_by_name_with_its_metrics():
    spec = harness.load_cell(ROOT, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"step_mfu.train", "fwd_ms.train", "bwd_ms.train", "update_ms.train",
            "device_step_ms.train", "device_idle_pct.train", "compile_s", "boot_s"} <= names
    assert spec["cell"]["chips"] == 1 and spec["config"]["family"] == "lfm2"
    assert {m["name"] for m in spec["end_to_end"]} == {"train_steps_per_s", "setup_s"}
    b = tb.bench()
    # the new cell and configuration come last in their lists
    assert b["configs"][-1]["name"] == "lfm2-8b-a1b" and b["workloads"][-1]["name"] == CELL
    w = spec["workload"]
    assert (w["batch"], w["seq_len"], w["period_steps"]) == (2, 4096, 5)
    assert w["data"] == {"kind": "zipf_tokens", "windows": 4096, "exponent": 1.0, "shift": 8.0}
    assert w["optimizer"] == {"name": "adam", "learning_rate": 1e-5, "b1": 0.9, "b2": 0.999,
                              "eps": 1e-8}
    assert {"nonfinite_losses", "compiles_in_window"} <= set(w["limits"]) and len(w["limits"]) >= 4


def test_the_configuration_keeps_every_published_width():
    c = harness.load_cell(ROOT, CELL)["config"]
    published = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                 "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv",
                 "full_attention", "conv", "conv"]
    catalog = {  # the catalog's ``config`` of LFM2-8B-A1B
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": published, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536,
    }
    assert c["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers",
                            "num_experts", "vocab_size"]
    for key, value in catalog.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["layers_published"]) == (5, 24)
    assert c["layer_types"] == published[1:6] == PATTERN  # layer 1 and one whole period
    assert (c["num_dense_layers"], c["dense_layers_published"]) == (1, 2)
    assert (c["num_experts"], c["experts_held"], c["experts_published"]) == (8, 8, 32)
    assert c["vocab_size"] * 4 == c["vocab_published"] == 65536
    m = c["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (2048, 32, 8, 64)
    assert (m["d_ff"], m["moe_d_ff"], m["num_experts"], m["experts_held"], m["expert_top_k"]) == (
        7168, 1792, 32, 8, 4)
    assert (m["short_conv"], m["rope_theta"], m["norm_eps"]) == (3, 1000000, 1e-5)
    assert (m["route_scale"], m["route_eps"], m["remat"]) == (1.0, 1e-6, False)
    assert m["layer_types"] == PATTERN and m["num_dense_layers"] == 1
    for key in ("head_dim", "tie_word_embeddings", "intermediate_size", "conv", "attention",
                "norms", "router", "expert_bias", "aux_loss", "remat", "precision", "weights"):
        assert key in c["assumed"], key


# -------------------------------------------------------------- the work


def test_param_count_is_the_issue_s_and_the_built_model_s():
    import jax
    import jax.numpy as jnp

    from benchmark.families.lfm2 import lm_config
    from benchmark.reference import lfm2 as ref
    from ddl_tpu.models.transformer import TransformerLM, count_lm_params

    s = published_shapes()
    assert work.param_count(s) == 507_820_288
    assert work.param_count(s) == sum(math.prod(v) for v in ref._shapes(s).values())
    # by part: the tied embedding, layer 1, layer 2, each of layers 3-5
    layer = {i: 2 * 2048 for i in range(5)}
    layer[0] += 16_783_360 + 44_040_192                # conv mixer, dense MLP
    layer[1] += 10_485_888 + 65_568 + 88_080_384       # attention, router and bias, 8 experts
    for i in (2, 3, 4):
        layer[i] += 16_783_360 + 65_568 + 88_080_384
    assert (layer[0], layer[1], layer[2]) == (60_827_648, 98_635_936, 104_933_408)
    assert 33_554_432 + sum(layer.values()) + 2048 == work.param_count(s)
    # the program's own tree at the configuration's every width: shapes only
    abs_params = jax.eval_shape(
        lambda: TransformerLM(lm_config(s)).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert count_lm_params(abs_params) == work.param_count(s)


def test_work_functions_against_hand_counts():
    s = published_shapes()
    tokens, d = 8192, 2048
    assert work.tokens_per_step(s) == tokens
    assert work.visible_keys(s, 1) == 2048.5
    assert work.local_rows_per_layer(s) == 8192  # 1,024 a held expert
    per_token = (4 * 2 * 4 * d * d                                  # the conv mixers
                 + 2 * (2 * d * 2048 + 2 * d * 512) + 4 * 2048.5 * 2048   # attention
                 + 2 * 3 * d * 7168                                  # the dense MLP
                 + 4 * (2 * d * 32 + 2 * 3 * d * 1792)               # routers, experts
                 + 2 * d * 16384)                                    # the tied head
    assert work.forward_flops_per_token(s) == pytest.approx(per_token)
    assert per_token == 415_764_480
    assert work.train_step_flops(s) == pytest.approx(3 * per_token * tokens)
    assert 10.2e12 < work.train_step_flops(s) < 10.25e12
    gmm = work.expert_matmul_train(s)
    assert gmm["calls"] == 36 and gmm["flops"] == 4 * 9 * 2 * 8192 * d * 1792
    bank, rd, rf = 3 * 8 * d * 1792, 8192 * d * 2, 8192 * 1792 * 2
    assert gmm["bytes"] == 4 * ((2 * bank + 3 * rd + 3 * rf) + (2 * bank + 3 * rd + 3 * rf)
                                + (3 * rd + 3 * rf + 4 * bank))


def test_every_new_metric_names_a_reader_and_tags_that_exist():
    import importlib

    from ddl_tpu.train.lm_steps import STEP_PARTS

    for name in NEW_METRICS:
        spec = metric_spec(name)
        assert spec["name"] == name and spec["workloads"] == [CELL]
        assert spec["moves"] == "train_steps_per_s" and spec["source"] == "device_trace"
        assert tb.NAME.match(name) and tb.UNIT.match(spec["unit"])
        assert spec["better"] == ("higher" if "roofline" in name else "lower")
        mod, fn = spec["reader"].split(".")
        assert callable(getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn))
        if fn == "scope_ms":
            assert spec["params"]["label"] == "train_step.parts"
            assert set(spec["params"]["tags"]) <= set(STEP_PARTS.values())
        else:
            assert callable(getattr(work, spec["params"]["work"]))
            for k in ("moe_gmm_fwd.3", "moe_gmm_dx.1", "moe_gmm_dw.12"):
                assert re.search(spec["params"]["match"], k)
            assert not re.search(spec["params"]["match"], "moe_rows_gather.1")


# ------------------------------------------------------ through the harness


def test_reference_agrees_with_the_program_at_a_tiny_size(tiny):
    """A CPU rehearsal of the cell through ``load_cell`` and the train
    ``Driver``, found by name under a root with nothing but the data
    directories and ``BENCHMARK.json``: correct at a tiny size."""
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
    from benchmark.reference import lfm2 as ref

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
