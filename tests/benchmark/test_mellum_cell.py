"""The mellum family's cell (ISSUE 33): its files load by name, the
configuration keeps every published width, the work functions agree with
hand counts and with the built model's leaves, each new per-layer metric
names a reader and tags that exist, and the three checks that
``test_benchmark.py`` runs for the families it parametrises over (the
plain reference agrees with the program at a tiny size through the
harness's own ``measure``, the planted faults come out not correct, and so
does the control in the precision below).  No chip, no topology.
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
from benchmark.work import mellum as work  # noqa: E402
from tests.benchmark import test_benchmark as tb  # noqa: E402
from tests.benchmark.test_benchmark import (  # noqa: E402
    _half_batch, _measure, _unchanged_state, _wrong_feed,
)

CELL = "mellum2.train_b2_t4096"
NEW_METRICS = ("sparse_mlp_ms.train", "sparse_route_ms.train", "rope_attn_ms.train",
               "moe_gmm_128_roofline.train", "flash_w1024_roofline.train")
# d 64, 4 x 16 heads, 2 K/V heads, 8 experts top-2 of which a share of 2 is
# held, window 8, T 32, YaRN factor 4 over 16 positions; the layer pattern
# is the committed file's
TINY_MODEL = dict(
    vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    moe_d_ff=32, num_experts=8, experts_held=2, expert_share_index=1, expert_top_k=2,
    sliding_window=8, flash=False,
    rope={"sliding_attention": {"theta": 10000},
          "full_attention": {"theta": 10000, "factor": 4, "original": 16, "beta_fast": 2,
                             "beta_slow": 0.5, "attention_factor": 1.1386}},
)
# Limits between what the sound side reads here and what the control and
# the faults read (CPU, 3 seeds a side, PR 33; the harness's own tiny
# limits hold).  Worst leaf's gradient: the program in bf16 0.021-0.029,
# the reference in bf16 0.002-0.003, the fp8 control 0.046-0.217 (0.094 on
# the control test's seed), half the batch 0.43-0.71, a feed in another
# order 0.28-0.48.  Parameters' change: sound 0.004-0.010, half the batch
# 0.22-0.25, a state left unchanged 1 (another order 0.050-0.097 and the
# control's 0.011-0.030 do not separate here, nor do the losses).
tb.TINY["mellum"] = {
    "model": TINY_MODEL,
    "workload": dict(batch=2, seq_len=32, period_steps=2),
    "data": dict(windows=32),
    "limits": dict(grad_norm_gap=0.05, delta_norm_gap=0.05),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tb.tiny_root(tmp_path_factory.mktemp("mellum"), "mellum")


def published_shapes() -> dict:
    spec = harness.load_cell(ROOT, CELL)
    w = spec["workload"]
    return dict(spec["config"]["model"], batch=w["batch"], seq_len=w["seq_len"])


# ------------------------------------------------------------ the files


def test_the_cell_is_found_by_name_with_its_metrics():
    spec = harness.load_cell(ROOT, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_METRICS) | {"step_mfu.train", "fwd_ms.train", "bwd_ms.train",
                               "update_ms.train", "device_step_ms.train",
                               "device_idle_pct.train", "compile_s"} <= names
    assert not {"flash_roofline.train", "attn_ms.train", "moe_ms.train", "moe_gmm_roofline.train",
                "flash_mixed_roofline.train", "loader_busy_pct.train"} & names
    assert spec["cell"]["chips"] == 1 and spec["config"]["family"] == "mellum"
    assert {m["name"] for m in spec["end_to_end"]} == {"train_steps_per_s", "setup_s"}
    for other in ("gpt2s.train_b16_t1024", "densenet121.train_b120",
                  "trinity-mini.train_b2_t4096", "phi4-mini-flash.train_b2_t4096"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in harness.load_cell(ROOT, other)["per_layer"]}
    w = spec["workload"]
    assert (w["batch"], w["seq_len"], w["period_steps"]) == (2, 4096, 5)
    assert w["data"] == {"kind": "zipf_tokens", "windows": 4096, "exponent": 1.0, "shift": 8.0}
    assert w["optimizer"] == {"name": "adam", "learning_rate": 1e-5, "b1": 0.9, "b2": 0.999,
                              "eps": 1e-8}
    assert {"nonfinite_losses", "compiles_in_window"} <= set(w["limits"]) and len(w["limits"]) >= 4


def test_the_configuration_keeps_every_published_width():
    c = harness.load_cell(ROOT, CELL)["config"]
    pattern = ["sliding_attention"] * 3 + ["full_attention"]
    catalog = {"config": {  # the catalog's ``config`` of Mellum2-12B-A2.5B-Instruct
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 7168, "layer_types": pattern * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
        "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
        "use_sliding_window": True,
    }}
    assert c["source"] == ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct"
                           "/blob/main/config.json")
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                            "num_experts", "vocab_size"]
    for key, value in catalog["config"].items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["layers_published"]) == (4, 28)
    assert c["layer_types"] == catalog["config"]["layer_types"][:4]   # one whole period
    assert c["mlp_layer_types"] == ["sparse"] * 4
    assert (c["num_experts"], c["experts_held"], c["experts_published"]) == (16, 16, 64)
    assert c["vocab_size"] * 8 == c["vocab_published"] == 98304
    m = c["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (2304, 32, 4, 128)
    assert (m["moe_d_ff"], m["num_experts"], m["experts_held"], m["expert_top_k"]) == (
        896, 64, 16, 8)
    assert m["sliding_window"] == 1024 and m["norm_eps"] == 1e-6 and m["remat"] is False
    full = catalog["config"]["rope_parameters"]["full_attention"]
    assert m["rope"]["sliding_attention"] == {"theta": 500000}
    assert m["rope"]["full_attention"] == {
        "theta": full["rope_theta"], "factor": full["factor"],
        "original": full["original_max_position_embeddings"], "beta_fast": full["beta_fast"],
        "beta_slow": full["beta_slow"], "attention_factor": full["attention_factor"]}
    for key in ("qk_norm", "norms", "router", "aux_loss", "mtp_head", "max_position_embeddings",
                "rope", "remat", "precision"):
        assert key in c["assumed"], key


# -------------------------------------------------------------- the work


def test_param_count_is_the_issue_s_and_the_built_model_s():
    import jax
    import jax.numpy as jnp

    from benchmark.families.mellum import lm_config
    from benchmark.reference import mellum as ref
    from ddl_tpu.models.transformer import TransformerLM, count_lm_params

    s = published_shapes()
    assert work.param_count(s) == 538_531_072  # ISSUE 33's count
    assert work.param_count(s) == sum(math.prod(v) for v in ref._shapes(s).values())
    # a layer: attention with its norms 21,238,528, router 147,456, 16 experts 99,090,432
    assert (work.param_count(dict(s, n_layers=1)) - work.param_count(dict(s, n_layers=0))
            == 21_238_528 + 147_456 + 99_090_432 == 120_476_416)
    small = dict(s, **dict(TINY_MODEL, vocab_size=96))
    abs_params = jax.eval_shape(
        lambda: TransformerLM(lm_config(small)).init(
            jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"])
    assert work.param_count(small) == count_lm_params(abs_params)


def test_work_functions_against_hand_counts():
    s = published_shapes()
    tokens, d = 8192, 2304
    assert work.tokens_per_step(s) == tokens
    sliding = (1024 * 1025 / 2 + (4096 - 1024) * 1024) / 4096
    assert sliding == 896.125
    assert [work.visible_keys(s, i) for i in range(4)] == [sliding] * 3 + [2048.5]
    assert work.local_rows_per_layer(s) == 16384  # 1,024 a held expert
    proj = 2 * d * 4096 + 2 * d * 512
    per_token = (2 * (4 * proj + d * 12288 + 4 * d * 64 + 4 * 3 * d * 896 * 2)
                 + 4 * 4096 * (3 * sliding + 2048.5))
    assert work.forward_flops_per_token(s) == pytest.approx(per_token)
    assert work.train_step_flops(s) == pytest.approx(3 * per_token * tokens)
    assert 9.9e12 < work.train_step_flops(s) < 10.0e12     # ISSUE 33: 9.9 TFLOP
    gmm = work.expert_matmul_train(s)
    assert gmm["calls"] == 36 and gmm["flops"] == 4 * 9 * 2 * 16384 * d * 896
    assert gmm["flops"] / work.train_step_flops(s) == pytest.approx(0.246, abs=0.002)
    bank, rd, rf = 3 * 16 * d * 896, 16384 * d * 2, 16384 * 896 * 2
    assert gmm["bytes"] == 4 * ((2 * bank + 3 * rd + 3 * rf) + (2 * bank + 3 * rd + 3 * rf)
                                + (3 * rd + 3 * rf + 4 * bank))
    flash = work.flash_attention_train(s)
    assert flash["calls"] == 12
    assert flash["flops"] == pytest.approx(
        6 * 2 * 128 * 4096 * (3 * sliding + 2048.5) * 2 * 32)
    assert 1.90e12 < flash["flops"] < 1.92e12
    qo, kv, stats = 2 * 32 * 4096 * 128 * 2, 2 * 4 * 4096 * 128 * 2, 2 * 32 * 4096 * 4
    assert flash["bytes"] == 4 * (6 * qo + 6 * kv + 3 * stats)


def test_every_new_metric_names_a_reader_and_tags_that_exist():
    import importlib

    from ddl_tpu.train.lm_steps import STEP_PARTS

    listed = {m["name"]: m for m in tb.bench()["per_layer"]}
    kernels = {"moe_gmm_128_roofline.train": ("moe_gmm_fwd.3", "moe_gmm_dx.1", "moe_gmm_dw.12"),
               "flash_w1024_roofline.train": ("flash_fwd.7", "flash_bwd_dkv.2")}
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
            assert "moe/shared" not in spec["params"]["tags"]  # no op carries it here
        else:
            assert callable(getattr(work, spec["params"]["work"]))
            assert all(re.search(spec["params"]["match"], k) for k in kernels[name])
            assert not re.search(spec["params"]["match"], "moe_rows_gather.1")


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


def test_a_dropped_row_is_an_error_of_the_cell(tiny, monkeypatch):
    from benchmark.families import mellum as family

    root, cell = tiny
    spec = harness.load_cell(root, cell)
    built = family.build(spec["config"], spec["workload"], 5, str(root))
    monkeypatch.setattr(built.trainer, "run_period",
                        lambda period, guard=None: ({"loss": 1.0, "moe_rows_dropped": 3.0}, 1))
    with pytest.raises(RuntimeError, match="dropped 3.0 rows"):
        built.run_period(0)


def test_the_control_in_the_precision_below_comes_out_not_correct(tiny):
    """The reference in fp8 against itself in float32 fails the tiny
    cell's limits, which the same reference in bf16 passes."""
    import jax
    import numpy as np

    from benchmark import traffic
    from benchmark.reference import common
    from benchmark.reference import mellum as ref

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
