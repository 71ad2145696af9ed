"""The deepseek_v3 family's cell: its files load by name beside the others,
the configuration keeps every published width and names every key it
changed, the work functions agree with hand counts and with the built
model's leaves, each new per-layer metric names a reader and tags that
exist, and the three checks that ``test_benchmark.py`` runs for the
families it parametrises over (the plain reference agrees with the
program at a tiny size through the harness's own ``measure`` in a CPU
rehearsal, the planted faults come out not correct, and so does the
control in the precision below).  No chip, no topology.
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
from benchmark.work import deepseek_v3 as work  # noqa: E402
from tests.benchmark import test_benchmark as tb  # noqa: E402
from tests.benchmark.test_benchmark import (  # noqa: E402
    _half_batch, _measure, _unchanged_state, _wrong_feed,
)

CELL = "kanana2.train_b2_t4096"
CONFIG = "kanana-2-30b-a3b"
NEW_METRICS = ("mla_ms.train", "mla_latent_ms.train", "flash_mla_roofline.train")
# d 64, 4 heads with Q/K 24 wide (16 + a rotary 8) and V 16, a latent 32
# wide, dense MLP 96, 16 experts 32 wide top-6 of which a share of 2 is
# held, 2 shared, T 32; the five layers are the committed file's
TINY_MODEL = dict(
    vocab_size=512, d_model=64, n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=96, moe_d_ff=32, num_experts=16,
    experts_held=2, expert_share_index=1, flash=False,
)
# Limits between what the sound side reads here and what the control and
# the faults read (CPU, 3 to 4 seeds a side).  Worst leaf's gradient: the
# program in bf16 0.016-0.035, the reference in bf16 0.013-0.048, the fp8
# control 0.083-0.14, half the batch 0.55-0.85, a feed in another order
# 0.37-0.78.  Parameters' change: the program 0.0045-0.011, the reference
# in bf16 0.0045-0.0079, the control 0.020-0.033, half the batch 0.22-0.27,
# another order 0.041-0.16, a state left unchanged 1.
tb.TINY["deepseek_v3"] = {
    "model": TINY_MODEL,
    "workload": dict(batch=2, seq_len=32, period_steps=2),
    "data": dict(windows=32),
    "limits": dict(grad_norm_gap=0.06, delta_norm_gap=0.015),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tb.tiny_root(tmp_path_factory.mktemp("deepseek_v3"), "deepseek_v3")


def metric_spec(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def published_shapes() -> dict:
    spec = harness.load_cell(ROOT, CELL)
    w = spec["workload"]
    return dict(spec["config"]["model"], batch=w["batch"], seq_len=w["seq_len"])


# ------------------------------------------------------------ the files


def test_the_cell_is_found_by_name_with_its_metrics():
    bench = tb.bench()
    spec = harness.load_cell(ROOT, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"step_mfu.train", "fwd_ms.train", "bwd_ms.train", "update_ms.train",
            "device_step_ms.train", "device_idle_pct.train", "compile_s", "boot_s",
            *NEW_METRICS} <= names
    assert spec["cell"]["chips"] == 1 and spec["config"]["family"] == "deepseek_v3"
    assert {m["name"] for m in spec["end_to_end"]} == {"train_steps_per_s", "setup_s"}
    configs = [c for c in bench["configs"] if c["name"] == CONFIG]
    cells = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(configs) == 1 and cells == [spec["cell"]] and cells[0]["config"] == CONFIG
    w = spec["workload"]
    assert (w["batch"], w["seq_len"], w["period_steps"]) == (2, 4096, 5)
    assert w["data"] == {"kind": "zipf_tokens", "windows": 4096, "exponent": 1.0, "shift": 8.0}
    assert w["optimizer"] == {"name": "adam", "learning_rate": 1e-5, "b1": 0.9, "b2": 0.999,
                              "eps": 1e-8}
    assert {"nonfinite_losses", "compiles_in_window"} <= set(w["limits"]) and len(w["limits"]) >= 4


def test_the_three_metrics_load_for_this_cell_and_no_other():
    for cell in tb.bench()["workloads"]:
        loaded = {m["name"]: m for m in harness.load_cell(ROOT, cell["name"])["per_layer"]}
        for name in NEW_METRICS:
            assert (name in loaded) == (cell["name"] == CELL), (name, cell["name"])
            if name in loaded:
                spec = metric_spec(name)
                assert (loaded[name]["reader"], loaded[name]["params"]) == (
                    spec["reader"], spec["params"])


# the catalog's ``config`` of kanana-2-30b-a3b-instruct-2601
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
    "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}


def test_the_configuration_keeps_every_published_width_and_names_each_change():
    c = harness.load_cell(ROOT, CELL)["config"]
    assert c["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert "16 chips share each layer" in c["deployment"]
    # every catalog key is there; those that differ are exactly ``reduced``
    changed = [k for k, v in CATALOG.items() if c[k] != v]
    assert sorted(changed) == sorted(c["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    assert (c["num_hidden_layers"], c["layers_published"]) == (5, 48)
    assert (c["n_routed_experts"], c["experts_held"], c["experts_published"]) == (8, 8, 128)
    assert c["vocab_size"] == 16032 and c["vocab_published"] == 128256 == 8 * 16032
    assert c["parameters"] == 424_961_024
    m = c["model"]
    assert (m["d_model"], m["n_heads"], m["kv_lora_rank"]) == (2048, 32, 512)
    assert (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]) == (128, 64, 128)
    assert (m["d_ff"], m["moe_d_ff"], m["num_experts"], m["experts_held"], m["expert_top_k"],
            m["num_shared_experts"]) == (6144, 768, 128, 8, 6, 2)
    assert (m["route_scale"], m["rope_theta"], m["norm_eps"], m["remat"]) == (
        2.448, 1000000, 1e-6, False)
    assert (m["n_layers"], m["num_dense_layers"], m["vocab_size"]) == (5, 1, 16032)
    for key in ("head_dim", "num_key_value_heads", "attention", "rope", "norms", "router",
                "shared_experts", "expert_bias", "aux_loss", "mtp", "remat", "precision",
                "weights"):
        assert key in c["assumed"], key


# -------------------------------------------------------------- the work


def test_param_count_is_the_issue_s_and_the_built_model_s():
    import jax
    import jax.numpy as jnp

    from benchmark.families.deepseek_v3 import lm_config
    from benchmark.reference import deepseek_v3 as ref
    from ddl_tpu.models.transformer import TransformerLM, count_lm_params

    s = published_shapes()
    assert work.param_count(s) == 424_961_024
    assert work.param_count(s) == sum(math.prod(v) for v in ref._shapes(s).values())
    mla = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert work.mla_projection_params(s) + 512 == mla == 26_345_984
    dense = mla + 2 * 2048 + 3 * 2048 * 6144
    expert = mla + 2 * 2048 + 2048 * 128 + 128 + (8 + 2) * 3 * 2048 * 768
    assert (dense, expert) == (64_098_816, 73_798_272)
    assert 2 * 16032 * 2048 == 65_667_072
    assert dense + 4 * expert + 65_667_072 + 2048 == work.param_count(s)
    # the program's own tree at the configuration's every width: shapes only
    abs_params = jax.eval_shape(
        lambda: TransformerLM(lm_config(s)).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert count_lm_params(abs_params) == work.param_count(s)


def test_work_functions_against_hand_counts():
    s = published_shapes()
    tokens, d = 8192, 2048
    assert work.tokens_per_step(s) == tokens
    assert work.visible_keys(s, 3) == 2048.5
    assert work.local_rows_per_layer(s) == 3072  # 384 a held expert
    per_token = (5 * 2 * 26_345_472                                  # the MLA projections
                 + 5 * 2 * 2048.5 * 32 * (192 + 128)                 # QK^T and PV
                 + 2 * 3 * d * 6144                                  # the dense MLP
                 + 4 * (2 * d * 128 + 2 * 3 * d * 768 * (2 + 6 * 8 / 128))  # routers, experts
                 + 2 * d * 16032)                                    # the head
    assert work.forward_flops_per_token(s) == pytest.approx(per_token)
    assert work.train_step_flops(s) == pytest.approx(3 * per_token * tokens)
    assert 17.34e12 < work.train_step_flops(s) < 17.36e12
    # the MLA share: projections and flash core, two thirds of the step
    mla = 3 * tokens * (5 * 2 * 26_345_472 + 5 * 2 * 2048.5 * 32 * 320)
    assert 0.66 < mla / work.train_step_flops(s) < 0.68
    flash = work.flash_attention_train(s)
    pairs = 4096 * 4097 / 2
    assert flash["flops"] == pytest.approx(5 * 2 * 32 * pairs * 2 * (3 * 192 + 3 * 128))
    assert 5.15e12 < flash["flops"] < 5.16e12 and flash["calls"] == 10
    qk, vo, st = 2 * 32 * 4096 * 192 * 2, 2 * 32 * 4096 * 128 * 2, 2 * 32 * 4096 * 4
    assert flash["bytes"] == 5 * ((2 * qk + 2 * vo + st) + (4 * qk + 4 * vo + 2 * st))
    gmm = work.expert_matmul_train(s)
    assert gmm["calls"] == 36 and gmm["flops"] == 4 * 9 * 2 * 3072 * d * 768


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
            assert "mla/latent" in spec["params"]["tags"]
        else:
            assert callable(getattr(work, spec["params"]["work"]))
            for k in ("flash_fwd.3", "flash_bwd_dkv.12"):
                assert re.search(spec["params"]["match"], k)
            assert not re.search(spec["params"]["match"], "moe_gmm_fwd.1")


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
    from benchmark.reference import deepseek_v3 as ref

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
