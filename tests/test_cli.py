"""CLI end-to-end: preset + dotted overrides drive a full tiny training run."""

import numpy as np

from ddl_tpu.utils.csv_logger import read_metric_csv


def test_cli_single_end_to_end(tmp_path, monkeypatch):
    from ddl_tpu import cli

    monkeypatch.setenv("DDL_JOB_ID", "single-clitest")
    cli.main(
        [
            "--preset",
            "single",
            "--set",
            "model.growth_rate=4",
            "model.block_config=[2,2]",
            "model.num_init_features=8",
            "model.bn_size=2",
            "model.split_blocks=[1]",
            "model.remat=false",
            "data.image_size=16",
            "data.synthetic_num_train=32",
            "data.synthetic_num_test=16",
            "data.global_batch_size=8",
            "data.eval_batch_size=8",
            "data.num_workers=0",
            "train.max_epochs=1",
            f"train.log_dir={tmp_path}/logs",
            f"train.checkpoint_dir={tmp_path}/ckpt",
        ]
    )
    rows = read_metric_csv(tmp_path / "logs" / "by_job_id" / "single-clitest" / "loss.csv")
    assert len(rows) == 1 and np.isfinite(rows[0]["value"])
    sps = read_metric_csv(
        tmp_path / "logs" / "by_job_id" / "single-clitest" / "steps_per_sec.csv"
    )
    assert sps[0]["value"] > 0


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """``chip_smoke.py`` is the proof that the system starts on the chip:
    on the CPU backend it must exit non-zero with ``ok`` false in its
    last line — at full size at once, and after a whole tiny-size
    rehearsal too — and its parent must stay off JAX."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    phase = json.loads(out.stdout.strip().splitlines()[0])
    assert phase["phase"] == "cnn" and phase["ok"] is False
    assert "needs a TPU" in phase["error"]
    # the parent imports neither jax nor the package
    probe = (
        "import sys, runpy; sys.argv = ['chip_smoke.py', '--help']\n"
        "try: runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules and 'ddl_tpu' not in sys.modules"
    ) % os.path.join(repo, "chip_smoke.py")
    subprocess.run(
        [sys.executable, "-c", probe], env=env, check=True, timeout=60,
        capture_output=True, cwd=tmp_path,
    )
