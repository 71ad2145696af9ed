"""Config system, CSV logger, launcher, smoke test, comm bench, analysis."""

import json

import numpy as np
import pytest

from ddl_tpu.config import Config, MeshConfig, apply_overrides, preset, to_dict


class TestConfig:
    def test_presets_match_reference_batching(self):
        # single.py:286 bs=30; ddp.py:335 bs=15/rank; pp.py:365 bs=30;
        # ddp_n_pp.py:371 bs=10/dp-row on a (3,2) mesh.
        assert preset("single").data.global_batch_size == 30
        assert preset("dp").data.global_batch_size == 15 * 2
        assert preset("pp").data.global_batch_size == 30
        dnp = preset("dp_pp")
        assert (dnp.mesh.data, dnp.mesh.pipe) == (3, 2)
        assert dnp.data.global_batch_size == 30
        assert dnp.train.num_microbatches == 5  # pp.py:378

    def test_overrides(self):
        cfg = preset("dp", **{"mesh.data": 4, "data.global_batch_size": 60})
        assert cfg.mesh.data == 4 and cfg.data.global_batch_size == 60
        cfg2 = preset("single", **{"train.max_epochs": "3"})
        assert cfg2.train.max_epochs == 3

    def test_unknown_override_rejected(self):
        with pytest.raises(KeyError):
            apply_overrides(Config(), {"train.nope": 1})

    def test_validation(self):
        bad = Config(strategy="pp", mesh=MeshConfig(2, 2))
        with pytest.raises(ValueError):
            bad.validate()
        bad2 = Config(strategy="pp", mesh=MeshConfig(1, 3))  # 3 != 2 stages
        with pytest.raises(ValueError):
            bad2.validate()

    def test_to_dict_json_serialisable(self):
        json.dumps(to_dict(preset("dp_pp")))


class TestCsvLogger:
    def test_row_schema(self, tmp_path):
        from ddl_tpu.utils import MetricLogger
        from ddl_tpu.utils.csv_logger import read_metric_csv

        lg = MetricLogger(tmp_path, "job-abc", global_rank=2, local_rank=0)
        lg.log("loss", 0.5, epoch=7)
        rows = read_metric_csv(tmp_path / "by_job_id" / "job-abc" / "loss.csv")
        (r,) = rows
        # reference row: [ts, job, grank, lrank, model_start_job, epoch, value]
        # (single.py:269)
        assert r["job_id"] == "job-abc"
        assert r["global_rank"] == 2
        assert r["model_start_job_id"] == "job-abc"
        assert r["epoch"] == 7 and r["value"] == 0.5

    def test_lineage_column_on_resume(self, tmp_path):
        from ddl_tpu.utils import MetricLogger
        from ddl_tpu.utils.csv_logger import read_metric_csv

        lg = MetricLogger(tmp_path, "job-new", model_start_job_id="job-old")
        lg.log("qwk", 0.9, epoch=0)
        (r,) = read_metric_csv(tmp_path / "by_job_id" / "job-new" / "qwk.csv")
        assert r["model_start_job_id"] == "job-old"

    def test_gradient_stats(self, tmp_path):
        from ddl_tpu.utils import MetricLogger

        lg = MetricLogger(tmp_path, "j")
        lg.log_gradient_stats({"w": np.array([1.0, -2.0]), "b": np.array([0.5])}, step=3)
        lines = (tmp_path / "gradient.csv").read_text().strip().splitlines()
        assert len(lines) == 2 and ",w," in lines[0]


class TestLauncher:
    def test_pod_commands(self):
        from ddl_tpu.launcher import JobSpec, pod_commands

        spec = JobSpec(preset="dp_pp", num_hosts=4, overrides=("mesh.data=8",))
        cmds = pod_commands(spec, coordinator_host="10.0.0.1")
        assert len(cmds) == 4
        assert "DDL_PROCESS_ID=3" in cmds[3]
        assert "DDL_NUM_PROCESSES=4" in cmds[0]
        assert "--preset dp_pp" in cmds[0] and "mesh.data=8" in cmds[0]
        # all hosts share one job id
        jid = [tok for tok in cmds[0].split() if tok.startswith("DDL_JOB_ID=")]
        assert all(jid[0] in c for c in cmds)

    def test_kubernetes_manifest(self):
        from ddl_tpu.launcher import JobSpec, kubernetes_manifest

        y = kubernetes_manifest(JobSpec(preset="dp", num_hosts=2))
        assert "parallelism: 2" in y and "google.com/tpu" in y


class TestSmoke:
    def test_mesh_collectives(self):
        from ddl_tpu.tools.smoke import run_smoke

        assert run_smoke(data=2, pipe=2)


class TestCommBench:
    def test_ping_pong(self):
        from ddl_tpu.bench.comm import ping_pong

        r = ping_pong(iterations=5, payload_elems=1024)
        assert r.times_ms.shape == (6,)
        assert np.isfinite(r.mean_ms) and r.mean_ms > 0
        assert r.one_way_gbps > 0

    @pytest.mark.parametrize(
        "op", ["psum", "all_gather", "reduce_scatter", "ppermute", "all_to_all"]
    )
    def test_collective_bandwidth(self, op):
        from ddl_tpu.bench.comm import collective_bandwidth

        r = collective_bandwidth(op, payload_elems=1024, iterations=3)
        assert np.isfinite(r["algbw_gbps"]) and r["algbw_gbps"] > 0

    def test_axis_sweep_covers_every_nontrivial_axis(self):
        """Per-axis attribution (the Ulysses all_to_all rides 'seq', DP
        grads ride 'data'): every axis with size > 1 gets every op; size-1
        axes are skipped."""
        import jax
        from jax.sharding import Mesh

        from ddl_tpu.bench.comm import COLLECTIVE_OPS, axis_bandwidth_sweep

        mesh = Mesh(
            np.array(jax.devices()[:8]).reshape(2, 1, 4),
            ("data", "pipe", "model"),
        )
        sweep = axis_bandwidth_sweep(mesh, payload_elems=512, iterations=2)
        assert set(sweep) == {"data", "model"}  # pipe=1 skipped
        for axis, per_op in sweep.items():
            assert set(per_op) == set(COLLECTIVE_OPS)
            for op, r in per_op.items():
                assert r["axis"] == axis
                assert np.isfinite(r["algbw_gbps"]) and r["algbw_gbps"] > 0, (
                    axis, op,
                )
        assert sweep["data"]["psum"]["devices"] == 2
        assert sweep["model"]["psum"]["devices"] == 4

    def test_run_comm_bench_writes_reference_csv(self, tmp_path):
        from ddl_tpu.bench.comm import run_comm_bench

        s = run_comm_bench(log_dir=tmp_path, job_id="commjob", iterations=3)
        lines = (tmp_path / "communication_time.csv").read_text().strip().splitlines()
        assert len(lines) == 4  # warmup + 3
        job, it, ms = lines[0].split(",")
        assert job == "commjob" and it == "0" and float(ms) > 0
        assert "psum_gbps" in s


class TestAnalysis:
    def test_aggregations(self, tmp_path):
        from ddl_tpu.bench.analysis import (
            comm_time_summary,
            epoch_time_per_job,
            final_epoch_quality,
        )
        from ddl_tpu.utils import MetricLogger

        for job, et in (("dp-aaa", 10.0), ("dp-bbb", 20.0), ("single-ccc", 30.0)):
            lg = MetricLogger(tmp_path, job)
            for epoch in range(2):
                lg.log("epoch_time", et + epoch, epoch)
                lg.log("qwk", 0.5 + epoch / 10, epoch)
                lg.log("loss", 1.0 - epoch / 10, epoch)
        per_job = epoch_time_per_job(tmp_path)
        assert per_job["dp-aaa"] == pytest.approx(10.5)
        quality = final_epoch_quality(tmp_path)
        assert quality["dp"]["qwk"] == pytest.approx(0.6)
        assert quality["single"]["loss"] == pytest.approx(0.9)
        with open(tmp_path / "communication_time.csv", "w") as f:
            f.write("j,0,100.0\nj,1,1.0\nj,2,3.0\n")
        s = comm_time_summary(tmp_path)
        assert s["j"]["mean_ms"] == pytest.approx(2.0)  # iteration 0 excluded
        assert s["j"]["init_ms"] == pytest.approx(100.0)


class TestGraftEntry:
    def test_dryrun_multichip(self):
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)

    def test_entry_lowers(self):
        """The flagship forward must trace+lower under jit (full compile of
        densenet121 on CPU is exercised by the driver)."""
        import jax

        import __graft_entry__ as ge

        fn, args = ge.entry()
        jax.jit(fn).lower(*args)  # raises on any tracing/sharding error


class TestMfu:
    def test_compiled_step_flops_exact(self):
        import jax.numpy as jnp

        from ddl_tpu.bench.mfu import compiled_step_flops

        n = 64
        flops = compiled_step_flops(lambda x: x @ x, jnp.ones((n, n)))
        assert flops == 2 * n**3  # XLA counts 2mnk for a matmul

    def test_peak_lookup_prefix_precedence(self):
        from ddl_tpu.bench.mfu import PEAK_BF16_FLOPS, device_peak_flops

        class FakeDev:
            def __init__(self, kind, platform="tpu"):
                self.device_kind = kind
                self.platform = platform

        assert device_peak_flops(FakeDev("TPU v5 lite")) == PEAK_BF16_FLOPS["TPU v5 lite"]
        assert device_peak_flops(FakeDev("TPU v5p")) == PEAK_BF16_FLOPS["TPU v5p"]
        assert device_peak_flops(FakeDev("TPU v4")) == PEAK_BF16_FLOPS["TPU v4"]
        assert device_peak_flops(FakeDev("cpu", platform="cpu")) is None

    def test_unlisted_tpu_kind_raises(self):
        """A device that is not in the table is an error, not a default:
        no peak is assumed for a bare or future kind string."""
        from ddl_tpu.bench.mfu import device_peak_flops, mfu

        class FakeDev:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        for kind in ("TPU v5", "TPU v9x", ""):
            with pytest.raises(KeyError, match="PEAK_BF16_FLOPS"):
                device_peak_flops(FakeDev(kind))
        with pytest.raises(KeyError):
            mfu(1e12, 0.01, FakeDev("TPU v5"))

    def test_mfu_on_cpu_is_none(self):
        from ddl_tpu.bench.mfu import mfu

        assert mfu(1e12, 0.01) is None  # CPU device: peak unknown

    def test_step_fns_expose_lower(self):
        """The set_mesh wrappers re-export jit's .lower so cost analysis
        can reach the compiled step (lm_steps/vit_steps _with_mesh)."""
        import jax
        import optax

        from ddl_tpu.models.transformer import LMConfig
        from ddl_tpu.parallel.sharding import LMMeshSpec
        from ddl_tpu.train.lm_steps import make_lm_step_fns

        cfg = LMConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, head_dim=16,
            d_ff=64, compute_dtype="float32", remat=False,
        )
        fns = make_lm_step_fns(
            cfg, LMMeshSpec(), optax.adam(1e-3), jax.random.key(0), 2, 8
        )
        assert hasattr(fns.train, "lower")
        import jax.numpy as jnp

        from ddl_tpu.bench.mfu import compiled_step_flops

        state = fns.init_state()
        toks = jnp.zeros((2, 8), jnp.int32)
        flops = compiled_step_flops(fns.train, state, toks, toks)
        assert flops > 0


class TestMemoryStats:
    def test_graceful_none_without_stats(self):
        from ddl_tpu.utils.memory import hbm_stats

        class NoStats:
            platform = "cpu"

            def memory_stats(self):
                return None

        class Raises(NoStats):
            def memory_stats(self):
                raise RuntimeError("unsupported")

        assert hbm_stats(NoStats()) is None
        assert hbm_stats(Raises()) is None
        # and whatever the ambient backend returns, it's a dict or None
        assert hbm_stats() is None or isinstance(hbm_stats(), dict)

    def test_shape_when_backend_reports(self):
        from ddl_tpu.utils.memory import hbm_stats

        class FakeDev:
            platform = "tpu"

            def memory_stats(self):
                return {"bytes_in_use": 10, "peak_bytes_in_use": 99,
                        "bytes_limit": 1000}

        out = hbm_stats(FakeDev())
        assert out == {"bytes_in_use": 10, "peak_bytes_in_use": 99,
                       "bytes_limit": 1000}

    def test_tpu_without_stats_raises(self):
        """On the chip a missing watermark is a fault, not a None."""
        from ddl_tpu.utils.memory import hbm_stats

        class NoStats:
            platform = "tpu"

            def memory_stats(self):
                return None

        class Raises(NoStats):
            def memory_stats(self):
                raise RuntimeError("unsupported")

        with pytest.raises(RuntimeError, match="no memory stats"):
            hbm_stats(NoStats())
        with pytest.raises(RuntimeError, match="unsupported"):
            hbm_stats(Raises())


def test_flash_attention_train_flops_band_closed_form():
    """The analytic visible-pair count matches brute force, windowed and
    causal, and the remat/no-remat matmul multipliers hold their ratio."""
    import numpy as np

    from ddl_tpu.bench.mfu import flash_attention_train_flops

    def brute_pairs(t, w):
        n = 0
        for q in range(t):
            lo = max(0, q - w + 1) if w else 0
            n += q - lo + 1
        return n

    for t, w in ((64, 0), (64, 16), (64, 64), (64, 100), (128, 31)):
        got = flash_attention_train_flops(
            1, 1, t, 1, 1, window=w, accounting="executed"
        )
        want = 9 * 2.0 * brute_pairs(t, w)
        np.testing.assert_allclose(got, want, rtol=1e-12, err_msg=f"{t},{w}")
    # model accounting (MFU): 6 theoretical matmuls, remat-invariant;
    # executed accounting (HFU): 9, +2 under remat replay
    model = flash_attention_train_flops(2, 8, 256, 64, 12)
    assert model == flash_attention_train_flops(2, 8, 256, 64, 12, remat=True)
    ex = flash_attention_train_flops(2, 8, 256, 64, 12, accounting="executed")
    ex_r = flash_attention_train_flops(
        2, 8, 256, 64, 12, remat=True, accounting="executed"
    )
    assert ex / model == 9 / 6 and ex_r / model == 11 / 6
    # banded < causal
    banded = flash_attention_train_flops(2, 8, 256, 64, 12, window=32)
    assert banded < model


def test_chunked_ce_extra_flops_restores_scan_trips():
    """Cost analysis counts a lax.scan body once; the ce_chunk correction
    must bring the loss edge back to full-T FLOPs (VERDICT round 3 #7:
    emitted JSON undercounted chunked rows by the trip count)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.bench.mfu import chunked_ce_extra_flops, compiled_step_flops
    from ddl_tpu.ops.losses import fused_chunked_ce

    b, t, d, v, chunk = 2, 64, 64, 256, 16  # 4 scan trips

    def loss(h, w, tgt):
        ce, _ = fused_chunked_ce(h, w, tgt, chunk)
        return ce

    g = jax.grad(loss, argnums=(0, 1))
    h = jnp.zeros((b, t, d), jnp.float32)
    w = jnp.zeros((v, d), jnp.float32)  # vocab-major, as LMHead stores it
    tgt = jnp.zeros((b, t), jnp.int32)
    counted = compiled_step_flops(g, h, w, tgt)
    if not counted > 0:
        import pytest

        pytest.skip("backend has no cost analysis")
    matmul = 2.0 * b * t * d * v
    # the undercount is real: the compiled program reports well under the
    # three model matmuls
    assert counted < 2.5 * matmul
    extra = chunked_ce_extra_flops(b, t, d, v, chunk, accounting="executed")
    # counted-once scan bodies + correction ≈ the four executed matmuls
    # (fwd, checkpoint replay, dx, dW); tolerance covers elementwise work
    np.testing.assert_allclose(counted + extra, 4 * matmul, rtol=0.1)
    # model accounting excludes exactly the checkpoint replay
    delta = extra - chunked_ce_extra_flops(b, t, d, v, chunk)
    np.testing.assert_allclose(delta, matmul, rtol=1e-12)


def test_vocab_chunked_ce_extra_flops_restores_scan_trips():
    """Same counted-once rule for the VOCAB-streamed loss edge: the
    correction must bring the compiled count back to the four executed
    full-V matmuls (fwd, bwd recompute, dx, dW)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.bench.mfu import (
        compiled_step_flops,
        vocab_chunked_ce_extra_flops,
    )
    from ddl_tpu.ops.losses import fused_vocab_chunked_ce

    b, t, d, v, vb = 2, 64, 64, 256, 64  # 4 vocab blocks

    def loss(h, w, tgt):
        return fused_vocab_chunked_ce(h, w, tgt, vb)[0]

    g = jax.grad(loss, argnums=(0, 1))
    h = jnp.zeros((b, t, d), jnp.float32)
    w = jnp.zeros((v, d), jnp.float32)
    tgt = jnp.zeros((b, t), jnp.int32)
    counted = compiled_step_flops(g, h, w, tgt)
    if not counted > 0:
        import pytest

        pytest.skip("backend has no cost analysis")
    matmul = 2.0 * b * t * d * v
    assert counted < 2.0 * matmul  # the undercount is real
    extra = vocab_chunked_ce_extra_flops(b, t, d, v, vb,
                                         accounting="executed")
    np.testing.assert_allclose(counted + extra, 4 * matmul, rtol=0.1)
    # model accounting excludes exactly the backward's recompute matmul
    delta = extra - vocab_chunked_ce_extra_flops(b, t, d, v, vb)
    np.testing.assert_allclose(delta, matmul, rtol=1e-12)


def test_fused_dense_block_train_flops_closed_form():
    """The fused-block FLOPs correction (Pallas calls report zero to
    cost analysis): model convention counts 3x (fwd + dW + dx) of the
    true-width 1x1 and the nine-tap 3x3 per layer of each FUSED block
    only; executed adds the padded width and the backward's forward
    recompute, so executed >= model always."""
    import pytest

    from ddl_tpu.bench.mfu import fused_dense_block_train_flops
    from ddl_tpu.ops.fused_dense_block import block_pad

    # one fused block at image 32 -> stem leaves hw=8: two layers
    batch, g, bn_size, f0 = 2, 4, 2, 8
    bn, s = bn_size * g, 8 * 8
    want = 0.0
    for i in range(2):
        want += 3 * (2 * s * (f0 + i * g) * bn) + 3 * (2 * s * 9 * bn * g)
    want *= batch
    got = fused_dense_block_train_flops(
        batch, 32, (2, 2), g, bn_size, f0, fused_blocks=(0,)
    )
    assert got == want
    # non-fused blocks contribute nothing (XLA counts them itself)
    assert fused_dense_block_train_flops(
        batch, 32, (2, 2), g, bn_size, f0, fused_blocks=()
    ) == 0.0
    ex = fused_dense_block_train_flops(
        batch, 32, (2, 2), g, bn_size, f0, fused_blocks=(0,),
        accounting="executed",
    )
    assert ex > got
    pad0, p_total = block_pad(f0, 2, g)
    assert p_total > f0 + 2 * g  # padding is what makes executed larger
    with pytest.raises(ValueError):
        fused_dense_block_train_flops(
            batch, 32, (2, 2), g, bn_size, f0, (0,), accounting="nope"
        )
