"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: phases cnn, lm, serve
    python chip_smoke.py --chips 4  # one four-chip host: dp4, pp4, lm4

Drives the main paths once through the entry points a user calls, at the
full width of the models the repo supports, and checks what comes out:

* ``cnn``   the reference's own job — DenseNet121 (published widths,
  224x224x3 uint8 in, 5 classes, global batch 30, bf16 compute) through
  ``ddl_tpu.cli --preset single``, synthetic data cut to 2 epochs of 5
  steps and 2 eval batches each.
* ``lm``    the 124M GPT-2-small LM of ``ddl_tpu/bench/lm.py`` (d_model
  768, 12 heads x 64, d_ff 3072, vocab 50304, T 1024, 12 layers, bf16,
  flash attention, no remat, batch 8): three train steps on one batch.
* ``serve`` a ``ServeEngine`` over the same 124M config, random-init
  weights, bf16 KV pool: ``precompile()``, then 8 requests submitted
  together; every token checked against the model's plain full forward,
  and the streams against a one-at-a-time ``make_lm_generator`` replay.
* ``--chips 4`` runs ONLY the four-chip phases and what each is compared
  with: the DenseNet121 step on one batch of 32 under ``dp`` on a (4,1)
  mesh (``dp4``) and ``dp_pp`` on a (2,2) mesh with 4 microbatches
  (``pp4``), each against its one-device reference as
  ``tests/test_parallel.py`` defines it, and one 124M LM step on
  ``LMMeshSpec(data=2, model=2)`` against one device (``lm4``).  Cold,
  this held a four-chip host 25 minutes (PERF.md, PR 22): most of it
  ``pp4``'s one-device reference, whose optimizer update runs op by op
  and compiles every small op — jit that before the next such call.

One process per chip: this parent never imports JAX (nor ``ddl_tpu``) and
runs the phases as children, one after another; a failing phase stops
the run.  Every child asserts the platform before anything else and
fails on any other than ``tpu``.  ``--size tiny`` rehearses the control
flow on the CPU (add ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
for ``--chips 4``); such a run never ends in ``"ok": true``.

Standard output: whatever the entry points print, one JSON object per
phase (seconds split into compile and run, first and last loss or the
tokens checked with their hash and rate, compile-cache hits and misses,
``peak_bytes_in_use``, the kernels found in the compiled text), and as
the LAST line the device as JAX reports it: ``{"ok": true, "device":
{"platform": "tpu", "kind": "...", "count": 1}}``.  Exit code 0 only when
every phase passed on a TPU at full size.  Logs and snapshots go under
``chiprun_out/`` (the chip tool's output directory), never into tracked
paths.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

T0 = time.perf_counter()  # a phase's seconds include reaching the chip
REPO = Path(__file__).resolve().parent
OUT_ROOT = REPO / "chiprun_out" / "chip_smoke"
PHASES = {1: ("cnn", "lm", "serve"), 4: ("dp4", "pp4", "lm4")}
# the whole run must end inside 1200 s, compilation included
PHASE_TIMEOUT_S = {"cnn": 600, "lm": 300, "serve": 600}
FOUR_CHIP_TIMEOUT_S = 1500
SEED = 0
# bf16 on the chip against the same math in another order: the CPU tests
# hold f32 to 1e-5; here a few 1e-2 relative on the loss is the honest
# bound (ISSUE 22), stated once for all four-chip comparisons
LOSS_RTOL = 3e-2
# serve: a token counts as right when the reference forward puts it within
# this many bf16 ulps (at the max logit's magnitude) of its max.  A wrong
# token sits about four logit-sigmas down, hundreds of ulps away.
NEAR_TIE_ULPS = 4


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------


def run_parent(args) -> int:
    device = None
    failed = None
    for phase in PHASES[args.chips]:
        result = OUT_ROOT / f"{phase}.json"
        result.unlink(missing_ok=True)
        timeout = (
            FOUR_CHIP_TIMEOUT_S if args.chips == 4 else PHASE_TIMEOUT_S[phase]
        )
        sys.stdout.flush()
        try:
            # the child writes to this process's stdout itself, and leaves
            # its phase line in ``result`` for the verdict below
            rc = subprocess.run(
                [sys.executable, str(Path(__file__).resolve()),
                 "--phase", phase, "--chips", str(args.chips),
                 "--size", args.size],
                cwd=str(REPO), timeout=timeout,
            ).returncode
        except subprocess.TimeoutExpired:  # run() has killed the child
            rc = 124
        line = json.loads(result.read_text()) if result.exists() else {}
        device = line.get("device", device)
        if rc != 0 or not line.get("ok"):
            failed = phase
            print(f"[chip_smoke] phase {phase} failed (exit {rc})", flush=True)
            break
    on_chip = (
        device is not None
        and device["platform"] == "tpu"
        and device["count"] == args.chips
    )
    ok = failed is None and on_chip and args.size == "full"
    if failed is None and not ok:
        print(
            "[chip_smoke] every phase ran, but not at full size on "
            f"{args.chips} TPU chip(s): not a pass", flush=True,
        )
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# children: one phase, one process, one owner of the chip(s)
# ---------------------------------------------------------------------------


class PhaseReport:
    """What a phase child prints as its JSON line.  Built BEFORE the
    phase runs so the compile timers and the cache counters see all of
    it; asserts the platform before anything else."""

    def __init__(self, phase: str, chips: int, size: str) -> None:
        import jax
        from jax import monitoring

        self.phase = phase
        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        self.fields: dict = {}
        self.compile_s = 0.0
        if size == "full" and self.device["platform"] != "tpu":
            self.fail(f"needs a TPU, JAX found {self.device['platform']!r}")
        if len(devices) < chips:
            self.fail(f"needs {chips} device(s), JAX found {len(devices)}")

        def on_duration(event: str, duration: float, **kw) -> None:
            # trace + lower + backend compile (a persistent-cache hit is
            # a short backend_compile): everything that is not running
            if event.startswith("/jax/core/compile/"):
                self.compile_s += duration

        monitoring.register_event_duration_secs_listener(on_duration)
        from ddl_tpu.utils.compile_cache import activate_compile_cache

        # the trainer CLI arms the cache itself (launch.bootstrap); the
        # library-level phases do what their entry points do
        if phase != "cnn":
            activate_compile_cache()

    def fail(self, why: str):
        self.emit(ok=False, error=why)
        raise SystemExit(2)

    def check(self, cond: bool, why: str) -> None:
        if not cond:
            self.fail(why)

    def emit(self, ok: bool = True, **extra) -> None:
        import jax

        from ddl_tpu.utils.compile_cache import cache_stats
        from ddl_tpu.utils.memory import hbm_stats

        total = time.perf_counter() - T0
        cache = cache_stats()
        peaks = [hbm_stats(d) for d in jax.local_devices()]
        line = {
            "phase": self.phase,
            "ok": ok,
            "seconds": {
                "total": round(total, 2),
                "compile": round(self.compile_s, 2),
                "run": round(total - self.compile_s, 2),
            },
            # None: the cache could not be armed and the phase compiled
            # cold (launch.py says why, once, above)
            "compile_cache": cache and {
                k: cache[k]
                for k in ("dir", "placed", "entries_before", "hits", "misses")
            },
            "peak_bytes_in_use": [
                p["peak_bytes_in_use"] if p else None for p in peaks
            ],
            **self.fields,
            **extra,
            "device": self.device,
        }
        print(json.dumps(line), flush=True)
        OUT_ROOT.mkdir(parents=True, exist_ok=True)
        (OUT_ROOT / f"{self.phase}.json").write_text(json.dumps(line))


def _finite_and_falling(rep: PhaseReport, losses: list[float]) -> None:
    import math

    rep.fields["losses"] = [round(x, 5) for x in losses]
    rep.check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    rep.check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def _kernels_in(text: str) -> dict:
    """Proof that no Pallas kernel ran interpreted: a compiled kernel is a
    ``tpu_custom_call`` in the optimized program's text."""
    return {"tpu_custom_call": text.count("tpu_custom_call")}


def _lm_config(size: str, **kw):
    from ddl_tpu.models.transformer import LMConfig

    if size == "tiny":
        return LMConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
            d_ff=256, **kw,
        )
    # the GPT-2-small widths bench/lm.py defaults to
    return LMConfig(
        vocab_size=50304, d_model=768, n_layers=12, n_heads=12, head_dim=64,
        d_ff=3072, **kw,
    )


# -- cnn --------------------------------------------------------------------


def phase_cnn(rep: PhaseReport, size: str) -> None:
    import contextlib
    import io

    from ddl_tpu import cli
    from ddl_tpu.utils.csv_logger import read_metric_csv

    out = OUT_ROOT / "cnn"
    shutil.rmtree(out, ignore_errors=True)
    # snapshots (two of DenseNet121's state) are too big for the chip
    # tool's output directory: a temp directory, gone with the phase
    snapshots = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    job = "chip-smoke-cnn"
    os.environ["DDL_JOB_ID"] = job
    argv = [
        "--preset", "single", "--set",
        "model.compute_dtype=bfloat16",
        "data.synthetic_num_train=150", "data.synthetic_num_test=60",
        "train.max_epochs=2",
        f"train.log_dir={out / 'logs'}",
        f"train.checkpoint_dir={snapshots}",
    ]
    if size == "tiny":
        argv += [
            "model.growth_rate=4", "model.block_config=[2,2]",
            "model.num_init_features=8", "model.bn_size=2",
            "model.split_blocks=[1]", "data.image_size=32",
        ]

    class Tee(io.StringIO):
        def write(self, s):
            sys.__stdout__.write(s)
            return super().write(s)

    tee = Tee()
    try:
        with contextlib.redirect_stdout(tee):
            cli.main(argv)
        saved = sorted(p.name for p in Path(snapshots, job).glob("epoch_*"))
    finally:
        shutil.rmtree(snapshots, ignore_errors=True)
    rep.fields["snapshots"] = saved
    rep.check(bool(saved), "no snapshot written (QWK-gated save)")
    world = next(
        json.loads(ln.split("world: ", 1)[1])
        for ln in tee.getvalue().splitlines() if "[ddl_tpu] world: " in ln
    )
    rep.fields["world_platform"] = world["platform"]
    rep.check(
        size == "tiny" or world["platform"] == "tpu",
        f"world line says {world['platform']!r}",
    )
    job_dir = out / "logs" / "by_job_id" / job
    csvs = sorted(p.name for p in job_dir.glob("*.csv"))
    rep.fields["metric_csvs"] = len(csvs)
    for name in ("loss.csv", "val_loss.csv", "qwk.csv", "steps_per_sec.csv"):
        rep.check(name in csvs, f"metric CSV {name} not written ({csvs})")
    losses = [float(r["value"]) for r in read_metric_csv(job_dir / "loss.csv")]
    rep.check(len(losses) == 2, f"expected 2 epoch losses, got {losses}")
    # an epoch's loss is the mean of its steps': finite means every step
    # was (the trainer also halts on a non-finite one)
    _finite_and_falling(rep, losses)


# -- lm ---------------------------------------------------------------------


def _lm_batch(cfg, batch: int, seq_len: int):
    import jax.numpy as jnp
    import numpy as np

    toks = jnp.asarray(
        np.random.default_rng(SEED).integers(
            0, cfg.vocab_size, (batch, seq_len + 1)
        ),
        jnp.int32,
    )
    return toks[:, :-1], toks[:, 1:]


def _lm_three_steps(cfg, spec, batch, seq_len, devices=None):
    """The calls ``bench/lm.py`` makes: build, init, step on one batch."""
    import jax
    import optax

    from ddl_tpu.parallel.sharding import normalize_flash
    from ddl_tpu.train.lm_steps import make_lm_step_fns

    cfg = normalize_flash(cfg, spec, seq_len)
    fns = make_lm_step_fns(
        cfg, spec, optax.adamw(3e-4), jax.random.key(SEED), batch, seq_len,
        devices=devices,
    )
    state = fns.init_state()
    inp, tgt = _lm_batch(cfg, batch, seq_len)
    losses = []
    for _ in range(3):
        state, m = fns.train(state, inp, tgt)
        losses.append(float(m["loss"]))
    return fns, state, (inp, tgt), losses


def phase_lm(rep: PhaseReport, size: str) -> None:
    from ddl_tpu.parallel.sharding import LMMeshSpec

    batch, seq_len = (8, 1024) if size == "full" else (4, 128)
    cfg = _lm_config(
        size, compute_dtype="bfloat16", flash=True, remat=False
    )
    fns, state, (inp, tgt), losses = _lm_three_steps(
        cfg, LMMeshSpec(), batch, seq_len
    )
    _finite_and_falling(rep, losses)
    text = fns.train.lower(state, inp, tgt).compile().as_text()
    rep.fields["kernels"] = _kernels_in(text)
    # flash forward, dQ and dK/dV: three kernels in the train step
    rep.check(
        size == "tiny" or rep.fields["kernels"]["tpu_custom_call"] >= 3,
        "no compiled flash-attention kernel in the LM train step",
    )


# -- serve ------------------------------------------------------------------


def phase_serve(rep: PhaseReport, size: str) -> None:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.infer.decode import make_lm_generator
    from ddl_tpu.models.transformer import TransformerLM
    from ddl_tpu.parallel.sharding import LMMeshSpec
    from ddl_tpu.serve.engine import ServeEngine

    cfg = _lm_config(size, compute_dtype="bfloat16")
    spec = LMMeshSpec()
    # random-init weights exactly as serve/bench.py builds them
    params = nn.meta.unbox(
        TransformerLM(cfg, None).init(
            jax.random.key(SEED), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    if size == "full":
        p_lo, p_hi, n_lo, n_hi, block, blocks = 32, 512, 16, 64, 32, 256
    else:
        p_lo, p_hi, n_lo, n_hi, block, blocks = 4, 40, 4, 8, 8, 64
    rng = np.random.default_rng(SEED)
    clients = [
        {
            "id": f"c{i}",
            "prompt": rng.integers(
                0, cfg.vocab_size, int(rng.integers(p_lo, p_hi + 1))
            ).astype(np.int32),
            "max_new": int(rng.integers(n_lo, n_hi + 1)),
        }
        for i in range(8)
    ]
    # the envelope: random prompts share no prefix, so no prefix cache
    # and no chunk programs; 4 fused decode steps bound the decode grid
    engine = ServeEngine(
        cfg, params, spec, block_size=block, num_blocks=blocks, max_batch=8,
        max_blocks_per_seq=blocks // 8, max_steps_per_dispatch=4,
        prefix_cache=False,
    )
    pre = engine.precompile(p_hi, n_hi)
    for c in clients:  # together, so continuous batching is exercised
        engine.submit(c["prompt"], c["max_new"], request_id=c["id"],
                      rng_seed=SEED)
    t0 = time.perf_counter()
    results = engine.run()  # numpy out: the device has finished
    run_s = time.perf_counter() - t0
    streams = [np.asarray(results[c["id"]], np.int32) for c in clients]
    st = engine.stats
    rep.fields.update(
        # for a comparison of two trees on one chip: the same seed gives
        # the same streams, and the rate is a lead, one run of 8 requests
        tokens_sha256=hashlib.sha256(np.concatenate(streams).tobytes()).hexdigest(),
        run_tok_per_s=round(sum(map(len, streams)) / run_s, 1),
        precompiled=pre,
        completed=st["completed"],
        prefill_compiles=st["prefill_compiles"],
        decode_compiles=st["decode_compiles"],
        decode_attention=st["decode_attention"],
        peak_lanes=engine.scheduler.peak_lanes,
    )
    rep.check(st["completed"] == 8, f"completed {st['completed']} of 8")
    rep.check(
        st["prefill_compiles"] == 0 and st["decode_compiles"] == 0,
        f"compiled after precompile(): prefill {st['prefill_compiles']}, "
        f"decode {st['decode_compiles']}",
    )
    rep.check(
        engine.scheduler.peak_lanes > 1, "requests never shared a batch"
    )
    # the compiled decode program over the widest table
    prog, _ = engine.fns.decode_for(1, engine.fns.max_blocks_per_seq)
    with jax.set_mesh(engine.fns.mesh):
        text = prog.lower(
            engine.params, engine.pools,
            *engine.fns.probe_inputs("decode", engine.fns.max_batch),
        ).compile().as_text()
    rep.fields["kernels"] = _kernels_in(text)
    if size == "full":
        rep.check(
            st["decode_attention"] == "kernel",
            f"engine took the {st['decode_attention']} decode path",
        )
        rep.check(
            rep.fields["kernels"]["tpu_custom_call"] >= cfg.n_layers,
            "no compiled decode-attention kernel in the decode program",
        )
    _check_tokens(rep, cfg, spec, params, clients, results)


def _check_tokens(rep: PhaseReport, cfg, spec, params, clients, results):
    """Are the engine's tokens right?  Two references, both plain:

    * the model's own full forward (the training path: no KV cache, no
      paging, no batching), teacher-forced on each request's prompt plus
      the engine's output — every token the engine emitted must be that
      forward's argmax at its position, or within ``NEAR_TIE_ULPS`` bf16
      ulps of it;
    * the one-at-a-time ``make_lm_generator`` replay that ``serve-bench
      --compare-sequential`` checks.  On the CPU in f32 the two streams
      are bit-identical; in bf16 on the chip the replay runs programs of
      other shapes (exact-length prefill, batch 1, another cache length)
      and random-init logits over 50k words sit close, so a stream may
      leave the replay at a near-tie.  Where it does, the first forward
      adjudicates: both candidates must be near-ties of its max there.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.infer.decode import make_lm_generator
    from ddl_tpu.models.transformer import TransformerLM

    model = TransformerLM(cfg, None)
    forward = jax.jit(lambda p, toks: model.apply({"params": p}, toks)[0])
    width = max(len(c["prompt"]) + c["max_new"] for c in clients)
    width += -width % 128  # one program for all eight (causal: padding
    # after a position cannot reach it)

    def near_tie(row, tok) -> bool:
        top = float(row.max())
        ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 1e-30))) - 7)
        return top - float(row[tok]) <= NEAR_TIE_ULPS * ulp

    checked = argmax_agree = replay_identical = 0
    left_replay_at = {}
    for c in clients:
        got = np.asarray(results[c["id"]])
        n, plen = len(got), len(c["prompt"])
        rep.check(n == c["max_new"], f"{c['id']}: {n} of {c['max_new']} tokens")
        seq = np.zeros((1, width), np.int32)
        seq[0, :plen] = c["prompt"]
        seq[0, plen:plen + n - 1] = got[:-1]
        # row i predicts generated token i
        rows = np.asarray(forward(params, jnp.asarray(seq)))[0, plen - 1:plen - 1 + n]
        rep.check(bool(np.isfinite(rows).all()), f"{c['id']}: non-finite logits")
        for i, tok in enumerate(got):
            checked += 1
            argmax_agree += int(rows[i].argmax() == tok)
            rep.check(
                near_tie(rows[i], tok),
                f"{c['id']} token {i} ({tok}) is {float(rows[i].max() - rows[i][tok]):.3f} "
                "below the reference forward's max: not a near-tie",
            )
        gen = make_lm_generator(
            cfg, spec, prompt_len=plen, max_new=c["max_new"], batch=1,
        )
        want = np.asarray(
            gen(params, jnp.asarray(c["prompt"])[None],
                jax.random.PRNGKey(SEED))
        )[0]
        if np.array_equal(got, want):
            replay_identical += 1
            continue
        first = int(np.argmax(got != want))
        left_replay_at[c["id"]] = first
        rep.check(
            near_tie(rows[first], want[first]),
            f"{c['id']} leaves the sequential replay at token {first}, and "
            "the replay's token is not a near-tie of the reference forward",
        )
    rep.fields.update(
        tokens_checked=checked,
        argmax_agree=argmax_agree,
        near_tie_ulps=NEAR_TIE_ULPS,
        replay_identical=f"{replay_identical}/{len(clients)}",
        left_replay_at=left_replay_at,
    )


# -- four chips -------------------------------------------------------------


def _check_placed_on_all(rep: PhaseReport, tree, mesh, what: str) -> int:
    """Every array of a state over ``mesh`` has shards where its sharding
    says, each of the shard shape its rule gives, and together they reach
    every device of the mesh.  Returns how many leaves are really split
    (shard smaller than array).  Code that has only met virtual CPU
    devices may put all on the first."""
    import jax

    split, reached = 0, set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = what + jax.tree_util.keystr(path)
        held = {s.device for s in leaf.addressable_shards}
        want = set(leaf.sharding.device_set)
        rep.check(held == want, f"{name}: shards on {len(held)} of {len(want)}")
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        rep.check(
            all(s.data.shape == shard_shape for s in leaf.addressable_shards),
            f"{name}: shard shapes differ from {shard_shape}",
        )
        split += shard_shape != leaf.shape
        reached |= held
    rep.check(
        reached == set(mesh.devices.flat),
        f"{what} reaches {len(reached)} of {mesh.devices.size} devices",
    )
    in_use = [d.memory_stats() for d in mesh.devices.flat]
    if all(in_use):  # the CPU rehearsal has no stats
        rep.check(
            all(m["bytes_in_use"] > 0 for m in in_use),
            f"{what}: a device holds nothing: "
            f"{[m['bytes_in_use'] for m in in_use]}",
        )
    return split


def _compare_losses(rep: PhaseReport, got: list[float], want: list[float]):
    import math

    rep.fields.update(
        losses=[round(x, 5) for x in got],
        reference_losses=[round(x, 5) for x in want],
        loss_rtol=LOSS_RTOL,
    )
    rep.check(
        all(math.isfinite(x) for x in got + want), f"non-finite: {got} {want}"
    )
    rep.check(
        all(abs(a - b) <= LOSS_RTOL * abs(b) for a, b in zip(got, want)),
        f"losses {got} differ from the reference's {want} by more than "
        f"{LOSS_RTOL} relative",
    )


def _cnn_parity_setup(size: str, num_stages):
    """As ``tests/test_parallel.py::_fresh(sgd=True)`` at full width: one
    synthetic batch of 32, SGD (Adam's first step is +-lr * sign(grad),
    which turns reduction-order noise on near-zero gradients into
    full-lr sign flips)."""
    import jax
    import numpy as np
    import optax

    from ddl_tpu.config import ModelConfig
    from ddl_tpu.data.dataset import SyntheticAptosDataset
    from ddl_tpu.models import build_stages
    from ddl_tpu.train.state import create_train_state

    if size == "full":
        cfg, image = ModelConfig(compute_dtype="bfloat16"), 224
    else:
        cfg, image = ModelConfig(
            growth_rate=4, block_config=(2, 2), num_init_features=8,
            bn_size=2, split_blocks=(1,), compute_dtype="bfloat16",
        ), 32
    data = SyntheticAptosDataset(32, image, cfg.num_classes, seed=SEED)
    images, labels = zip(*(data[i] for i in range(32)))
    images = np.stack(images).astype(np.uint8)
    labels = np.asarray(labels, np.int32)
    stages = build_stages(cfg, num_stages=num_stages)
    tx = optax.sgd(0.01)
    state = create_train_state(stages, tx, jax.random.key(SEED), image)
    return cfg, image, stages, tx, state, images, labels


def _three_cnn_steps(fns, state, images, labels):
    import jax
    import jax.numpy as jnp

    state = jax.tree.map(jnp.copy, state)  # the step donates its state
    losses = []
    for _ in range(3):
        state, loss, _ = fns.train(state, images, labels)
        losses.append(float(loss))
    return state, losses


def phase_dp4(rep: PhaseReport, size: str) -> None:
    """(b) dp on a (4,1) mesh against (a) the same step on one device, as
    ``tests/test_parallel.py::test_dp_matches_single``."""
    import jax.numpy as jnp

    from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
    from ddl_tpu.train.steps import make_dp_step_fns

    _, _, stages, tx, state0, images, labels = _cnn_parity_setup(size, 1)
    single = make_dp_step_fns(
        stages, tx, build_mesh(MeshSpec(1, 1)), jnp.bfloat16
    )
    mesh = build_mesh(MeshSpec(4, 1))
    dp = make_dp_step_fns(stages, tx, mesh, jnp.bfloat16)
    _, want = _three_cnn_steps(single, state0, images, labels)
    state, got = _three_cnn_steps(dp, state0, images, labels)
    _compare_losses(rep, got, want)
    _check_placed_on_all(rep, state, mesh, "dp state")


def phase_pp4(rep: PhaseReport, size: str) -> None:
    """(c) dp_pp on a (2,2) mesh, 4 microbatches, against (a) the
    sequential microbatched reference with the same M and D, as
    ``tests/test_parallel.py::test_pipeline_matches_sequential`` — the
    same microbatches, so the BatchNorm statistics agree."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO / "tests"))
    from test_parallel import (
        microbatch_loss_and_grads,
        sequential_reference_step,
    )

    from ddl_tpu.models import stage_boundary_shapes
    from ddl_tpu.parallel.mesh import MeshSpec, build_mesh
    from ddl_tpu.parallel.pipeline import make_pipeline_step_fns

    D, M = 2, 4
    cfg, image, stages, tx, state0, images, labels = _cnn_parity_setup(
        size, None
    )
    mesh = build_mesh(MeshSpec(D, 2))
    fns = make_pipeline_step_fns(
        stages, tx, mesh, jnp.bfloat16, num_microbatches=M,
        boundary_shapes=stage_boundary_shapes(cfg, image),
        num_classes=cfg.num_classes, remat=cfg.remat,
    )
    # the reference on ONE device, its microbatch jitted: op-by-op
    # dispatch of DenseNet121 would take minutes
    micro = jax.jit(partial(microbatch_loss_and_grads, stages))
    ref, want = state0, []
    for _ in range(3):
        params, stats, loss, _ = sequential_reference_step(
            stages, tx, ref, images, labels, M=M, D=D, micro=micro
        )
        ref = ref.replace(params=params, batch_stats=tuple(stats))
        want.append(loss)
    state, got = _three_cnn_steps(fns, state0, images, labels)
    _compare_losses(rep, got, want)
    _check_placed_on_all(rep, state, mesh, "dp_pp state")


def phase_lm4(rep: PhaseReport, size: str) -> None:
    """One 124M LM step (three, for the loss match) on
    ``LMMeshSpec(data=2, model=2)`` against the same step on one device."""
    import jax

    from ddl_tpu.parallel.sharding import LMMeshSpec

    batch, seq_len = (8, 1024) if size == "full" else (4, 128)
    cfg = _lm_config(size, compute_dtype="bfloat16", flash=True, remat=False)
    _, _, _, want = _lm_three_steps(
        cfg, LMMeshSpec(), batch, seq_len, devices=jax.devices()[:1]
    )
    fns, state, (inp, tgt), got = _lm_three_steps(
        cfg, LMMeshSpec(data=2, model=2), batch, seq_len
    )
    _compare_losses(rep, got, want)
    split = _check_placed_on_all(rep, state, fns.mesh, "lm state")
    rep.fields["split_leaves"] = split
    rep.check(split > 0, "no leaf of the LM state is split over the mesh")
    text = fns.train.lower(state, inp, tgt).compile().as_text()
    rep.fields["kernels"] = _kernels_in(text)
    rep.check(
        size == "tiny" or rep.fields["kernels"]["tpu_custom_call"] >= 3,
        "no compiled flash-attention kernel in the sharded LM train step",
    )


PHASE_FNS = {
    "cnn": phase_cnn, "lm": phase_lm, "serve": phase_serve,
    "dp4": phase_dp4, "pp4": phase_pp4, "lm4": phase_lm4,
}


def run_child(args) -> int:
    rep = PhaseReport(args.phase, args.chips, args.size)
    PHASE_FNS[args.phase](rep, args.size)
    rep.emit(ok=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip phases and their references")
    ap.add_argument("--size", choices=("full", "tiny"), default="full",
                    help="tiny: rehearse control flow on the CPU (never ok)")
    ap.add_argument("--phase", choices=tuple(PHASE_FNS),
                    help="(internal) run one phase in this process")
    args = ap.parse_args()
    if args.phase is None:
        return run_parent(args)
    return run_child(args)


if __name__ == "__main__":
    raise SystemExit(main())
