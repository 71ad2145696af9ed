"""Read a training cell's comparison on many seeds in one process: the
program against the plain reference (the lower readings), and on the
first ``--controls`` seeds the control (the reference in the precision
below the stated one) and the planted fault "half of the batch left out"
against the same reference (the upper readings).  No measured window:
training's readings need none.  One JSON line a seed, on standard output
and appended to ``--out``.  The limits in ``workloads/<cell>.json`` are set
from these lines and from nothing else (``PERF.md`` keeps the readings).
Each line also holds, under ``judged``, what ``metrics.judge`` says of each
side against the limits the cell's file has now: the program has to come
out correct, the control and the fault not.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --first-seed 2147484000
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    import time

    from benchmark import harness, metrics

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--reference-only", action="store_true",
                    help="no program: the reference in f32 against itself in the stated "
                         "precision, in the control's and with half of the batch left out")
    ap.add_argument("--root", default=harness._DEFAULT_ROOT)
    args = ap.parse_args(argv)

    spec = harness.load_cell(args.root, args.workload)
    import importlib

    import jax

    rehearse = os.environ.get(harness.REHEARSE_ENV) == "1"
    device = harness._device(jax, int(spec["cell"]["chips"]), rehearse)
    from ddl_tpu.utils.compile_cache import activate_compile_cache

    activate_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)  # as the harness
    driver_mod = importlib.import_module(f"benchmark.drivers.{spec['workload']['driver']}")
    control = spec["config"]["control_precision"]

    limits = spec["workload"].get("limits", {})

    def judged(side: dict) -> dict:
        """``judge`` on one side's numbers against the cell's own limits
        (those of the numbers a calibration reads: it has no window)."""
        n = side["numbers"]
        ok, table = metrics.judge(n, {k: v for k, v in limits.items() if k in n})
        return {"correct": ok,
                "failed": sorted(k for k, r in table.items() if not r["value"] <= r["limit"])}

    def emit(line: dict) -> None:
        sides = ("program", "stated", "control", "half_batch")
        line["judged"] = {k: judged(line[k]) for k in sides if k in line}
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")

    for i in range(args.seeds if args.reference_only else 0):
        seed = args.first_seed + 37 * i
        line = _reference_only(spec, seed, control)
        emit(dict(line, cell=args.workload, seed=seed, device=device))
    for i in range(0 if args.reference_only else args.seeds):
        seed = args.first_seed + 37 * i
        workdir = tempfile.mkdtemp(prefix="bench_cal_")
        t0 = time.perf_counter()
        try:
            d = driver_mod.Driver(spec["config"], spec["workload"], seed, workdir)
            d.setup()
            d.warm(whole_period=False)
            t1 = time.perf_counter()
            d.free()
            ref = d.reference_steps("f32")
            line = {"cell": args.workload, "seed": seed, "device": device,
                    "program": metrics.training_numbers(d.checked, ref),
                    "losses_program": d.checked["losses"], "losses_reference": ref["losses"],
                    "build_s": t1 - t0, "reference_step_s": ref["step_seconds"]}
            if i < args.controls:
                ctl = d.reference_steps(control)
                line["control"] = metrics.training_numbers(ctl, ref)
                line["control_precision"] = control
                half = d.reference_steps("f32", half_batch=True)
                line["half_batch"] = metrics.training_numbers(half, ref)
            line["total_s"] = time.perf_counter() - t0
            emit(line)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


def _reference_only(spec: dict, seed: int, control: str) -> dict:
    """The readings that need no program, on the first three batches of
    the cell's traffic in file order."""
    import importlib

    import jax
    import numpy as np

    from benchmark import metrics, traffic
    from benchmark.reference import common

    config, w = spec["config"], spec["workload"]
    model, n = config["model"], int(w["batch"])
    ref = importlib.import_module(f"benchmark.reference.{config['family']}")
    if w["data"]["kind"] == "zipf_tokens":
        t = int(w["seq_len"])
        toks = traffic.generate(w["data"], seed, vocab_size=model["vocab_size"], seq_len=t)
        rows = np.stack([toks[r * t: r * t + t + 1] for r in range(3 * n)]).astype(np.int32)
        batches = [(rows[i * n:(i + 1) * n, :-1], rows[i * n:(i + 1) * n, 1:]) for i in range(3)]
    else:
        images, labels = traffic.generate(w["data"], seed, split="train")
        batches = [(images[i * n:(i + 1) * n], labels[i * n:(i + 1) * n].astype(np.int32))
                   for i in range(3)]
    params = jax.jit(lambda k: ref.init_params(k, model))(jax.random.key(traffic.fold_seed(seed)))
    rb = int(w.get("reference", {}).get("row_block", 0))
    run = lambda precision, half=False: common.three_steps(  # noqa: E731
        ref, model, w["optimizer"], params, batches, precision=precision,
        row_block=rb, half_batch=half)
    f32 = run("f32")
    out = {"reference_step_s": f32["step_seconds"], "losses_reference": f32["losses"]}
    for name, got in (("stated", run({"bfloat16": "bf16", "float32": "f32"}[config["precision_stated"]])),
                      ("control", run(control)), ("half_batch", run("f32", True))):
        out[name] = metrics.training_numbers(got, f32)
        out[name + "_step_s"] = got["step_seconds"]
    return out


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
