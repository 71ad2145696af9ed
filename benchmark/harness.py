"""The harness: finds a cell's files by name, refuses anything but the
chips the cell asks for, builds and warms the cell through its driver,
measures for ``--seconds``, reads the device, checks the outputs against
the plain reference and prints the contract's one JSON object last.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

__all__ = ["main", "load_cell", "REHEARSE_ENV", "TRACE_CAP_S"]

# Set to 1 to walk the control flow on the CPU at a size that fits it.
# Such a run never prints a device metric and never prints correct: true.
REHEARSE_ENV = "BENCH_REHEARSE_CPU"
# A traced run measures an untraced window first and then traces at most
# this long and this many periods.  The profiler slows a program of many
# small operations (DenseNet121's step has 14,000: 24 steps took 5.4 s
# traced, 1.3 s untraced, and 8 GB of host memory), so the trace gives the
# device's work a step and the kernels' times, and the untraced window the
# rate and the host's phases that go with them.
TRACE_CAP_S = 3.0
TRACE_CAP_PERIODS = 3

_HERE = os.path.dirname(os.path.abspath(__file__))
_DEFAULT_ROOT = os.path.dirname(_HERE)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> dict:
    """Everything that describes one cell, found by name under ``root``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[cell["config"]]["file"]))
    pkg = bench["paths"][0]
    workload = _read_json(os.path.join(root, pkg, "workloads", f"{name}.json"))

    def reported_here(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reported_here(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = []
    for m in bench["per_layer"]:
        if reported_here(m) and m["moves"] in e2e_names:
            spec = _read_json(os.path.join(root, pkg, "layer_metrics", f"{m['name']}.json"))
            per_layer.append({**m, "reader": spec["reader"], "params": spec.get("params", {})})
    return {
        "bench": bench, "cell": cell, "config": config, "workload": workload,
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


class _Compiles:
    """Compiles and cache traffic seen by ``jax.monitoring``."""

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.backend_compile_s = 0.0
        self.cache_retrieval_s = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw) -> None:
        if "backend_compile" in event:
            self.count += 1
            self.backend_compile_s += duration
        elif "cache_retrieval_time" in event:
            self.cache_retrieval_s += duration

    def _event(self, event, **kw) -> None:
        if event.endswith("cache_hits"):
            self.hits += 1
        elif event.endswith("cache_misses"):
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "events": self.count + self.hits + self.misses,
            "backend_compiles": self.count,
            "backend_compile_s": self.backend_compile_s,
            "cache_retrieval_s": self.cache_retrieval_s,
            "cache_hits": self.hits, "cache_misses": self.misses,
        }


def _device(jax, chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not rehearse:
        raise SystemExit(f"benchmark: JAX found no accelerator (platform {d0.platform!r})")
    if d0.platform == "tpu" and len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def _memory_peak(jax) -> tuple[int, dict]:
    """The fullest chip's peak: the buffers' peak plus the peak of what
    the runtime holds in reserve for the loaded programs' temporaries.
    On this runtime ``peak_bytes_in_use`` leaves a program's temporaries
    out and ``peak_bytes_reserved`` is where they are (it matches the
    compiled plan's ``temp_bytes`` to 1% in both families: PERF.md s6)."""
    peak, full = 0, {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        here = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        if here >= peak:
            peak, full = here, dict(stats)
    return peak, full


def _proc_gb(path: str, field: str) -> float:
    """A ``<field>: <n> kB`` line of a /proc file, in GB."""
    with open(path) as f:
        for line in f:
            if line.startswith(field + ":"):
                return int(line.split()[1]) / 1e6
    return 0.0


def _rss_gb() -> float:
    return _proc_gb("/proc/self/status", "VmRSS")


# A run whose host memory passes this share of the machine's is ended by
# itself, with a line that says so: a machine that runs out of memory is
# taken away, and every other run of the call with it.
HOST_MEMORY_GUARD_SHARE = 0.85


def _trim_host_memory() -> None:
    """Hand freed heap back to the system: a compile leaves gigabytes of
    it behind (DenseNet121's step: 14 GB), and the trace comes on top."""
    import ctypes
    import gc

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def _start_memory_guard(log) -> None:
    import threading

    limit = HOST_MEMORY_GUARD_SHARE * _proc_gb("/proc/meminfo", "MemTotal")

    def watch():
        worst = 0.0
        while limit > 0:
            time.sleep(0.5)
            rss = _rss_gb()
            if rss > worst + 2.0:
                worst = rss
                log(f"[bench] host_rss_gb {rss:.1f}")
            if rss > limit:
                log(f"[bench] host memory {rss:.1f} GB is over the guard "
                    f"{limit:.1f} GB: ending the run, no result")
                os._exit(3)

    threading.Thread(target=watch, daemon=True).start()


def _peaks(root: str, pkg: str, kind: str) -> dict:
    table = _read_json(os.path.join(root, pkg, "peaks.json"))
    if kind not in table or kind.startswith("_"):
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


_PLAN_KEYS = ("label", "analysis", "argument_bytes", "output_bytes",
              "temp_bytes", "alias_bytes", "code_bytes")


def measure(spec: dict, *, seed: int, seconds: float, trace: bool, device: dict,
            on_chip: bool, root: str, t_start: float, log=print, after_setup=None) -> dict:
    """Drive one run of a cell that ``load_cell`` described and return the
    result object.  ``correct`` here is what the comparison says; ``main``
    alone decides whether a chip was there to say it on.  ``after_setup``
    is called with the driver once the cell is built (the tests plant
    their faults through it)."""
    import jax

    from benchmark import metrics as bm
    from benchmark import trace as tr

    cell, config, workload = spec["cell"], spec["config"], spec["workload"]
    pkg = spec["bench"]["paths"][0]
    compiles = _Compiles()
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        driver_mod = importlib.import_module(f"benchmark.drivers.{workload['driver']}")
        driver = driver_mod.Driver(config, workload, seed, workdir, log=log)
        driver.setup()
        if after_setup is not None:
            after_setup(driver)
        t_built = time.perf_counter()
        driver.warm()
        rss_before_trim = _rss_gb()
        _trim_host_memory()
        setup_s = time.perf_counter() - t_start
        setup_compiles = compiles.snapshot()
        log(f"[bench] setup_s {setup_s:.3f} (build {t_built - t_start:.3f}, "
            f"warm {setup_s - (t_built - t_start):.3f}) compiles {setup_compiles} "
            f"host_rss_gb {rss_before_trim:.2f} trimmed to {_rss_gb():.2f}")

        trace_dir = os.path.join(workdir, "trace")
        traced = None
        if trace:
            win = driver.window(max(seconds - TRACE_CAP_S, TRACE_CAP_S))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tr.ANCHOR):
                    anchor_wall = time.time()
                    traced = driver.window(TRACE_CAP_S, TRACE_CAP_PERIODS)
            finally:
                log(f"[bench] traced window closed, host_rss_gb {_rss_gb():.2f}")
                jax.profiler.stop_trace()
                log(f"[bench] trace stopped, host_rss_gb {_rss_gb():.2f}")
            log(f"[bench] traced window {traced['elapsed']:.3f}s {traced['steps']} steps "
                f"{traced['steps'] / traced['elapsed']:.4f} steps/s (the tracer's cost is in it)")
        else:
            win = driver.window(seconds)
        in_window = compiles.snapshot()["backend_compiles"] - setup_compiles["backend_compiles"]
        peak_bytes, mem_stats = _memory_peak(jax)
        rate = win["steps"] / win["elapsed"]
        log(f"[bench] window {win['elapsed']:.3f}s {len(win['periods'])} periods "
            f"{win['steps']} steps {rate:.4f} steps/s "
            f"{rate * win['rows_per_step']:.1f} rows/s compiles_in_window {in_window} "
            f"host_rss_gb {_rss_gb():.2f}")
        log(f"[bench] memory_stats {json.dumps(mem_stats)}")
        events = driver.events()
        plans = [{k: e.get(k) for k in _PLAN_KEYS} for e in events if e.get("kind") == "hbm_plan"]
        log(f"[bench] hbm_plan {json.dumps(plans)}")
        # the program's own phase spans, (name, wall start, wall end)
        spans = [(e["name"], e["ts"] - e["dur"], e["ts"]) for e in events if e.get("kind") == "span"]
        shapes = driver.shapes()

        # the check: after the window, the peak read and the state freed
        checked = driver.check()
        numbers = dict(checked["numbers"], compiles_in_window=float(in_window))
        limits = workload.get("limits", {})
        correct, compared = bm.judge(numbers, limits)
        read_only = {k: v for k, v in numbers.items() if k not in limits}
        log(f"[bench] read, not compared: {json.dumps(read_only)}")
        log(f"[bench] check notes {json.dumps(checked['notes'])}")

        both = [win] + ([traced] if traced else [])
        result = {"correct": bool(correct), "attempted": sum(w["attempted"] for w in both),
                  "failed": both[-1]["failed"], "metrics": {}, "device": dict(device)}
        values = {}
        if not trace:
            values = dict(driver.end_to_end(win), setup_s=setup_s)
            wanted = spec["end_to_end"]
        else:
            t = tr.load(trace_dir)
            t.anchor_wall = anchor_wall
            log(f"[bench] trace read, host_rss_gb {_rss_gb():.2f}")
            ctx = {
                "window": win, "traced": traced, "trace": t, "spans": spans,
                "compile": setup_compiles, "shapes": shapes, "chips": int(cell["chips"]),
                "work": importlib.import_module(f"benchmark.work.{config['family']}"),
                "peak": _peaks(root, pkg, device["kind"]) if on_chip else None,
                "notes": {},
            }
            wanted = spec["per_layer"]
            for m in wanted:
                mod, fn = m["reader"].split(".")
                reader = getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn)
                if ctx["peak"] is None and m["source"] == "device_trace":
                    continue  # no chip: no device metric, under any name
                v = reader(ctx, m["params"])
                if v is not None:
                    values[m["name"]] = v
            log(f"[bench] reader notes {json.dumps(ctx['notes'])}")
            raw: dict = {}
            for n, s0, e0 in t.all_ops():
                raw[n] = raw.get(n, 0.0) + (e0 - s0)
            log(f"[bench] trace planes {sorted(t.ops)} anchor {t.anchor} "
                f"ops {sum(len(v) for v in t.ops.values())} "
                f"modules {sum(len(v) for v in t.modules.values())}")
            for n, sec in sorted(raw.items(), key=lambda kv: -kv[1])[:25]:
                log(f"[bench] op {sec:.6f}s {n[:160]}")
            lo, hi = t.window()
            idle = tr.gaps(t.intervals(next(iter(t.ops))), lo, hi) if t.ops else []
            mapped = [(n, t.to_trace_clock(s), t.to_trace_clock(e)) for n, s, e in spans]
            _, per_gap = tr.attribute_gaps(idle, [m for m in mapped if m[1] is not None])
            merged: dict = {}
            for name, sec in per_gap:
                merged[name] = merged.get(name, 0.0) + sec
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in tr.top_ops(t.all_ops(), 10)],
                "idle_gaps": [[n, s] for n, s in
                              sorted(merged.items(), key=lambda kv: -kv[1])[:10]],
            }
            if t.ops and t.window_s() > 0:
                log(f"[bench] traced stretch: busy {t.busy_s():.4f}s of {t.window_s():.4f}s, "
                    f"idle share {100 * (1 - t.busy_s() / t.window_s()):.2f}% "
                    f"(the tracer's cost is in it)")
            if on_chip:
                result["device"].update(busy_s=t.busy_s(), window_s=t.window_s())
        units = {m["name"]: m["unit"] for m in wanted}
        result["metrics"] = {
            k: {"value": v, "unit": units[k]} for k, v in values.items() if k in units
        }
        result["device"]["memory_peak_bytes"] = peak_bytes
        result["compared"] = compared
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=_DEFAULT_ROOT,
                    help="where BENCHMARK.json and the data directories are read from")
    args = ap.parse_args(argv)
    log = lambda *a: print(*a, flush=True)  # noqa: E731

    spec = load_cell(args.root, args.workload)
    rehearse = os.environ.get(REHEARSE_ENV) == "1"

    import jax

    device = _device(jax, int(spec["cell"]["chips"]), rehearse)
    on_chip = device["platform"] == "tpu"
    from ddl_tpu.utils.compile_cache import activate_compile_cache

    cache = activate_compile_cache()
    # No eviction from this process: a cell's programs together (DenseNet121's
    # step alone has 318 MB of code) pass the 192 MiB that the machine's
    # JAX_COMPILATION_CACHE_MAX_SIZE allows, and a least-recently-used cache
    # that is read in the same order every run then never hits (PERF.md s6).
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"[bench] cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} device {device} cache {cache}")
    _start_memory_guard(log)
    result = measure(
        spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        device=device, on_chip=on_chip, root=args.root, t_start=t_start, log=log,
    )
    if not on_chip:
        # no chip: nothing here is a measurement, and nothing is correct
        result["rehearsal"] = {"counts": result["metrics"],
                               "note": "no chip: not a measurement"}
        result["metrics"] = {}
        result["device"].pop("memory_peak_bytes", None)
        result["correct"] = False
    compared = result.pop("compared")
    result["compared"] = compared  # last in the line
    for name, row in compared.items():
        print(f"[bench] compared {name} value {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"[bench] correct {result['correct']}", file=sys.stderr, flush=True)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


