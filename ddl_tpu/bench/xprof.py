"""Shared per-op device-time trace analysis (`jax.profiler.ProfileData`).

Captures live on any workload: run the step a few times warm, trace N
steps, then aggregate the device plane's sync-op line — XLA-op exclusive
times — into opcode categories.  The async-DMA line is reported
separately (those copies overlap compute; summing them into the op time
double-counts).  Used by ``profile_densenet`` (the headline CNN story,
PERF.md round 4), ``profile_lm``, and the anomaly-triggered capture path
(``obs/profiler.py``), whose ``profile_capture`` events carry the
``op_digest`` summary so a regression is explainable without opening
TensorBoard.

Traces are read with ``jax.profiler.ProfileData``.  CPU traces have no
``/device:`` plane at all (XLA ops land on ``/host:CPU`` thread-pool
lines named ``tf_XLA*``), so the reader falls back to those when no
device plane exists — the same digest, host-sided, which is exactly what
a CPU-JAX CI run can check.

``op_digest`` groups device time by the run's scope tables
(``obs/scope.py``: ``fwd`` / ``bwd`` / ``kernel/<name>`` / ``update``),
each op joined with the table of the module it ran in: the tables this
process planned (``obs/hbm.plan_program``), or the ``scope-h*.json`` files
of the run the capture lies under.  With no table at hand, or a trace that
names no modules (CPU), it groups by opcode category.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

from ddl_tpu.obs.scope import opcode_of, own_name

__all__ = [
    "analyze", "op_digest", "opcode_of", "own_name", "print_report",
    "read_trace", "CATEGORY",
]

CATEGORY = {
    "convolution": "conv",
    "fusion:Output": "conv/matmul fusion (+fused elementwise)",
    "fusion:Convolution": "conv/matmul fusion (+fused elementwise)",
    "dot": "conv/matmul fusion (+fused elementwise)",
    "copy": "copy (layout/concat materialise)",
    "copy-start": "async copy (overlapped)",
    "copy-done": "copy-done (DMA wait)",
    "slice-start": "async slice (overlapped)",
    "slice-done": "slice-done (DMA wait)",
    "dynamic-update-slice": "copy (layout/concat materialise)",
    "concatenate": "copy (layout/concat materialise)",
    "fusion:Loop": "fusion (elementwise loops)",
    "fusion:Input": "fusion (reduce/stats)",
    "reduce": "fusion (reduce/stats)",
    "reduce-window": "fusion (reduce/stats)",
    "fusion:Custom": "custom call (Pallas)",
    "custom-call": "custom call (Pallas)",
    "all-gather-start": "collective",
    "all-reduce-start": "collective",
    "collective-permute-start": "collective",
    "sort": "sort",
    "scatter": "scatter",
    "gather": "gather",
}


# ---------------------------------------------------------------------------
# Trace reader:
#     [(plane_name, [(line_name, [(event_name, start_ms, dur_ms), ...]), ...])]
# ---------------------------------------------------------------------------


def _read_xplane_profiledata(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (
            plane.name,
            [
                (
                    line.name,
                    [
                        (ev.name, ev.start_ns / 1e6, (ev.end_ns - ev.start_ns) / 1e6)
                        for ev in line.events
                    ],
                )
                for line in plane.lines
            ],
        )
        for plane in data.planes
    ]


def read_trace(trace_dir: str):
    """Read the newest ``*.xplane.pb`` under ``trace_dir`` into
    ``[(plane_name, [(line_name, [(event_name, start_ms, dur_ms), ...]),
    ...])]``."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return _read_xplane_profiledata(max(paths, key=os.path.getmtime))


# Host-plane lines that carry XLA op execution when there is no device
# plane (CPU backend): the thread-pool lines the CPU client names
# tf_XLAEigen/... and tf_XLATfrtCpuClient/....  Runtime bookkeeping
# events on those lines are filtered by name.
_HOST_XLA_LINE = re.compile(r"^tf_XLA")
_HOST_NOISE = re.compile(
    r"ThreadpoolListener|ThunkExecutor|^\$|^Execute$|Infeed|Outfeed"
)


def _module_of(modules, start_ms: float) -> str | None:
    """The name of the module event (sorted ``(start, end, name)``) that
    holds ``start_ms``, without its ``(<program id>)``; None outside all."""
    i = bisect.bisect_right(modules, (start_ms, float("inf"), "")) - 1
    if i < 0 or start_ms >= modules[i][1]:
        return None
    return modules[i][2].split("(", 1)[0]


def _op_events(planes):
    """(module, event_name, dur_ms) of executed XLA ops: the device planes'
    sync-op line, each op with the module of that plane's ``XLA Modules``
    line it started in; or the host XLA thread-pool lines when no device
    plane exists (CPU traces), which name no module (None)."""
    out = []
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        by_line = dict(lines)
        modules = sorted(
            (s, s + d, n) for n, s, d in by_line.get("XLA Modules", ())
        )
        out.extend(
            (_module_of(modules, s), n, d)
            for n, s, d in by_line.get("XLA Ops", ())
        )
    if out:
        return out
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for lname, events in lines:
            if _HOST_XLA_LINE.search(lname):
                out.extend(
                    (None, n, d) for n, _, d in events
                    if not _HOST_NOISE.search(n)
                )
    return out


def _aggregate(planes):
    """({(module, event name): ms}, {event name: count}, async-DMA busy
    ms, XLA-module ms) of a read trace."""
    by_module: dict[tuple, float] = collections.defaultdict(float)
    per_op_count: dict[str, int] = collections.defaultdict(int)
    async_ms = 0.0
    module_ms = 0.0
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        for lname, events in lines:
            if lname == "XLA Modules":
                module_ms += sum(d for _, _, d in events)
            if lname == "Async XLA Ops":
                async_ms += sum(d for _, _, d in events)
    for module, name, dur in _op_events(planes):
        by_module[module, name] += dur
        per_op_count[name] += 1
    return by_module, per_op_count, async_ms, module_ms


def analyze(trace_dir: str):
    """Aggregate a captured trace.  Returns (per_op ms, per_op counts,
    async-DMA busy ms, XLA-module ms) — all totals over the traced steps."""
    by_module, per_op_count, async_ms, module_ms = _aggregate(read_trace(trace_dir))
    per_op: dict[str, float] = collections.defaultdict(float)
    for (_, name), ms in by_module.items():
        per_op[name] += ms
    return per_op, per_op_count, async_ms, module_ms


def _scope_tables(trace_dir: str) -> dict:
    """The scope tables a digest of ``trace_dir`` joins with: this
    process's own, or the files of the run the capture lies under."""
    from ddl_tpu.obs.hbm import scope_tables
    from ddl_tpu.obs.scope import load_tables

    return scope_tables() or load_tables(trace_dir)


def op_digest(trace_dir: str, top: int = 8, scope: dict | None = None) -> dict:
    """Compact device-time summary of a captured trace — the payload
    ``profile_capture`` events carry so a throughput anomaly is
    explainable from the event stream alone.  ``{"total_ms", "ops":
    {group: ms (top N)}, "top_op": name, "by": "scope" | "opcode"}``; ms
    totals are over the whole traced window.

    ``scope`` is ``{module name: {instruction name: tag}}``; left out,
    ``_scope_tables``.  An op joins the table of the module it ran in and
    groups by its tag — ``bwd``, ``kernel/flash_bwd_dkv`` (the one flash
    backward kernel: dQ, dK and dV), ``update`` —
    and an op of a module without a table, or one its table does not
    know, by ``other (<opcode>)``.  When no op finds a tag (no table, or
    a CPU trace, which names no module) all group by opcode category as
    before."""
    by_module, _counts, _async_ms, module_ms = _aggregate(read_trace(trace_dir))
    if scope is None:
        scope = _scope_tables(trace_dir)
    tags = {
        key: scope.get(key[0], {}).get(own_name(key[1])) for key in by_module
    }
    by_scope = any(tag is not None for tag in tags.values())
    cats: dict[str, float] = collections.defaultdict(float)
    per_op: dict[str, float] = collections.defaultdict(float)
    for key, ms in by_module.items():
        per_op[key[1]] += ms
        tag = tags[key]
        if tag is None:
            op = opcode_of(key[1])
            tag = f"other ({op})" if by_scope else CATEGORY.get(op, f"other ({op})")
        cats[tag] += ms
    ranked = sorted(cats.items(), key=lambda kv: -kv[1])
    top_op = max(per_op.items(), key=lambda kv: kv[1])[0] if per_op else None
    return {
        "total_ms": round(sum(per_op.values()), 3),
        "module_ms": round(module_ms, 3),
        "ops": {k: round(v, 3) for k, v in ranked[:top]},
        "top_op": top_op[:140] if top_op else None,
        "by": "scope" if by_scope else "opcode",
    }


def print_report(trace_dir: str, steps: int, top: int = 25, header: str = ""):
    """Analyze + print the category table, top ops, and one JSON line.
    Returns the category dict (ms/step)."""
    import json

    per_op, per_op_count, async_ms, module_ms = analyze(trace_dir)
    total = sum(per_op.values())
    cats: dict[str, float] = collections.defaultdict(float)
    for name, ms in per_op.items():
        op = opcode_of(name)
        cats[CATEGORY.get(op, f"other ({op})")] += ms

    print(f"# trace: {trace_dir}  ({steps} steps{header})")
    print(f"# XLA module time: {module_ms / steps:.2f} ms/step; "
          f"sync-op exclusive total: {total / steps:.2f} ms/step; "
          f"async-DMA busy (overlapped): {async_ms / steps:.2f} ms/step")
    print("\n== by category (ms/step, % of sync op time) ==")
    for cat, ms in sorted(cats.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:40s} {ms / steps:8.3f}  "
              f"({100 * ms / total:5.1f}%)")
    print(f"\n== top {top} ops (ms/step, count/step) ==")
    rows = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    for name, ms in rows:
        n = per_op_count[name] // steps
        print(f"  {ms / steps:8.3f}  x{n:<4d} {name[:140]}")
    print(json.dumps({
        "module_ms_per_step": round(module_ms / steps, 3),
        "sync_op_ms_per_step": round(total / steps, 3),
        "async_dma_busy_ms_per_step": round(async_ms / steps, 3),
        "category_ms_per_step": {
            k: round(v / steps, 3) for k, v in cats.items()
        },
    }))
    return cats
