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

Traces are read with ``jax.profiler.ProfileData``.  A log-analysis
host without JAX (``ddl_tpu bench digest`` over a mounted trace dir)
gets a minimal protobuf *wire-format* reader for the stable
XSpace/XPlane schema instead — no TensorFlow/xprof import, just the
handful of field numbers the analysis needs.  CPU traces have no
``/device:`` plane at all (XLA ops land on ``/host:CPU`` thread-pool
lines named ``tf_XLA*``), so the readers fall back to those when no
device plane exists — the same digest, host-sided, which is exactly what
a CPU-JAX CI run can check.
"""

from __future__ import annotations

import collections
import glob
import os
import re

__all__ = [
    "analyze", "op_digest", "opcode_of", "print_report", "read_trace",
    "CATEGORY",
]

# HLO text looks like "%fusion.123 = bf16[...] fusion(...), kind=kLoop ..."
_OPCODE_RX = re.compile(r"=\s*(?:\([^)]*\)|[^ ]+)\s+([a-z][a-z0-9-]*)\(")


def opcode_of(name: str) -> str:
    """Pull the HLO opcode out of a profiler op-event name."""
    m = _OPCODE_RX.search(name)
    if m:
        op = m.group(1)
    else:
        # bare names like "fusion.123" / "copy-start.4"
        op = name.split(" ")[0].lstrip("%").split(".")[0]
    if "fusion" in name and (kind := re.search(r"kind=k(\w+)", name)):
        return f"fusion:{kind.group(1)}"
    return op


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
# Trace readers.  Both normalize to the same shape:
#     [(plane_name, line_name, [(event_name, dur_ms), ...]), ...]
# ---------------------------------------------------------------------------


def _pb_varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _pb_fields(buf: bytes):
    """Iterate (field_number, value) over one serialized proto message —
    the minimal wire-format walk (varint + length-delimited + fixed)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _pb_varint(buf, i)
        fnum, wt = key >> 3, key & 7
        if wt == 0:  # varint
            val, i = _pb_varint(buf, i)
        elif wt == 1:  # fixed64
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:  # length-delimited
            ln, i2 = _pb_varint(buf, i)
            val, i = buf[i2:i2 + ln], i2 + ln
        elif wt == 5:  # fixed32
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, val


def _read_xplane_wire(path: str):
    """Parse an ``*.xplane.pb`` without ``ProfileData``: XSpace.planes=1;
    XPlane{name=2, lines=3, event_metadata=4}; XLine{name=2,
    display_name=11, events=4}; XEvent{metadata_id=1, duration_ps=3};
    XEventMetadata{name=2, display_name=4} — the stable subset of the
    schema this analysis needs."""
    with open(path, "rb") as fh:
        space = fh.read()
    planes = []
    for fnum, plane_buf in _pb_fields(space):
        if fnum != 1:
            continue
        pname, line_bufs, meta = "", [], {}
        for f2, v2 in _pb_fields(plane_buf):
            if f2 == 2:
                pname = v2.decode("utf-8", "replace")
            elif f2 == 3:
                line_bufs.append(v2)
            elif f2 == 4:  # map<int64, XEventMetadata>
                key, name = None, ""
                for f3, v3 in _pb_fields(v2):
                    if f3 == 1:
                        key = v3
                    elif f3 == 2:
                        for f4, v4 in _pb_fields(v3):
                            if f4 == 2 and not name:
                                name = v4.decode("utf-8", "replace")
                            elif f4 == 4:  # display_name wins
                                name = v4.decode("utf-8", "replace")
                if key is not None:
                    meta[key] = name
        lines = []
        for lb in line_bufs:
            lname, ldisp, events = "", "", []
            for f3, v3 in _pb_fields(lb):
                if f3 == 2:
                    lname = v3.decode("utf-8", "replace")
                elif f3 == 11:
                    ldisp = v3.decode("utf-8", "replace")
                elif f3 == 4:
                    mid = dur_ps = 0
                    for f4, v4 in _pb_fields(v3):
                        if f4 == 1:
                            mid = v4
                        elif f4 == 3:
                            dur_ps = v4
                    events.append((meta.get(mid, f"op-{mid}"), dur_ps / 1e9))
            lines.append((ldisp or lname, events))
        planes.append((pname, lines))
    return planes


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
                        (ev.name, (ev.end_ns - ev.start_ns) / 1e6)
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
    ``[(plane_name, [(line_name, [(event_name, dur_ms), ...]), ...])]``,
    via ``ProfileData``, or the wire reader on a host without JAX."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    try:
        from jax.profiler import ProfileData  # noqa: F401
    except ImportError:
        return _read_xplane_wire(path)
    return _read_xplane_profiledata(path)


# Host-plane lines that carry XLA op execution when there is no device
# plane (CPU backend): the thread-pool lines the CPU client names
# tf_XLAEigen/... and tf_XLATfrtCpuClient/....  Runtime bookkeeping
# events on those lines are filtered by name.
_HOST_XLA_LINE = re.compile(r"^tf_XLA")
_HOST_NOISE = re.compile(
    r"ThreadpoolListener|ThunkExecutor|^\$|^Execute$|Infeed|Outfeed"
)


def _op_events(planes):
    """(event_name, dur_ms) pairs of executed XLA ops: the device planes'
    sync-op line, or the host XLA thread-pool lines when no device plane
    exists (CPU traces)."""
    out = []
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        for lname, events in lines:
            if lname == "XLA Ops":
                out.extend(events)
    if out:
        return out
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for lname, events in lines:
            if _HOST_XLA_LINE.search(lname):
                out.extend(
                    (n, d) for n, d in events if not _HOST_NOISE.search(n)
                )
    return out


def analyze(trace_dir: str):
    """Aggregate a captured trace.  Returns (per_op ms, per_op counts,
    async-DMA busy ms, XLA-module ms) — all totals over the traced steps."""
    planes = read_trace(trace_dir)

    per_op: dict[str, float] = collections.defaultdict(float)
    per_op_count: dict[str, int] = collections.defaultdict(int)
    async_ms = 0.0
    module_ms = 0.0
    for pname, lines in planes:
        if not pname.startswith("/device:"):
            continue
        for lname, events in lines:
            if lname == "XLA Modules":
                module_ms += sum(d for _, d in events)
            if lname == "Async XLA Ops":
                async_ms += sum(d for _, d in events)
    for name, dur in _op_events(planes):
        per_op[name] += dur
        per_op_count[name] += 1
    return per_op, per_op_count, async_ms, module_ms


def op_digest(trace_dir: str, top: int = 8) -> dict:
    """Compact per-op-category device-time summary of a captured trace —
    the payload ``profile_capture`` events carry so a throughput anomaly
    is explainable from the event stream alone.  ``{"total_ms", "ops":
    {category: ms (top N)}, "top_op": name}``; ms totals are over the
    whole traced window."""
    per_op, _counts, _async_ms, module_ms = analyze(trace_dir)
    cats: dict[str, float] = collections.defaultdict(float)
    for name, ms in per_op.items():
        op = opcode_of(name)
        cats[CATEGORY.get(op, f"other ({op})")] += ms
    ranked = sorted(cats.items(), key=lambda kv: -kv[1])
    top_op = max(per_op.items(), key=lambda kv: kv[1])[0] if per_op else None
    return {
        "total_ms": round(sum(per_op.values()), 3),
        "module_ms": round(module_ms, 3),
        "ops": {k: round(v, 3) for k, v in ranked[:top]},
        "top_op": top_op[:140] if top_op else None,
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
