"""Per-layer metrics read from the profiler's device trace.  A reader
that finds nothing to read returns None, never 0."""

from __future__ import annotations

from benchmark import trace as tr


def _traced_steps(ctx):
    return ctx["traced"]["steps"] if ctx.get("traced") else 0


def device_step_ms(ctx, params):
    """Device-busy time a step in the traced stretch (the union of the
    device's op intervals over its steps)."""
    t = ctx.get("trace")
    if t is None or not t.ops or not _traced_steps(ctx):
        return None
    return 1e3 * t.busy_s() / _traced_steps(ctx)


def step_mfu(ctx, params):
    """Required forward+backward FLOPs a step over the device-busy time a
    step of the traced stretch and the chips' peak: the share of the peak
    that the step programs reach while the device runs them.  The host's
    stalls do not move it; ``device_idle_pct`` carries those.
    Recomputation is not counted."""
    step_ms, work = device_step_ms(ctx, params), ctx.get("work")
    if step_ms is None or work is None:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * work.train_step_flops(ctx["shapes"]) / (step_ms * 1e-3) / peak


def kernel_roofline(ctx, params):
    """The least time the chip could take for the kernel's required
    operations and bytes (the larger of the two bounds) over the time its
    events took in the trace."""
    t, work = ctx.get("trace"), ctx.get("work")
    if t is None or work is None or not _traced_steps(ctx):
        return None
    fn = getattr(work, params["work"], None)
    if fn is None:
        return None
    events = tr.match_events(t.all_ops(), params["match"])
    seconds = sum(e - s for _, s, e in events)
    if seconds <= 0:
        return None
    need = fn(ctx["shapes"])
    chips = max(1, len(t.ops))
    per_step = seconds / chips / _traced_steps(ctx)
    bound_flops = need["flops"] / ctx["peak"]["bf16_flops_per_s"]
    bound_bytes = need["bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})[params.get("note", "kernel")] = {
        "bound": "flops" if bound_flops >= bound_bytes else "bytes",
        "kernel_ms_per_step": 1e3 * per_step,
        "events_per_step": len(events) / chips / _traced_steps(ctx),
    }
    return 100.0 * max(bound_flops, bound_bytes) / per_step
