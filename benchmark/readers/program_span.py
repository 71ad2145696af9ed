"""Per-layer metrics read from the program's own phase spans (``obs``:
``data_wait``, ``h2d``, ``step``, ``fence``; host clock)."""

from __future__ import annotations

from benchmark import trace as tr


def phase_share_pct(ctx, params):
    """Seconds of the named phases inside the window over the window."""
    win = ctx.get("window")
    spans = ctx.get("spans")
    if not win or not spans:
        return None
    lo, hi = win["wall_start"], win["wall_end"]
    names = set(params["phases"])
    got = [s for s in spans if s[0] in names and s[2] > lo and s[1] < hi]
    if not got:
        return None
    sec = sum(min(e, hi) - max(s, lo) for _, s, e in got)
    return 100.0 * sec / (hi - lo)


def fence_after_idle_pct(ctx, params):
    """Period-end host time in the untraced window: each ``fence`` span's
    seconds minus the device work still outstanding when it began.  A
    period's steps are dispatched without waiting, so at the fence's start
    the device still owes (steps x busy time a step, from the trace) less
    the time the loop took; what the fence lasts beyond that is host work
    with the device idle: copies to the host and metric arithmetic."""
    win, spans, t = ctx.get("window"), ctx.get("spans"), ctx.get("trace")
    traced = ctx.get("traced")
    if not win or not spans or t is None or not t.ops or not traced or not traced["steps"]:
        return None
    step_s = t.busy_s() / traced["steps"]
    lo, hi = win["wall_start"], win["wall_end"]
    inside = sorted((s for s in spans if s[1] >= lo and s[2] <= hi), key=lambda s: s[1])
    total, seen, loop_start, steps = 0.0, False, lo, 0
    for name, s, e in inside:
        if name == params.get("step_phase", "step"):
            steps += 1
        elif name == params.get("phase", "fence"):
            owed = max(0.0, steps * step_s - (s - loop_start))
            total += max(0.0, (e - s) - owed)
            seen, loop_start, steps = True, e, 0
    if not seen:
        return None
    return 100.0 * total / (hi - lo)
