"""Per-layer metrics whose rate comes from the host's clock around the
untraced window of the same run."""

from __future__ import annotations

from benchmark.readers.device_trace import device_step_ms


def device_idle_pct(ctx, params):
    """The device's idle share at the untraced window's rate: 1 - (busy
    time a step, from the trace) x (steps a second of the untraced window,
    by the host's clock).  A host stall moves it, as it should.  The traced
    stretch's own idle share holds the tracer's cost; it is in
    ``device.busy_s``/``window_s`` and on an earlier line."""
    step_ms, win = device_step_ms(ctx, params), ctx.get("window")
    if step_ms is None or not win or win["elapsed"] <= 0 or not win["steps"]:
        return None
    return 100.0 * (1.0 - step_ms * 1e-3 * win["steps"] / win["elapsed"])
