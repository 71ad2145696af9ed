"""Per-layer metrics of set-up, read from the program's own spans of it
(``obs``: the stages ``setup.boot`` / ``setup.model`` / ``setup.data`` /
``setup.plan``, and ``compile.trace`` / ``compile.lower`` /
``compile.backend`` for every trace, lowering and backend compile of the
process; host clock).

Set-up, for these readers, is ``[setup.boot's start, window.wall_start]``:
from the process's start as the kernel recorded it to the window's first
call.  A span counts by its part inside that, and spans that nest or
overlap count once (a union).  A program that writes no ``setup.boot`` (one
from before these spans) gives no number, under any of the names.
"""

from __future__ import annotations

from benchmark import trace as tr

__all__ = ["span_s", "seen_pct"]


def _setup(ctx):
    """``(lo, hi)`` of set-up on the spans' clock, or None."""
    win, spans = ctx.get("window"), ctx.get("spans")
    if not win or not spans:
        return None
    boots = [s for name, s, _ in spans if name == "setup.boot"]
    if not boots or win["wall_start"] <= min(boots):
        return None
    lo, hi = min(boots), win["wall_start"]
    ctx.get("notes", {})["setup_from_boot_s"] = hi - lo
    return lo, hi


def _covered(ctx, params, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] under at least one span named in
    ``params["spans"]`` or beginning with one of ``params["prefixes"]``."""
    names = set(params.get("spans", ()))
    prefixes = tuple(params.get("prefixes", ()))
    held = [(s, e) for name, s, e in ctx["spans"]
            if name in names or (prefixes and name.startswith(prefixes))]
    return tr.busy_inside(held, lo, hi)


def span_s(ctx, params):
    """Seconds of set-up under the named spans; 0 where set-up was
    recorded and none of them ran in it."""
    setup = _setup(ctx)
    if setup is None:
        return None
    return _covered(ctx, params, *setup)


def seen_pct(ctx, params):
    """The share of set-up under any of the named spans: the coverage of
    the program's account of its own start.  At most 100 by construction
    (a union, cut to set-up, over set-up)."""
    setup = _setup(ctx)
    if setup is None:
        return None
    lo, hi = setup
    return 100.0 * _covered(ctx, params, lo, hi) / (hi - lo)
