"""From a profiler trace and the program's spans to numbers.

Pure functions over plain lists, so the arithmetic is checked on a
hand-built trace (``tests/benchmark``); ``load`` is the only part that
touches ``jax.profiler.ProfileData``.  Seconds everywhere, on the trace's
own clock; ``Trace.to_trace_clock`` maps a wall-clock time (the program's
span events carry ``time.time()``) onto it through the anchor annotation
the harness wraps around the window.

The parts of the reading taken from the program's ``bench/xprof.py`` (the
device plane's ``XLA Ops`` line, ``opcode_of``) are copies: the yardstick
lives here, where a later PR cannot move it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = [
    "ANCHOR", "Trace", "busy_union", "clip", "gaps", "attribute_gaps",
    "busy_inside", "match_events", "opcode_of", "own_name", "load", "top_ops",
]

ANCHOR = "bench_window"

_OPCODE_RX = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def own_name(name: str) -> str:
    """The instruction's own name: ``%attn.45 = (...) custom-call(...)``
    -> ``attn.45``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def opcode_of(name: str) -> str:
    """The HLO opcode in a profiler op-event name: the first ``word(``
    after the ``=`` that a blank precedes (the types before it hold
    ``T(8,128)`` and ``S(1)``, which no blank precedes); for a bare name
    such as ``fusion.123`` its stem.  A fusion carries its kind
    (``fusion:Loop``), as the program's ``xprof.opcode_of`` has it."""
    head, sep, rest = name.partition(" = ")
    m = _OPCODE_RX.search(" " + rest) if sep else None
    op = m.group(1) if m else own_name(head).split(".")[0]
    if op == "fusion" and (kind := re.search(r"kind=k(\w+)", name)):
        return f"fusion:{kind.group(1)}"
    return op


def clip(intervals, lo: float, hi: float):
    """Intervals cut to [lo, hi]; the empty ones dropped."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_union(intervals) -> float:
    """Seconds covered by at least one interval."""
    return sum(e - s for s, e in _merged(intervals))


def gaps(intervals, lo: float, hi: float):
    """The idle stretches of [lo, hi]: where no interval runs."""
    out, at = [], lo
    for s, e in _merged(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def busy_inside(intervals, lo: float, hi: float) -> float:
    return busy_union(clip(intervals, lo, hi))


def attribute_gaps(idle, spans):
    """Charge each idle stretch to what the host was doing in it.

    ``spans``: [(name, start, end)] on the same clock; where spans nest or
    overlap the latest-started one wins.  Returns ``({name: seconds},
    [(name, seconds) per gap by its largest holder])``; time under no span
    goes to ``"untraced"``."""
    totals: dict[str, float] = {}
    per_gap = []
    spans = sorted(spans, key=lambda s: s[1])
    for gs, ge in idle:
        cuts = {gs, ge}
        for _, s, e in spans:
            if e > gs and s < ge:
                cuts.update((max(s, gs), min(e, ge)))
        cuts = sorted(cuts)
        held: dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            owner = "untraced"
            for name, s, e in spans:
                if s <= mid < e:
                    owner = name  # later-started spans overwrite
            held[owner] = held.get(owner, 0.0) + (b - a)
        for name, sec in held.items():
            totals[name] = totals.get(name, 0.0) + sec
        if held:
            name = max(held, key=held.get)
            per_gap.append((name, ge - gs))
    return totals, per_gap


def match_events(events, pattern: str):
    """The op events whose own name or opcode matches ``pattern`` (a
    regex); the operands named in an event's text do not count."""
    rx = re.compile(pattern)
    return [ev for ev in events
            if rx.search(own_name(ev[0])) or rx.search(opcode_of(ev[0]))]


def top_ops(events, n: int = 10):
    """[(name, seconds)] of the ops that took most device time, grouped
    by opcode as the program's ``xprof.opcode_of`` groups them, with the
    heaviest single op's name kept where a group is one op."""
    by: dict[str, float] = {}
    for name, s, e in events:
        by[opcode_of(name)] = by.get(opcode_of(name), 0.0) + (e - s)
    return sorted(by.items(), key=lambda kv: -kv[1])[:n]


@dataclasses.dataclass
class Trace:
    """One traced window, reduced to lists.

    ``ops``: {device plane: [(name, start, end)]} of the ``XLA Ops`` line;
    ``modules``: the same for ``XLA Modules`` (one event per program run);
    ``anchor``: (start, end) of the harness's window annotation, or None;
    ``anchor_wall``: the wall-clock time taken inside the anchor's start.
    """

    ops: dict
    modules: dict
    anchor: tuple | None = None
    anchor_wall: float | None = None

    def window(self):
        if self.anchor is not None:
            return self.anchor
        starts = [e[1] for evs in self.ops.values() for e in evs]
        ends = [e[2] for evs in self.ops.values() for e in evs]
        if not starts:
            return (0.0, 0.0)
        return (min(starts), max(ends))

    def to_trace_clock(self, wall: float) -> float | None:
        if self.anchor is None or self.anchor_wall is None:
            return None
        return self.anchor[0] + (wall - self.anchor_wall)

    def intervals(self, plane: str):
        return [(s, e) for _, s, e in self.ops[plane]]

    def busy_s(self) -> float:
        """Device-busy seconds inside the window, averaged over chips."""
        lo, hi = self.window()
        if not self.ops:
            return 0.0
        per = [busy_inside(self.intervals(p), lo, hi) for p in self.ops]
        return sum(per) / len(per)

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def all_ops(self):
        lo, hi = self.window()
        return [
            (n, max(s, lo), min(e, hi))
            for evs in self.ops.values() for n, s, e in evs
            if e > lo and s < hi
        ]


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, anchor = {}, {}, None
    names: dict = {}  # one string an op, not one an event: an HLO text is long
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = []
                    for ev in line.events:
                        n = ev.name
                        evs.append((names.setdefault(n, n), ev.start_ns / 1e9,
                                    (ev.start_ns + ev.duration_ns) / 1e9))
                    (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = (ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
    return Trace(ops=ops, modules=modules, anchor=anchor)
