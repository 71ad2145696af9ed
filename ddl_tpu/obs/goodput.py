"""Goodput ledger: end-to-end chip-time accounting with badput
attribution.

The rest of the obs stack answers "how fast was a step" — this module
answers the question fleet operation actually bills by: **of every
chip-second a job consumed, how much was productive training, and where
did the rest go?**  Every input already rides the event stream (phase
spans folded into ``period`` events, ``compile_s``, ``snapshot_restore``
and ``rollback`` cursors, ``restart_latency`` decision stamps,
``coord_barrier`` waits, ``stall`` ages, the ``pipe_schedule`` bubble
model, ``decode`` activity); the fold engine reduces them per
(host, repoch) incarnation (``obs/fold.StreamFold.goodput``), and this
module turns those reductions into an **exhaustive, sums-to-total
account** rendered by ``ddl_tpu obs goodput`` and re-used verbatim by
``obs summarize`` / ``watch`` / ``export`` / ``fleet`` / the
``obs diff --fail-goodput-drop`` CI gate — one fold, one set of
numbers.

Bucket taxonomy (``CATEGORIES``; seconds, per incarnation):

    productive    step + fence phase time, minus the carve-outs below —
                  the compiled program actually advancing the model
    data_wait     host-side batch production
    h2d           host-to-device transfer / global-array assembly
    recompile     XLA backend compile seconds (``compile_s``), carved
                  out of step time (compiles block the dispatch)
    bubble        modeled pipeline-bubble fraction x remaining step
                  time (``pipe_schedule``; 0 for unpipelined runs)
    rolled_back   step time whose ground a later rollback / restore
                  cursor re-ran (wasted work; see precedence below)
    checkpoint    snapshot saves (phase) + startup/rollback restores
    eval / logging  their phases
    stall         watchdog-detected hung time (the wedged phase never
                  emits a span, so the stall age is its only record)
    barrier       pod join-barrier waits for this incarnation's epoch
    restart_gap   relaunch decision -> first event of the incarnation
                  (minus the barrier wait inside it), plus dead gaps
                  between same-repoch attempts
    startup       the incarnation's ``setup.*`` spans (``obs/steptrace``):
                  from the process's start to its first trainer stage
                  (``setup.boot``), building the model and its state, the
                  data, and each program's ``hbm_plan``.  They lie outside
                  every phase; the incarnation's window begins where its
                  ``setup.boot`` does, so the seconds before the stream's
                  first event are inside the wall they are carved from
    serve         serving activity window (decode requests)
    other         phase names outside the fixed vocabulary
    untracked     the residual — wall minus everything above.  Reported,
                  never dropped: it is what keeps the ledger honest
                  (what a start does between its stages, idle gaps; process
                  boot, imports and model build too in a stream from
                  before the ``setup.*`` spans).

Precedence for overlapping attributions (documented contract, see
ARCHITECTURE.md "Goodput accounting"): within step+fence time,
``rolled_back`` is carved first (a replayed period's compile/bubble was
wasted too), then ``recompile``, then ``bubble``; the restart-gap
envelope yields to the barrier wait measured inside it.  Each
incarnation's wall clock starts at its restart DECISION when one is on
record (``restart_latency.decision_ts``) — the relaunch gap belongs to
the incarnation it produced — else at its first event.

Since the fold's per-tenant attribution layer (sidecar v9) the job row
also carries a ``tenants`` account: per tenant, chip-seconds split into
served (decode durations), queued (lane waits) and modeled shed cost,
plus admit/shed/retire counts and availability (1 - shed rate) — the
inputs ``obs/slo.py`` evaluates error budgets over and ``obs fleet``
renders per-tenant columns from.

Pure stdlib over the fold state — no JAX, no stream re-read.
"""

from __future__ import annotations

__all__ = [
    "CATEGORIES",
    "dominant_badput",
    "ledger_from_fold",
    "render_goodput",
    "tenant_dominant_badput",
]

CATEGORIES = (
    "productive", "data_wait", "h2d", "recompile", "bubble",
    "rolled_back", "checkpoint", "eval", "logging", "stall", "barrier",
    "restart_gap", "startup", "serve", "other", "untracked",
)

# period-event phase names with a dedicated bucket; step+fence form the
# productive pool, anything else lands in "other"
_DIRECT_PHASES = ("data_wait", "h2d", "eval", "logging", "checkpoint")


def _incarnation_account(
    g: dict, barrier_s: float, bubble_fraction: float | None
) -> dict | None:
    """One (host, repoch) incarnation's sums-to-total account from its
    fold reduction ``g`` (``fold._new_goodput`` shape)."""
    first, last = g.get("first_ts"), g.get("last_ts")
    if first is None or last is None:
        return None
    dts = g.get("decision_ts")
    start = min(first, dts) if dts is not None else first
    wall = max(0.0, last - start)

    phases = g.get("phases") or {}
    sec = {c: 0.0 for c in CATEGORIES}
    for name in _DIRECT_PHASES:
        sec[name] = phases.get(name, 0.0)
    sec["other"] = sum(
        d for n, d in phases.items()
        if n not in _DIRECT_PHASES and n not in ("step", "fence")
    )
    sec["checkpoint"] += g.get("restore_s", 0.0)

    # productive pool with ordered carve-outs (see module docstring)
    step_fence = phases.get("step", 0.0) + phases.get("fence", 0.0)
    rolled = min(g.get("rolled_back_s", 0.0), step_fence)
    remaining = step_fence - rolled
    recompile = min(g.get("compile_s", 0.0), remaining)
    remaining -= recompile
    bubble = (bubble_fraction or 0.0) * remaining
    sec["rolled_back"] = rolled
    sec["recompile"] = recompile
    sec["bubble"] = bubble
    sec["productive"] = remaining - bubble

    sec["stall"] = g.get("stall_s", 0.0)
    # the pre-window gap (decision -> first event) envelopes the join
    # barrier measured inside it; the barrier keeps its own bucket and
    # the envelope yields
    pre_gap = max(0.0, first - start)
    barrier = min(max(0.0, barrier_s), pre_gap) if pre_gap else 0.0
    sec["barrier"] = barrier
    sec["restart_gap"] = (pre_gap - barrier) + g.get("gap_s", 0.0)
    sec["startup"] = g.get("startup_s", 0.0)
    if g.get("serve_t0") is not None and g.get("serve_t1") is not None:
        sec["serve"] = max(0.0, g["serve_t1"] - g["serve_t0"])

    attributed = sum(v for c, v in sec.items() if c != "untracked")
    sec["untracked"] = wall - attributed
    return {
        "start_ts": start, "end_ts": last, "wall_s": wall,
        "seconds": sec,
        "ratio": (sec["productive"] / wall) if wall > 0 else None,
        # the set-up line's sums (fold.StreamFold._consume_setup_span)
        "setup": dict(sorted((g.get("setup") or {}).items())),
        # per-tenant chip-second split inside this incarnation's serve
        # window (fold._new_tenant_goodput shape); sorted so the account
        # is byte-stable across fold resumes
        "tenants": {
            t: dict(v)
            for t, v in sorted((g.get("tenants") or {}).items())
        },
    }


def dominant_badput(seconds: dict) -> tuple[str, float] | None:
    """The largest non-productive bucket ``(category, seconds)``, or
    None when nothing was lost.  Ties break by CATEGORIES order so the
    answer is deterministic."""
    best = None
    for cat in CATEGORIES:
        if cat == "productive":
            continue
        v = seconds.get(cat, 0.0)
        if v > 0 and (best is None or v > best[1]):
            best = (cat, v)
    return best


def tenant_dominant_badput(row: dict) -> tuple[str, float] | None:
    """A tenant's largest lost-chip-time bucket — ``("queued", s)`` or
    ``("shed", s)`` from its ledger row — or None when nothing was lost.
    Ties break queued-first for determinism (mirrors
    ``dominant_badput``'s CATEGORIES-order rule)."""
    best = None
    for cat in ("queued", "shed"):
        v = float(row.get(cat + "_s", 0.0) or 0.0)
        if v > 0 and (best is None or v > best[1]):
            best = (cat, v)
    return best


def ledger_from_fold(fold) -> dict:
    """The job's full goodput ledger from a ``JobFold``:

    ``{"incarnations": [{host, repoch, start_ts, end_ts, wall_s,
    seconds, ratio}, ...], "job": {wall_s, seconds, ratio,
    dominant_badput}}``

    Incarnations are per (stream host, repoch), sorted.  The job row is
    the chip-time sum over every host: each host contributes its whole
    stream's wall span (supervisor coordination included), incarnation
    buckets sum, unmatched barrier waits (the start barrier, epochs
    without an account) land in ``barrier``, and the job residual —
    inter-incarnation slack the per-incarnation windows do not cover —
    lands in ``untracked``."""
    bubble = None
    ps = fold.pipe_schedule()
    if ps is not None:
        bubble = ps.get("bubble_fraction")

    incarnations = []
    job = {c: 0.0 for c in CATEGORIES}
    job_wall = 0.0
    tenants: dict[str, dict] = {}

    def _trow(t: str) -> dict:
        row = tenants.get(t)
        if row is None:
            row = tenants[t] = {
                "served_s": 0.0, "queued_s": 0.0, "shed_s": 0.0,
                "admits": 0, "sheds": 0, "retires": 0,
                "availability": None, "ratio": None, "class": None,
            }
        return row

    for name in sorted(fold.streams):
        sf = fold.streams[name]
        if sf.host is None:
            continue
        matched_barriers = set()
        host_attr = 0.0  # attributed seconds, untracked excluded
        host_inc_walls = 0.0
        for repoch in sorted(sf.goodput):
            bname = f"e{repoch}-join"
            barrier_s = sf.barrier_waits.get(bname, 0.0) if repoch else 0.0
            if repoch:
                matched_barriers.add(bname)
            acc = _incarnation_account(
                sf.goodput[repoch], barrier_s, bubble
            )
            if acc is None:
                continue
            acc["host"] = sf.host
            acc["repoch"] = repoch
            incarnations.append(acc)
            host_inc_walls += acc["wall_s"]
            for c, v in acc["seconds"].items():
                if c != "untracked":
                    job[c] += v
                    host_attr += v
            for t, tg in acc["tenants"].items():
                row = _trow(t)
                row["served_s"] += tg.get("served_s", 0.0)
                row["queued_s"] += tg.get("queued_s", 0.0)
        # stream-level per-tenant request counters (fold.tenant_serve;
        # authoritative for counts — the per-repoch split above only
        # covers events stamped with an incarnation)
        for t, tc in getattr(sf, "tenant_serve", {}).items():
            row = _trow(t)
            row["admits"] += tc.get("admit", 0)
            row["sheds"] += tc.get("shed", 0)
            row["retires"] += tc.get("retire", 0)
        # job-level extras this host carries: barrier waits no
        # incarnation claimed (the start barrier, join epochs without a
        # trainer window)
        extra_barrier = sum(
            w for n, w in sf.barrier_waits.items()
            if n not in matched_barriers
        )
        job["barrier"] += extra_barrier
        host_attr += extra_barrier
        span = getattr(sf, "all_span", [None, None])
        if span[0] is not None and span[1] is not None:
            # never let the job wall undercut the incarnation accounts
            # it must contain (a decision stamp from another clock can
            # precede the stream's first event)
            host_wall = max(0.0, span[1] - span[0], host_inc_walls)
            job_wall += host_wall
            job["untracked"] += host_wall - host_attr
    # finalize the per-tenant account: availability is the admitted
    # fraction of the tenant's offered load (1 - shed rate); shed_s is
    # MODELED — shed requests never ran, so their cost is estimated at
    # the tenant's own mean served duration (0 when nothing retired);
    # ratio is the tenant's goodput analogue, served over
    # served+queued+shed chip-seconds.  Priority class comes from the
    # serving digests (the one place the tag is max-reduced).
    classes: dict[str, str | None] = {}
    serving = getattr(fold, "serving", None)
    if callable(serving):
        for t, tb in serving().tenants.items():
            classes[t] = tb.get("class")
    for t in sorted(tenants):
        row = tenants[t]
        offered = row["admits"] + row["sheds"]
        if offered > 0:
            row["availability"] = row["admits"] / offered
        mean_served = (
            row["served_s"] / row["retires"] if row["retires"] else 0.0
        )
        row["shed_s"] = row["sheds"] * mean_served
        denom = row["served_s"] + row["queued_s"] + row["shed_s"]
        if denom > 0:
            row["ratio"] = row["served_s"] / denom
        row["class"] = classes.get(t)
    job_row = {
        "wall_s": job_wall,
        "seconds": job,
        "ratio": (job["productive"] / job_wall) if job_wall > 0 else None,
        "dominant_badput": dominant_badput(job),
        "tenants": {t: tenants[t] for t in sorted(tenants)},
    }
    incarnations.sort(key=lambda a: (a["host"], a["repoch"]))
    return {"incarnations": incarnations, "job": job_row}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------


def _fmt_s(v: float) -> str:
    return f"{v:.2f}"


def render_goodput(ledger: dict, job_id: str = "") -> str:
    """The ``obs goodput`` report: a job headline plus one column per
    incarnation and a summed job column, rows = buckets.  Every column
    sums to its wall clock by construction (the residual is the
    ``untracked`` row)."""
    incs = ledger["incarnations"]
    job = ledger["job"]
    lines = [f"== goodput — {job_id} ==" if job_id else "== goodput =="]
    ratio = job["ratio"]
    head = (
        f"chip-time: {job['wall_s']:.1f}s over "
        f"{len(incs)} incarnation(s) | productive: "
        + (f"{ratio:.1%}" if ratio is not None else "n/a")
    )
    dom = job.get("dominant_badput")
    if dom:
        cat, s = dom
        share = s / job["wall_s"] if job["wall_s"] else 0.0
        head += f" | top badput: {cat} {s:.1f}s ({share:.1%})"
    lines.append(head)

    cols = [(a, f"h{a['host']}/e{a['repoch']}") for a in incs]
    width = max([10] + [len(lbl) + 1 for _, lbl in cols])
    header = f"{'category':<12}" + "".join(
        f"{lbl:>{width}}" for _, lbl in cols
    ) + f"{'job':>{width}}"
    lines.append(header)
    for cat in CATEGORIES:
        row = f"{cat:<12}"
        for a, _lbl in cols:
            row += f"{_fmt_s(a['seconds'][cat]):>{width}}"
        row += f"{_fmt_s(job['seconds'][cat]):>{width}}"
        lines.append(row)
    row = f"{'wall':<12}"
    for a, _lbl in cols:
        row += f"{_fmt_s(a['wall_s']):>{width}}"
    row += f"{_fmt_s(job['wall_s']):>{width}}"
    lines.append(row)
    row = f"{'goodput':<12}"
    for a, _lbl in cols:
        cell = f"{a['ratio']:.1%}" if a["ratio"] is not None else "-"
        row += f"{cell:>{width}}"
    row += f"{ratio:>{width}.1%}" if ratio is not None else f"{'-':>{width}}"
    lines.append(row)

    tenants = job.get("tenants") or {}
    if tenants:
        lines.append("per-tenant chip-seconds (shed modeled at mean served):")
        lines.append(
            f"  {'tenant':<14}{'class':<14}{'served':>9}{'queued':>9}"
            f"{'shed':>9}{'avail':>8}{'goodput':>9}{'reqs':>7}"
        )
        for t in sorted(tenants):
            r = tenants[t]
            avail = (
                f"{r['availability']:.1%}"
                if r["availability"] is not None else "-"
            )
            gp = f"{r['ratio']:.1%}" if r["ratio"] is not None else "-"
            lines.append(
                f"  {t:<14}{(r['class'] or '-'):<14}"
                f"{_fmt_s(r['served_s']):>9}{_fmt_s(r['queued_s']):>9}"
                f"{_fmt_s(r['shed_s']):>9}{avail:>8}{gp:>9}"
                f"{r['admits']:>7}"
            )
    return "\n".join(lines)
