"""Per-layer metrics that join the device trace with the program's own
scope table: which instruction of the compiled step is forward, backward,
a kernel, the update (``ddl_tpu/obs/scope.py``).  The table is read
in-process from ``ddl_tpu.obs.hbm``, where ``plan_program`` keeps it, as
``compile_s`` reads ``jax.monitoring``.  A program that makes no table
(an older one, ``DDL_HBM_PLAN=aval``) gives None, never 0."""

from __future__ import annotations

from benchmark import trace as tr

# A table that names less than this share of the traced device-op seconds
# does not describe the trace (another program ran, or the step's ops sit
# in called computations): no number.
MIN_COVERAGE = 0.95


def _table(label: str):
    from ddl_tpu.obs import hbm

    get = getattr(hbm, "scope_table", None)
    return get(label) if get is not None else None


def _by_tag(ctx, label: str):
    """``{"coverage", "seconds": {tag: s}}`` of the traced device ops
    joined with the table by instruction name, once a run; None without a
    table or a trace."""
    cache = ctx.setdefault("_scope", {})
    if label not in cache:
        t, table, joined = ctx.get("trace"), _table(label), None
        if t is not None and t.ops and table:
            seconds: dict = {}
            total = named = 0.0
            for name, s, e in t.all_ops():
                total += e - s
                tag = table.get(tr.own_name(name))
                if tag is not None:
                    named += e - s
                    seconds[tag] = seconds.get(tag, 0.0) + (e - s)
            if total > 0:
                joined = {"coverage": named / total, "seconds": seconds}
        cache[label] = joined
    return cache[label]


def scope_ms(ctx, params):
    """Device milliseconds a step of the traced ops whose tag is one of
    ``params["tags"]``, averaged over chips.  A listed kernel that the
    trace does not show (renamed, fused away, split) gives None and a
    note, never the sum of the others; a direction that took no device
    time reads 0."""
    traced = ctx.get("traced")
    joined = _by_tag(ctx, params["label"])
    if joined is None or not traced or not traced["steps"]:
        return None
    chips = max(1, len(ctx["trace"].ops))
    per_step = 1e3 / chips / traced["steps"]
    seconds = joined["seconds"]
    notes = ctx.setdefault("notes", {})
    notes[f"scope.{params['label']}"] = {
        "coverage": joined["coverage"],
        "ms_per_step": {k: v * per_step for k, v in sorted(seconds.items())},
    }
    if joined["coverage"] < MIN_COVERAGE:
        return None
    missing = [t for t in params["tags"] if t.startswith("kernel/") and t not in seconds]
    if missing:
        notes[f"scope.{params['label']}.missing"] = missing
        return None
    return sum(seconds.get(tag, 0.0) for tag in params["tags"]) * per_step
