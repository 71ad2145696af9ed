"""HBM ledger: exhaustive per-device memory accounting.

The goodput ledger (obs/goodput.py) made chip-*time* decision-grade;
this module does the same for chip-*memory*.  Three event kinds carry
the raw material (obs/events.py):

``hbm_sample``
    A periodic live breakdown: per-device bytes for each tracked
    category (params, optimizer state, the serving KV pool split into
    cached/private/free blocks) plus the live watermark
    (``utils/memory.hbm_stats``).  On backends without memory stats
    (CPU simulation) the watermark is synthesized as the tracked sum
    plus any injected leak (``synthetic: true``) so the account stays
    exercisable end-to-end off-TPU.

``hbm_plan``
    A per-program static budget stamped at compile time from the
    compiled executable's memory analysis (argument/output/temp/code
    bytes — the run-time continuation of ``analysis/hlolint.py``'s
    lint-time memory inventory), degrading to pure aval arithmetic when
    the runtime exposes no analysis.  The same executable's text gives
    the program's scope table (``obs/scope.py``: which instruction is
    forward, backward, a kernel, the update); the event carries its
    per-tag counts (``scope_counts``) and the name of the table's file
    beside the event file (``scope_file``), which ``obs hbm`` prints with
    the plan and ``bench digest`` loads.

``hbm_oom_dump``
    The forensic snapshot an allocation failure emits before the
    process dies — resident buffers aggregated by (shape, dtype), the
    tracked category bytes, and the recent plans that predicted them —
    the memory analogue of the watchdog's stack dump.

The fold (obs/fold.py) reduces these into a bounded per-(host, repoch)
cell; ``account_from_fold`` turns that into the sums-to-total account
``obs hbm`` renders: every tracked category at the peak-watermark
sample, plus an ``untracked`` residual against the watermark that is
REPORTED, never dropped (it may be negative when tracked buffers were
partially paged out or double-counted — an honest reconciliation signal
either way).  Like the rest of the obs read path, everything below the
emit helpers is pure stdlib.
"""

from __future__ import annotations

__all__ = [
    "CATEGORIES",
    "SAMPLE_FIELDS",
    "account_from_fold",
    "dump_oom",
    "is_oom_error",
    "live_sample",
    "plan_program",
    "render_hbm",
    "sample_categories",
    "scope_table",
    "scope_tables",
    "summary_from_fold",
    "top_consumers",
    "tree_shard_bytes",
]

# The account's fixed category vocabulary.  Order is the tie-break for
# top-consumer selection (deterministic renders).  ``untracked`` is the
# residual row — always last, always reported.
CATEGORIES = (
    "params",
    "optimizer",
    "kv_cached",
    "kv_private",
    "kv_free",
    "untracked",
)

# tracked category -> the hbm_sample event field carrying its bytes
SAMPLE_FIELDS = {
    "params": "params_bytes",
    "optimizer": "opt_bytes",
    "kv_cached": "kv_cached_bytes",
    "kv_private": "kv_private_bytes",
    "kv_free": "kv_free_bytes",
}

# static-plan byte fields carried by hbm_plan events
PLAN_FIELDS = (
    "analysis",
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "alias_bytes",
    "code_bytes",
    "scope_counts",
    "scope_file",
    "kernel_tiles",
)

# buffers retained in an OOM dump / plans retained per repoch cell —
# the forensic value is in the head of the sorted list, and the fold
# sidecar must stay bounded
MAX_OOM_BUFFERS = 24
MAX_PLANS = 64

# last-wins plan per label emitted by THIS process — what dump_oom
# attaches so the forensic snapshot carries the budgets that predicted
# the resident buffers (bounded like the fold cell)
_recent_plans: dict[str, dict] = {}


def sample_categories(e: dict) -> dict:
    """Tracked category bytes present on one ``hbm_sample`` event."""
    out = {}
    for cat, field in SAMPLE_FIELDS.items():
        v = e.get(field)
        if v is not None:
            out[cat] = int(v)
    return out


# ---------------------------------------------------------------------------
# emit side (lazy jax imports only — the read path never touches these)
# ---------------------------------------------------------------------------


def tree_shard_bytes(tree) -> int | None:
    """Per-device bytes of a pytree of arrays: each leaf's actual shard
    shape (ZeRO/TP sharding reflected, like BaseTrainer's optimizer
    gauge) times its dtype width; None for an empty/None tree."""
    if tree is None:
        return None
    import math

    import jax

    total = 0
    seen = False
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        try:
            shard_shape = (
                sharding.shard_shape(shape)
                if sharding is not None else shape
            )
        except (TypeError, ValueError):
            shard_shape = shape
        total += math.prod(shard_shape) * dtype.itemsize
        seen = True
    return total if seen else None


def live_sample(
    writer,
    *,
    params_bytes: int | None = None,
    opt_bytes: int | None = None,
    kv_cached_bytes: int | None = None,
    kv_private_bytes: int | None = None,
    kv_free_bytes: int | None = None,
    step: int | None = None,
    context: str | None = None,
) -> dict | None:
    """Emit one ``hbm_sample``: the caller's tracked category bytes plus
    the live watermark.  Backends without memory stats get a synthetic
    watermark (tracked sum + injected leak) so the account — including
    the leak-growth gate — works on CPU simulation too."""
    if writer is None:
        return None
    from ddl_tpu.utils import faultinject
    from ddl_tpu.utils.memory import hbm_stats

    tracked = sum(
        v for v in (
            params_bytes, opt_bytes, kv_cached_bytes,
            kv_private_bytes, kv_free_bytes,
        ) if v
    )
    leaked = faultinject.leaked_bytes()
    mem = hbm_stats()
    if mem is not None:
        watermark = mem["bytes_in_use"]
        peak = mem["peak_bytes_in_use"]
        limit = mem["bytes_limit"] or None
        synthetic = False
    else:
        watermark = peak = tracked + leaked
        limit = None
        synthetic = True
    return writer.emit(
        "hbm_sample",
        step=step,
        watermark=int(watermark),
        peak=int(peak),
        limit=limit,
        synthetic=synthetic,
        params_bytes=params_bytes,
        opt_bytes=opt_bytes,
        kv_cached_bytes=kv_cached_bytes,
        kv_private_bytes=kv_private_bytes,
        kv_free_bytes=kv_free_bytes,
        **({"context": context} if context else {}),
    )


class _AvalOnly(Exception):
    """Internal: short-circuit plan_program to the aval budget."""


def _aval_bytes(x) -> int:
    import math

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * dtype.itemsize


def plan_program(
    writer, label: str, fn, args=(), kwargs=None,
    step: int | None = None, mode: str = "full", parts: dict | None = None,
) -> dict | None:
    """Emit one ``hbm_plan``: the static per-program memory budget for a
    jitted ``fn`` at these ``args``.  ``mode="full"`` compiles the
    program AOT and reads the executable's own memory analysis (one
    extra backend compile when the XLA compile caches are cold — the
    run-time continuation of hlolint's inventory); ``mode="aval"`` keeps
    the cheap shape-arithmetic budget (argument/output bytes, no temp).
    Either way degrades instead of raising — a budget that cannot be
    measured must not take the run down.  ``parts`` (``{scope name: tag}``,
    the caller's own scopes) asks for the second scope table, by part of
    the model (``obs/scope.parts_table``)."""
    if writer is None:
        return None
    kwargs = kwargs or {}
    analysis = "aval"
    arg_b = out_b = None
    temp_b = alias_b = code_b = None
    compiled = None
    try:
        import jax

        arg_b = sum(_aval_bytes(x) for x in jax.tree.leaves((args, kwargs)))
        out = jax.eval_shape(fn, *args, **kwargs)
        out_b = sum(_aval_bytes(x) for x in jax.tree.leaves(out))
    except Exception:
        pass
    try:
        if mode != "full":
            raise _AvalOnly
        compiled = fn.lower(*args, **kwargs).compile()
        ma = compiled.memory_analysis()
        if ma is not None:
            analysis = "memory_analysis"
            arg_b = int(getattr(ma, "argument_size_in_bytes", arg_b or 0))
            out_b = int(getattr(ma, "output_size_in_bytes", out_b or 0))
            temp_b = int(getattr(ma, "temp_size_in_bytes", 0))
            alias_b = int(getattr(ma, "alias_size_in_bytes", 0))
            code_b = int(
                getattr(ma, "generated_code_size_in_bytes", 0)
            )
    except Exception:
        pass
    plan = {
        "analysis": analysis,
        "argument_bytes": arg_b,
        "output_bytes": out_b,
        "temp_bytes": temp_b,
        "alias_bytes": alias_b,
        "code_bytes": code_b,
    }
    scope = _scope_of(writer, label, compiled, parts) if compiled is not None else None
    if len(_recent_plans) < MAX_PLANS or label in _recent_plans:
        # the table stays in-process (and in its file): 14,000 rows for
        # DenseNet121's step do not belong in a JSONL line
        _recent_plans[label] = {**plan, **(scope or {})}
    fields = {
        k: scope[k] for k in ("scope_counts", "scope_file", "kernel_tiles")
        if scope[k]
    } if scope else {}
    return writer.emit("hbm_plan", step=step, label=str(label), **plan, **fields)


def _scope_of(writer, label: str, compiled, parts=None) -> dict | None:
    """The compiled program's scope table (``obs/scope.py``), written
    beside the host's event file: ``{"scope": {...}, "scope_module",
    "scope_counts", "scope_file"}``, or None — like the budget, a table
    that cannot be made must not take the run down."""
    from ddl_tpu.obs import scope as sc

    try:
        text = compiled.as_text()
        module, table = sc.module_name(text), sc.scope_table(text)
        if not table:
            return None
        name = sc.write_table(writer.path.parent, writer.host, label, module, table)
        return {
            "parts": sc.parts_table(text, parts or {}),
            "scope": table,
            "scope_module": module,
            "scope_counts": sc.tag_counts(table),
            "scope_file": name,
            "kernel_tiles": sc.kernel_tiles(text),
        }
    except Exception:
        return None


def scope_table(label: str) -> dict | None:
    """``{instruction name: tag}`` of the program this process last
    planned under ``label``, or None (``mode="aval"``/``off``, no
    executable text, no plan yet).  ``<label>.parts`` is the same
    program's second table, by part of the model
    (``obs/scope.parts_table``); it is kept beside the direction table,
    which alone goes by module name in ``scope_tables``."""
    if label.endswith(".parts"):
        return _recent_plans.get(label[: -len(".parts")], {}).get("parts") or None
    return _recent_plans.get(label, {}).get("scope")


def scope_tables() -> dict[str, dict]:
    """``{HLO module name: table}`` of every program this process
    planned: what a digest of a profile of this process joins op events
    with, module by module (instruction names repeat from one program to
    the next).  Empty when no table was made."""
    return {
        plan["scope_module"]: plan["scope"]
        for plan in _recent_plans.values()
        if plan.get("scope")
    }


# OOM signatures across backends/versions; matched case-insensitively
# against the exception text (plus the RESOURCE_EXHAUSTED status name)
_OOM_MARKERS = (
    "resource_exhausted",
    "out of memory",
    "allocation failure",
    "failed to allocate",
    "oom",
)


def is_oom_error(exc: BaseException) -> bool:
    """Whether an exception looks like a device allocation failure."""
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(m in text for m in _OOM_MARKERS)


def dump_oom(
    writer,
    exc: BaseException,
    *,
    step: int | None = None,
    params_bytes: int | None = None,
    opt_bytes: int | None = None,
) -> dict | None:
    """Emit the ``hbm_oom_dump`` forensic snapshot: the failure text,
    the live watermark, every resident buffer aggregated by (shape,
    dtype) — top ``MAX_OOM_BUFFERS`` by bytes — and the static plans
    this process emitted.  Called on the way down; must never raise."""
    if writer is None:
        return None
    try:
        from ddl_tpu.utils.memory import hbm_stats

        mem = hbm_stats()
        groups: dict[tuple, list] = {}
        try:
            import jax

            for arr in jax.live_arrays():
                shape = tuple(getattr(arr, "shape", ()) or ())
                dtype = str(getattr(arr, "dtype", "?"))
                key = (shape, dtype)
                cell = groups.setdefault(key, [0, 0])
                cell[0] += 1
                cell[1] += int(getattr(arr, "nbytes", 0) or 0)
        except Exception:
            pass
        buffers = sorted(
            (
                {
                    "shape": list(shape),
                    "dtype": dtype,
                    "count": count,
                    "bytes": nbytes,
                }
                for (shape, dtype), (count, nbytes) in groups.items()
            ),
            key=lambda b: (-b["bytes"], b["dtype"], b["shape"]),
        )[:MAX_OOM_BUFFERS]
        return writer.emit(
            "hbm_oom_dump",
            step=step,
            error=str(exc)[:500],
            watermark=mem["bytes_in_use"] if mem else None,
            limit=(mem["bytes_limit"] or None) if mem else None,
            params_bytes=params_bytes,
            opt_bytes=opt_bytes,
            buffers=buffers,
            plans={
                label: {k: v for k, v in plan.items() if k in PLAN_FIELDS}
                for label, plan in _recent_plans.items()
            },
        )
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the account (pure stdlib — fold state in, rendered table out)
# ---------------------------------------------------------------------------


def _incarnation_account(hb: dict) -> dict | None:
    """One (host, repoch) cell -> its sums-to-watermark account, or None
    when the incarnation never sampled."""
    if not hb.get("samples"):
        return None
    watermark = int(hb.get("watermark", 0) or 0)
    at_peak = hb.get("at_peak") or {}
    bytes_by_cat = {}
    tracked = 0
    for cat in CATEGORIES:
        if cat == "untracked":
            continue
        v = int(at_peak.get(cat, 0) or 0)
        bytes_by_cat[cat] = v
        tracked += v
    # the residual against the live watermark: reported, never dropped
    # (negative when tracked exceeds the watermark — still honest)
    bytes_by_cat["untracked"] = watermark - tracked
    limit = hb.get("limit")
    return {
        "bytes": bytes_by_cat,
        "watermark": watermark,
        "device_peak": int(hb.get("device_peak", 0) or 0),
        "limit": int(limit) if limit else None,
        "headroom": (int(limit) - watermark) if limit else None,
        "samples": int(hb["samples"]),
        "synthetic": bool(hb.get("synthetic")),
        "plans": dict(hb.get("plans") or {}),
        "plans_dropped": int(hb.get("plans_dropped", 0) or 0),
        "oom_count": int(hb.get("oom_count", 0) or 0),
        "oom": hb.get("oom"),
    }


def top_consumers(bytes_by_cat: dict, n: int = 3) -> list:
    """Top-n nonzero categories by bytes, untracked included (it IS a
    consumer when large); ties broken in CATEGORIES order."""
    order = {c: i for i, c in enumerate(CATEGORIES)}
    ranked = sorted(
        ((c, v) for c, v in bytes_by_cat.items() if v > 0),
        key=lambda cv: (-cv[1], order.get(cv[0], len(order))),
    )
    return [[c, v] for c, v in ranked[:n]]


def account_from_fold(fold) -> dict:
    """``{"incarnations": [per-(host, repoch) accounts], "job": {...}}``.

    The job column sums each host's LATEST incarnation (a restart epoch
    replaces its predecessor's memory — summing repochs of one host
    would double-book the same device), so it reads as "the pod's
    per-device memory, now".  The headline peak is the max watermark any
    incarnation ever sampled."""
    incarnations = []
    latest_per_host: dict[int, dict] = {}
    peak = 0
    oom_count = 0
    for name in sorted(fold.streams):
        sf = fold.streams[name]
        if sf.host is None:
            continue
        for repoch in sorted(getattr(sf, "hbm", {})):
            acc = _incarnation_account(sf.hbm[repoch])
            if acc is None:
                continue
            acc["host"] = sf.host
            acc["repoch"] = repoch
            incarnations.append(acc)
            peak = max(peak, acc["watermark"])
            oom_count += acc["oom_count"]
            cur = latest_per_host.get(sf.host)
            if cur is None or repoch >= cur["repoch"]:
                latest_per_host[sf.host] = acc
    job_bytes = {c: 0 for c in CATEGORIES}
    job_watermark = 0
    limits = []
    headrooms = []
    synthetic = False
    for h in sorted(latest_per_host):
        acc = latest_per_host[h]
        for c, v in acc["bytes"].items():
            job_bytes[c] += v
        job_watermark += acc["watermark"]
        synthetic = synthetic or acc["synthetic"]
        if acc["limit"] is not None:
            limits.append(acc["limit"])
        if acc["headroom"] is not None:
            headrooms.append(acc["headroom"])
    incarnations.sort(key=lambda a: (a["host"], a["repoch"]))
    job_row = {
        "bytes": job_bytes,
        "watermark": job_watermark,
        "peak_bytes": peak,
        "limit": sum(limits) if limits else None,
        # the binding constraint is the tightest DEVICE, not the pool sum
        "headroom": min(headrooms) if headrooms else None,
        "top": top_consumers(job_bytes),
        "oom_count": oom_count,
        "synthetic": synthetic,
    }
    return {"incarnations": incarnations, "job": job_row}


def summary_from_fold(fold) -> dict | None:
    """The compact ``hbm`` section ``obs summarize`` embeds (and ``obs
    baseline`` therefore persists — the ``--fail-hbm-growth`` gate's
    comparison record); None when nothing ever sampled."""
    account = account_from_fold(fold)
    if not account["incarnations"]:
        return None
    job = account["job"]
    return {
        "peak_bytes": job["peak_bytes"],
        "watermark_bytes": job["watermark"],
        "limit_bytes": job["limit"],
        "headroom_bytes": job["headroom"],
        "untracked_bytes": job["bytes"]["untracked"],
        "top": job["top"],
        "oom_count": job["oom_count"],
        "synthetic": job["synthetic"],
        "incarnations": len(account["incarnations"]),
    }


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------


def fmt_bytes(v) -> str:
    if v is None:
        return "-"
    v = int(v)
    sign = "-" if v < 0 else ""
    a = abs(v)
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if a >= div:
            return f"{sign}{a / div:.1f}{unit}"
    return f"{sign}{a}B"


def render_hbm(account: dict, job_id: str = "") -> str:
    """The ``obs hbm`` report: one column per (host, repoch), a job
    column, category rows summing exactly to the watermark row (the
    residual is the ``untracked`` row), then the static plans and any
    OOM forensics."""
    incs = account["incarnations"]
    job = account["job"]
    lines = [f"== hbm — {job_id} ==" if job_id else "== hbm =="]
    if not incs:
        lines.append("no hbm samples recorded")
        return "\n".join(lines)
    head = (
        f"peak: {fmt_bytes(job['peak_bytes'])} per device over "
        f"{len(incs)} incarnation(s)"
    )
    if job["headroom"] is not None:
        head += f" | headroom: {fmt_bytes(job['headroom'])}"
    if job["top"]:
        head += " | top: " + ", ".join(
            f"{c} {fmt_bytes(v)}" for c, v in job["top"]
        )
    if job["synthetic"]:
        head += " | (synthetic watermark: backend exposes no memory stats)"
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
            row += f"{fmt_bytes(a['bytes'][cat]):>{width}}"
        row += f"{fmt_bytes(job['bytes'][cat]):>{width}}"
        lines.append(row)
    for label, key in (
        ("watermark", "watermark"),
        ("limit", "limit"),
        ("headroom", "headroom"),
    ):
        row = f"{label:<12}"
        for a, _lbl in cols:
            row += f"{fmt_bytes(a[key]):>{width}}"
        row += f"{fmt_bytes(job[key] if key != 'watermark' else job['watermark']):>{width}}"
        lines.append(row)
    row = f"{'samples':<12}"
    for a, _lbl in cols:
        row += f"{a['samples']:>{width}}"
    row += f"{'':>{width}}"
    lines.append(row)

    plans: dict[str, dict] = {}
    dropped = 0
    for a in incs:
        plans.update(a["plans"])
        dropped += a["plans_dropped"]
    if plans:
        lines.append("static plans (per compiled program):")
        lines.append(
            f"  {'program':<28}{'args':>10}{'out':>10}{'temp':>10}"
            f"{'code':>10}  analysis"
        )
        for label in sorted(plans):
            p = plans[label]
            lines.append(
                f"  {label:<28}"
                f"{fmt_bytes(p.get('argument_bytes')):>10}"
                f"{fmt_bytes(p.get('output_bytes')):>10}"
                f"{fmt_bytes(p.get('temp_bytes')):>10}"
                f"{fmt_bytes(p.get('code_bytes')):>10}"
                f"  {p.get('analysis', '?')}"
            )
            if p.get("scope_counts"):
                counts = ", ".join(f"{t} {n}" for t, n in p["scope_counts"].items())
                lines.append(f"    scope ({p.get('scope_file')}): {counts}")
            for kernel, n in (p.get("kernel_tiles") or {}).items():
                if "steps" in n:
                    # a selective-scan kernel: its grid and the time steps
                    # a grid step walks (the chunk's length)
                    lines.append(
                        f"    tiles {kernel}: {n.get('calls')} call(s), "
                        f"{n.get('total')} grid steps of "
                        f"{n['steps'] // max(n.get('total', 0), 1)} time steps"
                    )
                    continue
                if "computed" not in n:
                    # a grouped product or a row kernel of the dropless
                    # shuffle: its grid's worst case and the fewest steps
                    # any routing leaves it (the steps computed are the
                    # routing's, not the text's)
                    cols = ", ".join(
                        f"{v} of {k[3:]}" for k, v in sorted(n.items())
                        if k.startswith("col")
                    )
                    # a row kernel's MXU products a (row tile, token tile)
                    # pair: 1 for bf16 operands, 3 for float32's addends
                    passes = (
                        f", {n['passes'] / max(n.get('calls', 0), 1):g} product(s) a pair"
                        if "passes" in n else ""
                    )
                    lines.append(
                        f"    tiles {kernel}: {n.get('calls')} call(s), "
                        f"{n.get('total')} grid steps at most, "
                        f"{n.get('floor')} at least"
                        + (f", column blocks {cols}" if cols else "")
                        + passes
                    )
                    continue
                share = 100.0 * n.get("computed", 0) / max(n.get("total", 0), 1)
                # a flash kernel: its sub-tiles, and the (row, K step)
                # pairs its walk makes (absent from an older record)
                row_steps = (
                    f", {n['row_steps']} row-steps" if "row_steps" in n else ""
                )
                lines.append(
                    f"    tiles {kernel}: {n.get('calls')} call(s), "
                    f"{n.get('computed')} of {n.get('total')} sub-tiles "
                    f"computed ({share:.1f}%), {n.get('masked')} masked"
                    f"{row_steps}"
                )
        if dropped:
            lines.append(f"  (+{dropped} plan(s) beyond the retained cap)")

    if job["oom_count"]:
        lines.append(f"OOM forensics: {job['oom_count']} dump(s)")
        for a in incs:
            oom = a.get("oom")
            if not oom:
                continue
            lines.append(
                f"  h{a['host']}/e{a['repoch']}: {oom.get('error', '?')} "
                f"(watermark {fmt_bytes(oom.get('watermark'))}"
                + (
                    f" of {fmt_bytes(oom['limit'])})"
                    if oom.get("limit") else ")"
                )
            )
            for b in (oom.get("buffers") or [])[:3]:
                shape = "x".join(str(d) for d in b.get("shape", []))
                lines.append(
                    f"    {b.get('dtype', '?')}[{shape}] x{b.get('count', 1)} "
                    f"{fmt_bytes(b.get('bytes'))}"
                )
    return "\n".join(lines)
