"""Persistent XLA compile cache: one directory per checkout, warm
restarts for supervised pods.

A relaunched incarnation — or the next run of the same entry point —
pays the full XLA compile again (the goodput ledger prices it as the
``recompile`` bucket, the ``restart_latency`` obs event times it) unless
the persistent cache survives the process.  Where it lives:

* **Placed from outside**: with ``JAX_COMPILATION_CACHE_DIR`` set, JAX
  itself points at that directory and this module changes nothing about
  it — no ``jax.config.update`` of the directory, no sub-directory, no
  ``DDL_COMPILE_CACHE`` or pod agreement on top, no eviction.  It only
  counts the entries and the hits and misses there.
* **Otherwise** every entry point (trainer CLI, LM trainer, serve,
  ``bench.py``, ``chip_smoke.py``) shares one fixed directory inside the
  checkout, :func:`default_cache_root` (``<repo>/.jax_cache``): the path
  is part of JAX's cache key, so a directory that moves (``/tmp``, a
  pid, a time) never hits.  ``DDL_COMPILE_CACHE=<dir>`` moves that root;
  pod mode agrees one on the NAS.

Under a root this module chose, three pieces keep it safe and observable:

* **Topology keying** (:func:`topology_key`): executables are only
  reusable on the mesh they were built for, so the cache root is
  subdivided per ``<platform>-d<devices>-p<processes>`` — an elastic
  scale-down (8 hosts → 7) compiles into its own keyed subdir instead
  of colliding with the full pod's entries, and scaling BACK up finds
  the original entries untouched.
* **Pod-agreed root** (:func:`activate_compile_cache` with a
  rendezvous): the leader publishes the cache root through
  ``coord.Rendezvous.agree`` so every host of a pod compiles into ONE
  NAS directory — host 3's incarnation 2 reuses what host 0 compiled
  in incarnation 1.  The agreed default lives under the ``--pod``
  directory (``<pod>/compile_cache``), which outlives launches by
  construction.
* **Hit/miss counters** (:func:`cache_stats`): entry counts before the
  run plus the cache hits and misses that :class:`CompileLog` hears,
  emitted as the ``compile_cache`` obs event so `obs summarize`/`obs
  diff` can gate "the second incarnation must be warm"
  (``restart_latency`` and the ``recompile`` goodput bucket strictly
  lower).

* **Byte bound** (:func:`evict_to_byte_bound`): the shared NAS root
  otherwise grows without bound — every elastic shrink/grow leaves
  another topology key's executables behind forever.
  ``DDL_COMPILE_CACHE_MAX_BYTES`` caps the whole root with
  LRU-by-mtime eviction across keys; the active key's fresh entries
  are never evicted, so the bound cannot cost this incarnation its
  warm restart.  Eviction counts ride the same ``compile_cache`` event.

``DDL_COMPILE_CACHE=off`` leaves the cache as JAX finds it: nothing is
activated, counted or evicted.

:class:`CompileLog` is the package's one ``jax.monitoring`` listener: it
hears every trace, lowering and backend compile of the process with its
true start and end, the cache load inside a backend compile, and the
cache's hits and misses.  The counters above, ``period.compiles`` /
``compile_s`` and the ``compile.*`` spans of the event stream
(``obs/steptrace.py``) all come from it.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import deque
from pathlib import Path

__all__ = [
    "ENV_CACHE",
    "ENV_CACHE_MAX_BYTES",
    "ENV_CACHE_MIN_S",
    "CompileLog",
    "activate_compile_cache",
    "cache_entries",
    "cache_stats",
    "compile_log",
    "default_cache_root",
    "emit_cache_event",
    "evict_to_byte_bound",
    "topology_key",
]

ENV_CACHE = "DDL_COMPILE_CACHE"
# JAX's own variable.  Set, it places the cache from outside the program
# and nothing here overrides it (module docstring).
ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"
# Minimum compile seconds before XLA persists an executable (JAX's
# jax_persistent_cache_min_compile_time_secs).  1s skips trivial CPU
# kernels in production; tests/sims set 0 so every compile is cached.
ENV_CACHE_MIN_S = "DDL_COMPILE_CACHE_MIN_S"
DEFAULT_MIN_COMPILE_S = 1.0
# Byte bound for the WHOLE shared cache root (all topology keys).  The
# pod-agreed root lives on the NAS and outlives launches by design;
# without a bound every elastic shrink/grow leaves another keyed
# subdir's worth of executables behind forever.  Eviction is
# LRU-by-mtime across keys, with the ACTIVE key's fresh entries held
# back (see evict_to_byte_bound) so bounding the dir cannot turn this
# incarnation's warm restart cold.  Unset/empty/0 = unbounded
# (historical behavior).
ENV_CACHE_MAX_BYTES = "DDL_COMPILE_CACHE_MAX_BYTES"

# The last activation's stats (one activation per process — jax.config
# is global), read back by cache_stats()/emit_cache_event().
_active: dict | None = None
_counters = {"hits": 0, "misses": 0, "evicted": 0, "evicted_bytes": 0}


def default_cache_root() -> Path:
    """``<checkout>/.jax_cache``, resolved from this file: the same path
    for every entry point, process and run of one checkout."""
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def topology_key() -> str:
    """The cache subdir key for the current mesh: platform, device
    count, process count.  Executables are sharding-specialized, so two
    topologies must never share entries — and after an elastic
    scale-down the shrunken world's key differs from the full pod's, so
    a later scale-back-up still finds its original warm entries."""
    import jax

    return (
        f"{jax.default_backend()}"
        f"-d{jax.device_count()}-p{jax.process_count()}"
    )


def cache_entries(cache_dir: str | os.PathLike) -> int:
    """Persisted executables under one keyed cache dir (files only —
    XLA writes flat content-addressed entries)."""
    try:
        return sum(1 for p in Path(cache_dir).iterdir() if p.is_file())
    except OSError:
        return 0


class CompileLog:
    """What ``jax.monitoring`` says of the process's compiles, heard in
    one place (:func:`compile_log` makes it and registers it, once;
    listener registries are append-only).

    Every trace, lowering and backend compile becomes a span
    ``compile.trace`` / ``compile.lower`` / ``compile.backend`` with
    ``fn`` (JAX's ``fun_name``) and JAX's own start and end on
    ``time.time()``, the clock of the event stream's ``ts``.  A trace
    that runs inside another trace or inside a lowering (the jitted
    functions a step calls: 2,000 of them in a small DenseNet's, and the
    hundreds that lowering a random key traces) is a part of that one and
    not a span of its own; JAX announces each start as a scalar, which is
    what tells them apart.  JAX times a backend compile around
    ``compile_or_get_cached``, so a persistent-cache hit's load lies
    INSIDE it: the span carries it as ``cache_hit`` and ``cache_load_s``
    and its seconds are counted once.  ``count`` and ``secs`` are the
    backend compiles and their seconds so far, from which ``StepTrace``
    takes a period's ``compiles`` / ``compile_s``.

    A span goes to the open stream (``attach``: the newest ``StepTrace``
    that ``StepTrace.create`` made, held weakly) or, while none is open, into ``kept``, a bounded list
    that the next stream writes first, true times and order kept.  The
    trainers' stage spans (``obs/steptrace.stage``) take the same path
    through ``record``, and a span recorded inside an open stage names it
    as its parent."""

    KEEP = 4096
    SPANS = {
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
        "/jax/core/compile/backend_compile_duration": "compile.backend",
    }

    def __init__(self) -> None:
        self.count = 0
        self.secs = 0.0
        self.kept: deque = deque(maxlen=self.KEEP)
        self._sink = None  # weakref to the StepTrace that writes
        # per thread: the open stages, whether each open compile event
        # began inside another, the cache load heard last
        self._local = threading.local()

    def _mine(self, name: str) -> list:
        return self._local.__dict__.setdefault(name, [])

    # ---------------------------------------------- jax.monitoring's side
    def _on_event(self, event: str, **kw) -> None:
        if "compilation_cache" in event:
            if "hit" in event:
                _counters["hits"] += 1
            elif "miss" in event:
                _counters["misses"] += 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        # fires inside the backend compile it belongs to, on its thread
        if event.endswith("cache_retrieval_time_sec"):
            self._local.load_s = duration

    def _on_start(self, event: str, value, **kw) -> None:
        if event in self.SPANS:
            inside = self._mine("inside")
            inside.append(bool(inside))

    def _on_span(self, event: str, start: float, end: float, fun_name="", **kw) -> None:
        name = self.SPANS.get(event)
        if name is None:
            return
        inside = self._mine("inside")
        nested = inside.pop() if inside else False
        if nested and name == "compile.trace":
            return
        fields = {"fn": str(fun_name)}
        if name == "compile.backend":
            self.count += 1
            self.secs += end - start
            load_s, self._local.load_s = getattr(self._local, "load_s", None), None
            fields.update(cache_hit=load_s is not None, cache_load_s=load_s or 0.0)
        self.record(name, start, end, **fields)

    # --------------------------------------------------- the spans' side
    def stages(self) -> list:
        """This thread's open stage spans, outermost first."""
        return self._mine("stages")

    def record(self, name: str, start: float, end: float, **fields) -> None:
        open_stages = self.stages()
        if open_stages:
            fields.update(parent=open_stages[-1], depth=len(open_stages))
        sink = self._taker()
        if sink is None:
            self.kept.append((name, start, end, fields))
        else:
            sink.heard(name, start, end, fields)

    def _taker(self):
        return self._sink() if self._sink is not None else None

    def attach(self, sink) -> None:
        """``sink.heard(name, start, end, fields)`` takes every span from
        now on, the kept ones first."""
        self._sink = weakref.ref(sink)
        while self.kept:
            sink.heard(*self.kept.popleft())

    def detach(self, sink=None) -> None:
        """``sink`` takes no more spans (None: whichever does)."""
        if sink is None or self._taker() is sink:
            self._sink = None

    def attached(self, sink) -> bool:
        return self._taker() is sink


_log: CompileLog | None = None


def compile_log() -> CompileLog:
    """The process's :class:`CompileLog`, registered on first use."""
    global _log
    if _log is None:
        from jax import monitoring

        _log = CompileLog()
        monitoring.register_event_listener(_log._on_event)
        monitoring.register_event_duration_secs_listener(_log._on_duration)
        monitoring.register_scalar_listener(_log._on_start)
        monitoring.register_event_time_span_listener(_log._on_span)
    return _log


def _cache_max_bytes() -> int:
    try:
        return int(float(os.environ.get(ENV_CACHE_MAX_BYTES) or 0))
    except ValueError:
        return 0


def evict_to_byte_bound(
    root: str | os.PathLike,
    active_key: str | None = None,
    max_bytes: int | None = None,
    fresh_s: float = 600.0,
) -> dict | None:
    """Bound the WHOLE shared cache root to ``max_bytes`` (default: the
    ``DDL_COMPILE_CACHE_MAX_BYTES`` env; unset/0 = unbounded, return
    None).  Eviction is LRU-by-mtime across every topology key's subdir
    — XLA touches entries on hit, so mtime order IS recency order — with
    one carve-out: entries under ``active_key`` younger than ``fresh_s``
    are never evicted.  Those are the executables this incarnation just
    compiled (or is mid-warm-restart on); evicting them to satisfy the
    bound would silently turn the warm restart the cache exists for back
    into a cold one.  Stale entries of the active key ARE fair game — a
    key that outgrew the bound on its own still converges.

    Returns ``{"evicted", "evicted_bytes", "total_bytes", "max_bytes"}``
    and accumulates the eviction counters into :func:`cache_stats` (and
    therefore the ``compile_cache`` obs event).  Best-effort throughout:
    a racing peer evicting the same NAS dir, or a file vanishing
    mid-walk, must never fail an activation."""
    if max_bytes is None:
        max_bytes = _cache_max_bytes()
    if not max_bytes or max_bytes <= 0:
        return None
    import time

    now = time.time()
    protected = Path(root) / active_key if active_key else None
    files: list[tuple[float, int, Path]] = []
    total = 0
    try:
        walk = list(Path(root).rglob("*"))
    except OSError:
        return None
    for p in walk:
        try:
            if not p.is_file():
                continue
            st = p.stat()
        except OSError:
            continue
        total += st.st_size
        files.append((st.st_mtime, st.st_size, p))
    evicted = 0
    evicted_bytes = 0
    if total > max_bytes:
        files.sort(key=lambda t: t[0])  # oldest first
        for mtime, size, p in files:
            if total <= max_bytes:
                break
            if (
                protected is not None
                and p.is_relative_to(protected)
                and now - mtime < fresh_s
            ):
                continue
            try:
                p.unlink()
            except OSError:
                continue
            total -= size
            evicted += 1
            evicted_bytes += size
    _counters["evicted"] += evicted
    _counters["evicted_bytes"] += evicted_bytes
    return {
        "evicted": evicted,
        "evicted_bytes": evicted_bytes,
        "total_bytes": total,
        "max_bytes": int(max_bytes),
    }


def activate_compile_cache(
    rv=None,
    cache_root: str | os.PathLike | None = None,
    events=None,
) -> dict | None:
    """Arm the persistent compile cache for this process.

    ``JAX_COMPILATION_CACHE_DIR`` set: that directory, exactly, as JAX
    already has it — this call only reads it (module docstring).
    Otherwise the root is, in order: the ``cache_root`` arg, the
    ``DDL_COMPILE_CACHE`` env, the pod-agreed default
    (``<pod>/compile_cache``, published by the rendezvous leader so
    every host uses the same NAS directory), :func:`default_cache_root`
    — and JAX is pointed at its ``<root>/<topology key>`` sub-directory.
    ``DDL_COMPILE_CACHE=off|0`` force-disables.

    Returns the activation stats (also kept for :func:`cache_stats`):
    ``{"dir", "key", "entries_before", "warm", "agreed", "placed"}`` —
    ``warm`` is True when the directory already holds entries, i.e. this
    incarnation's compiles should be hits; ``placed`` when the directory
    came from ``JAX_COMPILATION_CACHE_DIR``.  Emits one
    ``compile_cache`` event when ``events`` is given.
    """
    global _active
    import jax

    env_root = os.environ.get(ENV_CACHE)
    if env_root is not None and env_root.strip().lower() in ("", "0", "off"):
        return None
    try:
        min_s = float(
            os.environ.get(ENV_CACHE_MIN_S) or DEFAULT_MIN_COMPILE_S
        )
    except ValueError:
        min_s = DEFAULT_MIN_COMPILE_S
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    placed = os.environ.get(ENV_JAX_CACHE)
    key = topology_key()
    agreed = False
    if placed:
        cache_dir = Path(placed)
    else:
        root = cache_root or env_root
        if rv is not None:
            # one pod, one cache dir: the leader publishes (its env wins
            # so an operator override propagates), everyone else adopts.
            # The default sits beside the launches/ subdirs, so it
            # survives relaunches AND later launches of the same pod
            # directory.
            default = str(Path(rv.root).parent.parent / "compile_cache")
            local = str(root) if root else default
            try:
                root = rv.agree("compile-cache", lambda: local)
                agreed = True
            except Exception:  # ddl-lint: disable=broad-except
                # agreement is an optimization (identical envs agree
                # trivially); a coord hiccup must not fail the launch
                root = local
        root = root or default_cache_root()
        cache_dir = Path(root) / key
        cache_dir.mkdir(parents=True, exist_ok=True)
        # bound the shared root BEFORE counting entries, so `warm` and
        # `entries_before` describe what actually survived the byte bound
        evict_to_byte_bound(root, active_key=key)
        if jax.config.jax_compilation_cache_dir != str(cache_dir):
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", str(cache_dir))
            # a process that already compiled keeps its first directory
            # open until the cache object is rebuilt
            compilation_cache.reset_cache()
    compile_log()
    entries = cache_entries(cache_dir)
    _active = {
        "dir": str(cache_dir),
        "key": key,
        "entries_before": entries,
        "warm": entries > 0,
        "agreed": agreed,
        "placed": bool(placed),
    }
    if events is not None:
        emit_cache_event(events)
    return _active


def cache_stats() -> dict | None:
    """The current activation's stats plus live hit/miss counters, or
    None when the cache is off."""
    if _active is None:
        return None
    return {**_active, **_counters}


def emit_cache_event(events) -> None:
    """One ``compile_cache`` obs event for this incarnation: where the
    cache points, whether it started warm, and the counters so far.
    The warm-relaunch drill reads ``warm``/``entries_before`` alongside
    ``restart_latency`` and the ``recompile`` goodput bucket."""
    stats = cache_stats()
    if stats is None or events is None:
        return
    events.emit("compile_cache", **stats)

