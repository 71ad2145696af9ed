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
  run plus ``jax.monitoring`` cache-hit/miss listeners, emitted as the
  ``compile_cache`` obs event so `obs summarize`/`obs diff` can gate
  "the second incarnation must be warm" (``restart_latency`` and the
  ``recompile`` goodput bucket strictly lower).

* **Byte bound** (:func:`evict_to_byte_bound`): the shared NAS root
  otherwise grows without bound — every elastic shrink/grow leaves
  another topology key's executables behind forever.
  ``DDL_COMPILE_CACHE_MAX_BYTES`` caps the whole root with
  LRU-by-mtime eviction across keys; the active key's fresh entries
  are never evicted, so the bound cannot cost this incarnation its
  warm restart.  Eviction counts ride the same ``compile_cache`` event.

``DDL_COMPILE_CACHE=off`` leaves the cache as JAX finds it: nothing is
activated, counted or evicted.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = [
    "ENV_CACHE",
    "ENV_CACHE_MAX_BYTES",
    "ENV_CACHE_MIN_S",
    "activate_compile_cache",
    "cache_entries",
    "cache_stats",
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
_listener_installed = False


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


def _install_counters() -> None:
    """Count persistent-cache hits/misses via ``jax.monitoring`` —
    the same listener surface steptrace's compile timer uses."""
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    from jax import monitoring

    def _on_event(event: str, **kw) -> None:
        if "compilation_cache" in event:
            if "hit" in event:
                _counters["hits"] += 1
            elif "miss" in event:
                _counters["misses"] += 1

    monitoring.register_event_listener(_on_event)


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
    _install_counters()
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

