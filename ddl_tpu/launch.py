"""Multi-host bootstrap and launcher utilities.

Replaces the reference's TorchX->Kubernetes launch stack (``.torchxconfig``,
``command``, ``torchx_component/submit_single.py``) with the JAX multi-host
model: *one process per TPU host*, each seeing its local chips, joined into
one SPMD world by ``jax.distributed.initialize``.  There is no NCCL
rendezvous and no rank->GPU binding (reference ``ddp.py:30-31``); the device
mesh spans all hosts' chips automatically once the coordinator handshake
completes.

On Cloud TPU pods the coordinator/process-id/process-count are discovered
from the TPU metadata environment, so ``bootstrap()`` with no arguments does
the right thing both on a v4-32 pod slice and on a single dev host.
``ddl_tpu.launcher.tpu_pod`` generates the per-host launch commands (the
``torchx run`` analog, reference ``command:2-34``).
"""

from __future__ import annotations

import os

import jax

__all__ = [
    "bootstrap", "host_id", "process_start_ts", "restart_epoch",
    "world_info", "force_cpu_devices",
]


def process_start_ts() -> float | None:
    """When this process began, on ``time.time()``'s clock, by the
    kernel's record of it: field 22 of ``/proc/self/stat`` (clock ticks
    after boot, a hundredth of a second) against ``/proc/uptime``.  What
    ran before any Python did is in it: the origin of ``setup.boot``
    (``obs/steptrace.stage``).  None where ``/proc`` does not say."""
    import time

    try:
        with open("/proc/self/stat") as f:
            # the command's name may hold blanks and brackets; the
            # fields after its closing bracket start at the third
            ticks = int(f.read().rpartition(")")[2].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def restart_epoch() -> int:
    """The pod restart epoch this process was launched under (0 for the
    initial launch and all non-pod runs).  Set by the pod supervisor
    (``DDL_RESTART_EPOCH``); stamped into ``world_info`` and every obs
    event so a run's telemetry attributes cleanly to its incarnation."""
    from ddl_tpu import coord

    return coord.restart_epoch()


def host_id() -> int:
    """This process's host index for telemetry (``obs/events.py`` stamps
    it into every event).  The launcher env (``DDL_HOST_ID``, falling
    back to the multihost rank ``DDL_PROCESS_ID``) wins so event files
    are correctly attributed even before/without ``bootstrap()``; else
    the JAX process index (0 on a single host)."""
    # set-but-empty vars count as unset (launchers template them from
    # possibly-empty scheduler vars), matching bootstrap()'s tolerance
    env = os.environ.get("DDL_HOST_ID") or os.environ.get("DDL_PROCESS_ID")
    if env:
        return int(env)
    try:
        return jax.process_index()
    except Exception:
        return 0


def force_cpu_devices(n: int) -> None:
    """Simulate ``n`` CPU devices instead of real TPUs (dev/test) — the one
    place the XLA_FLAGS + jax_platforms dance lives (used by the CLI's and
    the examples' ``--cpu-devices`` flags and mirrored by tests/conftest.py).
    Safe any time before the JAX backend initialises, even after ``import
    jax`` (``config.update`` works where the ``JAX_PLATFORMS`` env var,
    read at import, would be too late).  A no-op
    when the backend is already up on ``n``+ CPU devices (so callers can
    self-bootstrap without fighting tests/conftest.py)."""
    initialized = False
    try:
        from jax._src import xla_bridge

        initialized = xla_bridge.backends_are_initialized()
    except Exception:
        pass
    if initialized:
        devs = jax.devices()
        if devs and devs[0].platform == "cpu" and len(devs) >= n:
            return  # already simulating enough CPU devices
        raise RuntimeError(
            f"force_cpu_devices({n}) called after the JAX backend "
            f"initialized on {len(devs)} {devs[0].platform if devs else '?'} "
            "device(s); platform flags are no-ops post-init — call this "
            "before any jax.devices()/computation"
        )
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    jax.config.update("jax_platforms", "cpu")


def bootstrap(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    init_retries: int | None = None,
) -> None:
    """Join the multi-host world if one is configured; no-op otherwise.

    Explicit args win; else ``DDL_COORDINATOR`` / ``DDL_NUM_PROCESSES`` /
    ``DDL_PROCESS_ID`` env vars (the launcher sets these); else Cloud TPU
    metadata auto-detection via ``jax.distributed.initialize()``'s defaults
    when ``DDL_MULTIHOST=1``.

    The coordinator handshake is retried with exponential backoff and
    jitter (``init_retries`` re-dials, default 3, env override
    ``DDL_INIT_RETRIES``): after a preemption relaunch the hosts come up
    seconds apart, and the first workers to dial would otherwise die on a
    connection refusal the coordinator fixes moments later.  Jitter keeps
    a relaunched pod's N hosts from re-dialing in lockstep.

    After a pod-coordinated relaunch (``DDL_RESTART_EPOCH`` > 0) the env
    still carries the SAME coordinator address/world spec, so re-init is
    this exact path re-run — the retry loop absorbs the relaunched
    hosts' arrival skew.
    """
    coordinator_address = coordinator_address or os.environ.get("DDL_COORDINATOR")
    if num_processes is None and os.environ.get("DDL_NUM_PROCESSES"):
        num_processes = int(os.environ["DDL_NUM_PROCESSES"])
    if process_id is None and os.environ.get("DDL_PROCESS_ID"):
        process_id = int(os.environ["DDL_PROCESS_ID"])
    if init_retries is None:
        init_retries = int(os.environ.get("DDL_INIT_RETRIES", "3"))

    if coordinator_address is not None:
        initialize = lambda: jax.distributed.initialize(  # noqa: E731
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif os.environ.get("DDL_MULTIHOST") == "1":
        initialize = lambda: jax.distributed.initialize()  # noqa: E731
    else:
        _arm_compile_cache()
        return

    from ddl_tpu.utils.backoff import Backoff, retry_with_backoff

    def note(e, attempt):
        print(
            f"[ddl_tpu] jax.distributed.initialize failed ({e}); "
            f"retry {attempt + 1}/{init_retries}"
        )

    # transient handshake failures only (connection refused while the
    # coordinator comes up); a ValueError is a misconfigured world spec
    # and must fail fast on every host
    retry_with_backoff(
        initialize,
        retries=init_retries,
        exceptions=(RuntimeError, OSError),
        backoff=Backoff(base=2.0, factor=2.0, max_delay=60.0, jitter=0.5),
        on_retry=note,
    )
    _arm_compile_cache()


def _arm_compile_cache() -> None:
    """Arm the persistent XLA compile cache (``utils/compile_cache``) on
    the launch path: ``JAX_COMPILATION_CACHE_DIR`` as placed, else the
    checkout's fixed directory, or in pod mode the one NAS root the
    rendezvous leader publishes for every host.  Runs AFTER distributed
    init so the topology key sees the full world.  A cache that cannot
    be armed (read-only checkout, coord failure) costs a cold compile,
    never the launch: said once here, and ``cache_stats()`` stays None
    for whoever reports the run."""
    from ddl_tpu import coord
    from ddl_tpu.utils.compile_cache import activate_compile_cache

    try:
        stats = activate_compile_cache(rv=coord.from_env())
    except Exception as e:  # ddl-lint: disable=broad-except
        print(f"[ddl_tpu] compile cache unavailable, compiling cold ({e})")
        return
    if stats is None:
        print("[ddl_tpu] compile cache off (DDL_COMPILE_CACHE)")
        return
    state = "warm" if stats["warm"] else "cold"
    print(
        f"[ddl_tpu] compile cache {state}: {stats['dir']} "
        f"({stats['entries_before']} entries)"
    )


def world_info() -> dict:
    """Rank/world/device info (the reference prints this in its smoke test,
    ``test.py``)."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "host_id": host_id(),
        "restart_epoch": restart_epoch(),
        "local_devices": [str(d) for d in jax.local_devices()],
        "global_device_count": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }
