"""Device-memory (HBM) observability.

The reference has no memory instrumentation at all; on TPUs HBM is the
usual constraint (SURVEY.md §2.2 — remat/checkpointing exists to trade
FLOPs for it), so the trainer logs peak/in-use HBM per epoch alongside the
reference's metric CSVs.  Backed by ``Device.memory_stats()``, which TPU
runtimes populate; absent stats on the CPU simulation degrade to ``None``
rather than failing the run.  On a ``tpu`` device absent stats are an
error: a run that reports no watermark there is not observing the chip.
"""

from __future__ import annotations

import jax

__all__ = ["hbm_stats"]


def hbm_stats(device=None) -> dict | None:
    """``{bytes_in_use, peak_bytes_in_use, bytes_limit}`` for ``device``
    (default: first local device), or None when a non-TPU backend has no
    stats."""
    device = device or jax.local_devices()[0]
    if device.platform == "tpu":
        stats = device.memory_stats()
        if not stats:
            raise RuntimeError(f"{device} reports no memory stats")
    else:
        try:
            stats = device.memory_stats()
        except Exception:  # backends without the call (CPU simulation)
            return None
        if not stats:
            return None
    return {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(
            stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
        ),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
    }
