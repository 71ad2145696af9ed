"""Device fences for timing measurements.

XLA dispatch is asynchronous: a timing that does not wait for the result
measures the enqueue, not the compute.  On a directly attached chip
``jax.block_until_ready`` is a true fence.  ``fence`` does that and adds
a 1-element readback of the last leaf — the bytes only exist on the host
after the program ran — and is what every benchmark in this repo times
against (which one method to keep is the benchmark PR's call, ROADMAP).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fence"]


def fence(tree) -> None:
    """Wait until everything in ``tree`` has actually been computed."""
    leaves = [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]
    jax.block_until_ready(leaves)
    if leaves:
        jax.device_get(jnp.ravel(leaves[-1])[:1])
