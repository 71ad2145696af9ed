"""One answer to "does this Pallas kernel run interpreted?".

Compiled on a ``tpu`` backend, interpreted on ``cpu`` (tests and
rehearsals on the simulated mesh).  Any other backend is an error, never
a silent interpreter: a run that believes it is on the chip must not be
able to finish on a path orders of magnitude slower and report success.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_default"]


def interpret_default(platform: str | None = None) -> bool:
    """The ``interpret=`` a kernel's public entry point resolves ``None``
    to, for ``platform`` (default: the process's backend)."""
    if platform is None:
        platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"backend {platform!r} is neither — pass interpret= explicitly"
    )
