"""Per-layer metrics read from counters: the compile cache's and JAX's
own monitoring events, gathered by the harness during set-up."""

from __future__ import annotations


def compile_s(ctx, params):
    """Seconds of set-up spent in backend compiles or in loading compiled
    programs from the persistent cache."""
    c = ctx.get("compile")
    if not c or not c.get("events"):
        return None
    return c["backend_compile_s"] + c["cache_retrieval_s"]
