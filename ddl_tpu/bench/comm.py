"""Collective-communication microbenchmarks over the device mesh.

TPU-native re-design of the reference's latency probe
(``communication_time.py``): there, rank0 times a 4 MiB fp32 NCCL ``send`` to
rank1 plus a 1-float ack ``recv`` with CUDA events, 1000 iterations appended
to a CSV, iteration 0 discarded as NCCL-init cost (``ipynb/main.ipynb`` cell
9).  Here the equivalent p2p primitive is a jitted ``lax.ppermute`` pair over
a 2-device mesh — payload one hop forward, ack one hop back — fenced with
``utils/timing.fence`` (block + 1-element readback), with iteration 0
likewise the compile+warmup cost.  The fence's
own host round-trip is measured separately (``fence_floor_ms``) and
subtracted from the reported mean.  On top of the reference's
ping-pong, this module also measures the collectives the framework actually
trains with (``psum``, ``all_gather``, ``ppermute``) across a size sweep and
reports algorithmic bandwidth — the number that predicts DP-allreduce and
pipeline-handoff cost (BASELINE.json's "allreduce GB/s" target metric).

CSV output keeps the reference's row shape: ``job_id,iteration,elapsed_ms``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from time import perf_counter

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P

from ddl_tpu.utils.timing import fence

__all__ = [
    "PingPongResult",
    "ping_pong",
    "collective_bandwidth",
    "axis_bandwidth_sweep",
    "run_comm_bench",
]

COLLECTIVE_OPS = ("psum", "all_gather", "reduce_scatter", "ppermute", "all_to_all")

DEFAULT_PAYLOAD_ELEMS = 1024 * 1024  # 4 MiB fp32, reference communication_time.py:18


@dataclass
class PingPongResult:
    times_ms: np.ndarray  # per-iteration round-trip, iteration 0 = warmup/compile
    payload_bytes: int
    fence_floor_ms: float = 0.0  # host cost of the fence itself

    @property
    def mean_ms(self) -> float:
        """Mean excluding iteration 0 (init cost, per reference analysis),
        net of the measured per-sample fence overhead."""
        if len(self.times_ms) <= 1:
            return float("nan")
        return max(float(self.times_ms[1:].mean()) - self.fence_floor_ms, 1e-6)

    @property
    def one_way_gbps(self) -> float:
        return self.payload_bytes / (self.mean_ms * 1e-3) / 1e9


def _ring_mesh(n: int | None = None) -> Mesh:
    devices = jax.devices()
    n = n or min(2, len(devices))
    return Mesh(np.array(devices[:n]), ("ring",))


def ping_pong(
    iterations: int = 1000,
    payload_elems: int = DEFAULT_PAYLOAD_ELEMS,
    mesh: Mesh | None = None,
) -> PingPongResult:
    """Round-trip: payload device0 -> device1, 1-float ack device1 -> device0."""
    mesh = mesh or _ring_mesh(2)
    n = mesh.devices.size
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P("ring"),
        out_specs=P("ring"),
        check_vma=False,
    )
    def round_trip(x):
        y = lax.ppermute(x, "ring", fwd)
        ack = lax.ppermute(y[:1], "ring", bwd)
        return x + ack  # depend on the ack so the full round trip is timed

    x = jnp.ones((n * payload_elems,), jnp.float32)
    times = np.empty(iterations + 1)
    for i in range(iterations + 1):
        t0 = perf_counter()
        fence(round_trip(x))
        times[i] = (perf_counter() - t0) * 1e3
    # fence cost on an already-materialised array: the per-sample overhead
    # the fence adds on top of the round trip being measured
    floors = np.empty(20)
    for i in range(len(floors)):
        t0 = perf_counter()
        fence(x)
        floors[i] = (perf_counter() - t0) * 1e3
    return PingPongResult(
        times_ms=times,
        payload_bytes=payload_elems * 4,
        fence_floor_ms=float(np.median(floors)),
    )


def collective_bandwidth(
    op: str,
    mesh: Mesh | None = None,
    payload_elems: int = DEFAULT_PAYLOAD_ELEMS,
    iterations: int = 50,
    axis: str | None = None,
) -> dict:
    """Algorithmic bandwidth of one collective over one mesh axis.

    ``axis`` defaults to the mesh's first axis; on a multi-axis mesh the
    collective runs *within* the groups of that axis (the other axes stay
    idle), which is exactly how the training programs issue them — so a
    per-axis sweep attributes link bandwidth to the mesh axis that will
    carry each collective (DP grads on ``data``, Ulysses ``all_to_all`` on
    ``seq``, TP all-reduce on ``model``, stage handoff on ``pipe``).

    algbw = bytes_moved_per_device / time; for psum the standard convention
    bytes = 2 * (n-1)/n * payload (reduce-scatter + all-gather phases).
    """
    mesh = mesh or _ring_mesh()
    axis = axis or mesh.axis_names[0]
    n = mesh.shape[axis]
    # tiled reduce_scatter/all_to_all need the per-device shard divisible
    # by the axis size — round up so odd axis sizes (3, 5, 6 on real pods)
    # measure instead of aborting; payload_bytes reports the actual size
    payload_elems = -(-payload_elems // n) * n
    ring = [(i, (i + 1) % n) for i in range(n)]

    if op == "psum":
        body, out_spec = (lambda v: lax.psum(v, axis)), P(axis)
    elif op == "all_gather":
        body, out_spec = (lambda v: lax.all_gather(v, axis, tiled=True)), P()
    elif op == "reduce_scatter":
        body, out_spec = (lambda v: lax.psum_scatter(v, axis, tiled=True)), P(axis)
    elif op == "ppermute":
        body, out_spec = (lambda v: lax.ppermute(v, axis, ring)), P(axis)
    elif op == "all_to_all":
        # the Ulysses hot collective (parallel/ulysses.py): each device
        # splits its shard n ways and exchanges — (n-1)/n of it crosses
        # the links
        body, out_spec = (
            lambda v: lax.all_to_all(v, axis, 0, 0, tiled=True),
            P(axis),
        )
    else:
        raise ValueError(op)

    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=out_spec,
            check_vma=False,
        )
    )
    x = jnp.ones((n * payload_elems,), jnp.float32)

    fence(fn(x))  # compile
    t0 = perf_counter()
    for _ in range(iterations):
        out = fn(x)
    fence(out)
    elapsed = (perf_counter() - t0) / iterations
    payload_bytes = payload_elems * 4
    if op == "psum":
        moved = 2 * (n - 1) / n * payload_bytes
    elif op == "all_gather":
        # per-device shard is payload_bytes; gathered result n * payload
        moved = (n - 1) / n * (payload_bytes * n)
    elif op in ("reduce_scatter", "all_to_all"):
        moved = (n - 1) / n * payload_bytes
    else:
        moved = payload_bytes
    return {
        "op": op,
        "axis": axis,
        "devices": n,
        "payload_bytes": payload_bytes,
        "mean_ms": elapsed * 1e3,
        "algbw_gbps": moved / elapsed / 1e9,
    }


def axis_bandwidth_sweep(
    mesh: Mesh,
    ops: tuple[str, ...] = COLLECTIVE_OPS,
    payload_elems: int = DEFAULT_PAYLOAD_ELEMS,
    iterations: int = 50,
) -> dict[str, dict[str, dict]]:
    """Run every collective over every non-trivial axis of ``mesh``.

    Returns ``{axis: {op: collective_bandwidth result}}`` — on a real pod
    this shows which axes ride ICI vs DCN (the reference measured exactly
    this split by hand: ~10.6 GB/s intra-node vs ~0.23 GB/s inter-node,
    SURVEY.md §6), so shardings can be laid out to put the chatty
    collectives on the fast axes."""
    out: dict[str, dict[str, dict]] = {}
    for axis in mesh.axis_names:
        if mesh.shape[axis] < 2:
            continue
        out[axis] = {
            op: collective_bandwidth(
                op, mesh, payload_elems, iterations, axis=axis
            )
            for op in ops
        }
    return out


def run_comm_bench(
    log_dir: str | os.PathLike = "training_logs",
    job_id: str | None = None,
    iterations: int = 1000,
    payload_elems: int = DEFAULT_PAYLOAD_ELEMS,
) -> dict:
    """Full microbenchmark: ping-pong CSV (reference-compatible rows) +
    collective bandwidth sweep.  Returns a summary dict."""
    from ddl_tpu.train.trainer import resolve_job_id

    job_id = job_id or resolve_job_id()
    os.makedirs(log_dir, exist_ok=True)

    summary: dict = {"job_id": job_id, "devices": len(jax.devices())}
    if len(jax.devices()) >= 2:
        pp = ping_pong(iterations=iterations, payload_elems=payload_elems)
        with open(os.path.join(log_dir, "communication_time.csv"), "a") as f:
            for i, t in enumerate(pp.times_ms):
                f.write(f"{job_id},{i},{t}\n")
        summary["ping_pong_mean_ms"] = pp.mean_ms
        summary["ping_pong_one_way_gbps"] = pp.one_way_gbps
        for op in COLLECTIVE_OPS:
            r = collective_bandwidth(op, payload_elems=payload_elems)
            summary[f"{op}_gbps"] = r["algbw_gbps"]
            summary[f"{op}_ms"] = r["mean_ms"]
    else:
        # Single-chip: report HBM-loopback psum as a degenerate datapoint.
        r = collective_bandwidth(
            "psum", mesh=_ring_mesh(1), payload_elems=payload_elems
        )
        summary["psum_ms"] = r["mean_ms"]
    return summary


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iterations", type=int, default=None,
                    help="samples per measurement (default: 1000 flat, "
                    "100 per op/axis with --mesh)")
    ap.add_argument("--payload-elems", type=int, default=DEFAULT_PAYLOAD_ELEMS)
    ap.add_argument(
        "--mesh", default=None,
        help="per-axis sweep over a named mesh, e.g. 'data=2,seq=2,model=2' "
        "(axis sizes must multiply to <= device count); omitted = flat "
        "2-device ping-pong + single-axis collective sweep",
    )
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="simulate N CPU devices (dev/test)")
    args = ap.parse_args()
    if args.cpu_devices:
        from ddl_tpu.launch import force_cpu_devices

        force_cpu_devices(args.cpu_devices)

    if args.mesh:
        axes = dict(kv.split("=") for kv in args.mesh.split(","))
        names, sizes = tuple(axes), tuple(int(v) for v in axes.values())
        need = int(np.prod(sizes))
        have = len(jax.devices())
        if need > have:
            ap.error(
                f"--mesh {args.mesh} needs {need} devices, have {have} "
                "(axis sizes must multiply to <= device count)"
            )
        mesh = Mesh(np.array(jax.devices()[:need]).reshape(sizes), names)
        sweep = axis_bandwidth_sweep(
            mesh, payload_elems=args.payload_elems,
            iterations=args.iterations or 100,
        )
        print(json.dumps(sweep, indent=2))
    else:
        print(json.dumps(run_comm_bench(
            iterations=args.iterations or 1000,
            payload_elems=args.payload_elems,
        ), indent=2))
