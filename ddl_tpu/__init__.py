"""ddl_tpu — a TPU-native distributed deep-learning framework.

A ground-up JAX/XLA/pjit/shard_map re-design of the capabilities of the
PyTorch+NCCL reference ``Darrellcr/distributed-deep-learning``: DenseNet121
image classification (APTOS-2019, 5 classes) trained under single-device,
data-parallel, GPipe pipeline-parallel, and hybrid DP x PP configurations on a
``jax.sharding.Mesh``, plus a collective-communication microbenchmark, CSV
metric logging, sharded checkpoint/resume, and a multi-host TPU launcher.

Parallelism is expressed TPU-first: the ``data`` mesh axis replaces DDP's
NCCL gradient allreduce (reference ``ddp.py:127``) with an XLA ``psum`` over
ICI; the ``pipe`` axis replaces ``torch.distributed.pipelining`` GPipe
send/recv (reference ``pp.py:140-150``) with a ``lax.ppermute`` microbatch
rotation inside ``shard_map``; the hybrid config (reference
``ddp_n_pp.py:32-33``) is simply the 2-D ``(data, pipe)`` mesh.
"""

__version__ = "0.1.0"

# Importing the package imports no JAX: the supervisor, the `obs`
# readers and chip_smoke.py's parent must stay off the chip their
# children need (a chip belongs to one process at a time).
