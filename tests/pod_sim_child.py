"""Trainer child for the 3-host pod-recovery simulation (test_coord.py).

One "host" of a simulated pod: a real tiny-LM trainer (1 CPU device per
process) under pod supervision.  The hosts share one checkpoint store
(the tmpdir "NAS"), with host 0 as the snapshot writer — the single-
process analog of a pod's collective Orbax save — and every host logging
each consumed batch (the global step, since the LM stream is pure in
step) to ``consumed_h<i>.log`` so the test can audit exact resume:
no batch replayed, none skipped.

Steps are paced (``DDL_SIM_PACE`` seconds each) so the pod's hosts are
genuinely mid-training when one host's injected ``stall@step`` trips
the watchdog — the coordinated-kill path, not a staggered-completion
artifact.  Not collected by pytest (no ``test_`` prefix).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddl_tpu.launch import force_cpu_devices  # noqa: E402

force_cpu_devices(1)

import jax  # noqa: E402

# generation-0 children must not spend longer compiling than the watchdog
# deadline: the suite hands them its persistent compile cache through
# JAX_COMPILATION_CACHE_DIR (test_coord._suite_cache_env), which JAX reads
# itself
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import optax  # noqa: E402

from ddl_tpu.models.transformer import LMConfig  # noqa: E402
from ddl_tpu.parallel.sharding import LMMeshSpec  # noqa: E402
from ddl_tpu.train.lm_trainer import LMRunConfig, LMTrainer  # noqa: E402


def main() -> None:
    sim = os.environ["DDL_SIM_DIR"]
    host = int(os.environ.get("DDL_COORD_HOST", "0"))
    pace = float(os.environ.get("DDL_SIM_PACE", "0"))
    steps = int(os.environ.get("DDL_SIM_STEPS", "10"))
    epoch = os.environ.get("DDL_RESTART_EPOCH", "0")

    # membership/respec audit: record what the supervisor's spawn env
    # said about this incarnation's world (the elastic e2e asserts the
    # epoch-1 relaunch carried the shrunken membership and the
    # renumbered SPMD bootstrap vars)
    with open(os.path.join(sim, f"env_h{host}.log"), "a") as fh:
        fh.write(
            f"{epoch} members={os.environ.get('DDL_COORD_MEMBERS', '-')} "
            f"nproc={os.environ.get('DDL_NUM_PROCESSES', '-')} "
            f"pid={os.environ.get('DDL_PROCESS_ID', '-')}\n"
        )

    # elastic scale-UP drill (DDL_FAULT="rejoin@epoch:K"): once this
    # incarnation's restart epoch reaches K, leave the pod on purpose —
    # the supervisor sees EXIT_REJOIN, proposes its own eviction, and
    # rejoins through the join_request path.  Checked BEFORE training so
    # the leave lands at a restart boundary (a committed snapshot), and
    # consume-on-fire means the post-grow relaunch trains normally.
    from ddl_tpu.utils import faultinject

    if faultinject.check_epoch(int(epoch)):
        from ddl_tpu.supervisor import EXIT_REJOIN

        print(f"[child h{host}] injected rejoin at epoch {epoch}",
              flush=True)
        sys.exit(EXIT_REJOIN)

    cfg = LMConfig(
        vocab_size=256, d_model=16, n_layers=1, n_heads=2, head_dim=8,
        d_ff=32, compute_dtype="float32", remat=False,
    )
    run = LMRunConfig(
        batch=2, seq_len=8, steps=steps, save_every=3, log_every=1,
        job_id=os.environ.get("DDL_JOB_ID", "podsim"),
        checkpoint_dir=os.path.join(sim, "ckpt"),  # the shared "NAS"
        log_dir=os.path.join(sim, f"logs_h{host}"),
    )
    t = LMTrainer(cfg, LMMeshSpec(), optax.adam(1e-2), run)

    # audit trail: every batch this incarnation consumes, keyed by the
    # global step (the LM data cursor), tagged with the restart epoch
    consumed = os.path.join(sim, f"consumed_h{host}.log")
    orig_sample = t._sample_batch

    def sample(step):
        with open(consumed, "a") as fh:
            fh.write(f"{epoch} {step}\n")
            fh.flush()
        return orig_sample(step)

    t._sample_batch = sample

    if pace > 0:
        fns = t.fns
        orig_train = fns.train

        def paced(state, inp, tgt):
            time.sleep(pace)
            return orig_train(state, inp, tgt)

        t.fns = fns._replace(train=paced)

    if host != 0:
        # hosts 1+ read the shared store but never write it: the single-
        # process stand-in for a pod's rank-coordinated collective save
        t.save_snapshot = lambda period: None

    print(f"[child h{host}] start at step {t._start_step} "
          f"(restart epoch {epoch})", flush=True)
    t.train()
    final = int(jax.device_get(t.state.step))
    # the decisive cross-host check: a sha256 over the full param state —
    # identical final step AND identical weights on every host
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get(t.state.params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    with open(os.path.join(sim, f"final_h{host}.log"), "a") as fh:
        fh.write(f"{epoch} {final} {h.hexdigest()}\n")
    print(f"[child h{host}] CHILD_OK step={final}", flush=True)
    if t.preempted and os.environ.get("DDL_SUPERVISED") == "1":
        from ddl_tpu.supervisor import EXIT_PREEMPTED

        sys.exit(EXIT_PREEMPTED)


if __name__ == "__main__":
    main()
