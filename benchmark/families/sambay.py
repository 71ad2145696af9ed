"""The SambaY family (Phi-4-mini-flash-reasoning's stack): the program's
``LMTrainer`` over a token corpus made from the seed, built from
``LMConfig``'s per-layer fields.  The corpus, the feed's order and the
parameter plumbing are the LM family's."""

from __future__ import annotations

import os

import numpy as np

from benchmark import traffic
from benchmark.families.lm import _PERIODS, LMCell
from benchmark.reference import sambay as reference

__all__ = ["build", "lm_config"]


def lm_config(m: dict):
    """The program's ``LMConfig`` for a configuration file's ``model``."""
    from ddl_tpu.models.transformer import LMConfig

    cfg = LMConfig(
        vocab_size=m["vocab_size"], d_model=m["d_model"], n_layers=m["n_layers"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        d_ff=m["d_ff"], layer_types=tuple(m["layer_types"]),
        layer_indices=tuple(m["layer_indices"]), attn_window=m["sliding_window"],
        norm="layer", norm_eps=m["norm_eps"], tie_embeddings=True,
        diff_attn=True, mlp_gated=True,
        ssm_state=m["ssm_state"], ssm_conv=m["ssm_conv"], ssm_expand=m["ssm_expand"],
        compute_dtype=m["compute_dtype"], flash=m["flash"], remat=m["remat"],
        remat_policy=m.get("remat_policy", "full"),
    )
    # the file states the rank for the reference; the program derives it
    if m["ssm_dt_rank"] != cfg.ssm_rank:
        raise ValueError(f"ssm_dt_rank {m['ssm_dt_rank']} is not the program's "
                         f"ceil(d_model / 16) = {cfg.ssm_rank}")
    return cfg


class SambayCell(LMCell):
    reference = reference

    def __init__(self, config: dict, workload: dict, seed: int, workdir: str) -> None:
        import jax

        from ddl_tpu.parallel.sharding import LMMeshSpec
        from ddl_tpu.train.lm_trainer import LMRunConfig, LMTrainer
        from ddl_tpu.train.state import build_optimizer

        m = self.model = dict(config["model"])
        self.opt = o = dict(workload["optimizer"])
        self.period_steps = int(workload["period_steps"])
        self.rows_per_step = int(workload["batch"])
        self.seq_len = int(workload["seq_len"])
        self.tokens = traffic.generate(
            workload["data"], seed, vocab_size=m["vocab_size"], seq_len=self.seq_len
        )
        corpus = os.path.join(workdir, "corpus.npy")
        np.save(corpus, self.tokens)
        tx = build_optimizer(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
        run = LMRunConfig(
            batch=self.rows_per_step, seq_len=self.seq_len,
            steps=self.period_steps * _PERIODS, corpus=corpus,
            eval_every=0, checkpoint_dir=None, auto_resume=False,
            job_id="bench", log_dir=os.path.join(workdir, "logs"),
            log_every=self.period_steps, preemption_save=False,
        )
        self.key = jax.random.key(traffic.fold_seed(seed))
        self.trainer = LMTrainer(lm_config(m), LMMeshSpec(), tx, run, rng=self.key)
        self.install_weights(self.key)


def build(config, workload, seed, workdir) -> SambayCell:
    return SambayCell(config, workload, seed, workdir)
