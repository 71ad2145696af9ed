"""The mellum family (Mellum2-12B-A2.5B's block): the program's
``LMTrainer`` over a token corpus made from the seed, built from
``LMConfig``'s per-layer fields.  The corpus, the feed's order and the
parameter plumbing are the LM family's."""

from __future__ import annotations

import os

import numpy as np

from benchmark import traffic
from benchmark.families.lm import _PERIODS, LMCell
from benchmark.reference import mellum as reference

__all__ = ["build", "lm_config"]


def lm_config(m: dict):
    """The program's ``LMConfig`` for a configuration file's ``model``."""
    from ddl_tpu.models.transformer import LMConfig, Rope

    return LMConfig(
        vocab_size=m["vocab_size"], d_model=m["d_model"], n_layers=m["n_layers"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        layer_types=tuple(m["layer_types"]), attn_window=m["sliding_window"],
        rope_by_kind=tuple((kind, Rope(**rope)) for kind, rope in sorted(m["rope"].items())),
        qk_norm=True, mlp_gated=True, norm_eps=m["norm_eps"],
        # every layer's MLP is sparse: no dense MLP (d_ff), no shared expert
        num_experts=m["num_experts"], expert_top_k=m["expert_top_k"],
        moe_router="softmax", moe_layer="dropless", moe_d_ff=m["moe_d_ff"],
        expert_share=(m.get("expert_share_index", 0),
                      m["num_experts"] // m["experts_held"]),
        compute_dtype=m["compute_dtype"], flash=m["flash"], remat=m["remat"],
        remat_policy=m.get("remat_policy", "full"), ce_chunk=m.get("ce_chunk", 0),
    )


class MellumCell(LMCell):
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

    def run_period(self, period: int, guard=None):
        m, steps = self.trainer.run_period(period, guard)
        # the layer is dropless by its buffer's size; a run that counts a
        # dropped row has no result
        if m.get("moe_rows_dropped", 0.0) != 0.0:
            raise RuntimeError(f"the dropless layer dropped {m['moe_rows_dropped']} rows")
        return m, steps


def build(config, workload, seed, workdir) -> MellumCell:
    return MellumCell(config, workload, seed, workdir)
