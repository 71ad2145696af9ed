"""The deepseek_v3 family (Kanana-2-30B-A3B's stack): the program's
``LMTrainer`` over a token corpus made from the seed, built from
``LMConfig``'s latent attention and dropless expert fields.  The corpus,
the feed's order, the parameter plumbing and the dropless layer's
dropped-row check are the mellum family's; only the ``LMConfig`` differs,
and the load of the held experts is printed a period."""

from __future__ import annotations

import os
import sys

import numpy as np

from benchmark import traffic
from benchmark.families.lm import _PERIODS
from benchmark.families.mellum import MellumCell
from benchmark.reference import deepseek_v3 as reference

__all__ = ["build", "lm_config"]


def lm_config(m: dict):
    """The program's ``LMConfig`` for a configuration file's ``model``."""
    from ddl_tpu.models.transformer import LMConfig, Rope

    return LMConfig(
        vocab_size=m["vocab_size"], d_model=m["d_model"], n_layers=m["n_layers"],
        n_heads=m["n_heads"], d_ff=m["d_ff"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_by_kind=(("full_attention", Rope(float(m["rope_theta"]), interleave=True)),),
        mlp_gated=True, norm_eps=m["norm_eps"],
        num_experts=m["num_experts"], expert_top_k=m["expert_top_k"],
        num_dense_layers=m["num_dense_layers"], moe_router="sigmoid",
        moe_d_ff=m["moe_d_ff"], num_shared_experts=m["num_shared_experts"],
        route_scale=m["route_scale"],
        expert_share=(m.get("expert_share_index", 0),
                      m["num_experts"] // m["experts_held"]),
        compute_dtype=m["compute_dtype"], flash=m["flash"], remat=m["remat"],
        remat_policy=m.get("remat_policy", "full"),
    )


class DeepseekV3Cell(MellumCell):
    reference = reference

    # MellumCell's __init__ with this family's lm_config: mellum's builds
    # its trainer from mellum's
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
        m, steps = super().run_period(period, guard)
        # how the held experts are loaded, a line a period in the run's log
        # (a reader gets no step metric)
        print(f"[deepseek_v3] period {period} steps {steps} moe_local_rows "
              f"{m.get('moe_local_rows')} moe_load_max_over_mean "
              f"{m.get('moe_load_max_over_mean')}", file=sys.stderr, flush=True)
        return m, steps


def build(config, workload, seed, workdir) -> DeepseekV3Cell:
    return DeepseekV3Cell(config, workload, seed, workdir)
