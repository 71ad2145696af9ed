"""The LM family: the program's ``LMTrainer`` over a token corpus made
from the seed."""

from __future__ import annotations

import os

import numpy as np

from benchmark import traffic
from benchmark.families.common import TrainCell
from benchmark.reference import lm as reference

__all__ = ["build"]

# LMRunConfig.steps bounds the trainer's list of period boundaries; far
# beyond what any window reaches, small enough to build at once.
_PERIODS = 20000


class LMCell(TrainCell):
    reference = reference

    def __init__(self, config: dict, workload: dict, seed: int, workdir: str) -> None:
        import jax

        from ddl_tpu.models.transformer import LMConfig
        from ddl_tpu.parallel.sharding import LMMeshSpec
        from ddl_tpu.train.lm_trainer import LMRunConfig, LMTrainer
        from ddl_tpu.train.state import build_optimizer

        m = self.model = dict(config["model"])
        self.opt = dict(workload["optimizer"])
        self.period_steps = int(workload["period_steps"])
        self.rows_per_step = int(workload["batch"])
        self.seq_len = int(workload["seq_len"])
        tokens = traffic.generate(
            workload["data"], seed, vocab_size=m["vocab_size"], seq_len=self.seq_len
        )
        corpus = os.path.join(workdir, "corpus.npy")
        np.save(corpus, tokens)
        self.tokens = tokens
        cfg = LMConfig(
            vocab_size=m["vocab_size"], d_model=m["d_model"], n_layers=m["n_layers"],
            n_heads=m["n_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
            rope_theta=m.get("rope_theta", 10000.0),
            compute_dtype=m["compute_dtype"], flash=m["flash"], remat=m["remat"],
        )
        o = self.opt
        tx = build_optimizer(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
        run = LMRunConfig(
            batch=self.rows_per_step, seq_len=self.seq_len,
            steps=self.period_steps * _PERIODS, corpus=corpus,
            eval_every=0, checkpoint_dir=None, auto_resume=False,
            job_id="bench", log_dir=os.path.join(workdir, "logs"),
            log_every=self.period_steps, preemption_save=False,
        )
        self.key = jax.random.key(traffic.fold_seed(seed))
        self.trainer = LMTrainer(cfg, LMMeshSpec(), tx, run, rng=self.key)
        self.install_weights(self.key)

    def _params(self):
        return self.trainer.state.params

    def _set_params(self, tree) -> None:
        self.trainer.state = self.trainer.state.replace(params=tree)

    def first_batch(self, period: int):
        # Worked out here, not asked of the trainer: step s of the corpus
        # path is batch s mod (windows // batch) of epoch s div that, the
        # epoch's order is the sampler's under its fixed seed 0, window i is
        # tokens[i*T : i*T + T + 1], targets are inputs shifted by one.
        t, rows = self.seq_len, self.rows_per_step
        windows = (len(self.tokens) - 1) // t
        epoch, pos = divmod(period * self.period_steps, windows // rows)
        idx = traffic.epoch_order(windows, 0, epoch)[pos * rows:(pos + 1) * rows]
        w = np.stack([self.tokens[i * t: i * t + t + 1] for i in idx]).astype(np.int32)
        return w[:, :-1], w[:, 1:]

    def shapes(self) -> dict:
        return dict(self.model, batch=self.rows_per_step, seq_len=self.seq_len)


def build(config, workload, seed, workdir) -> LMCell:
    return LMCell(config, workload, seed, workdir)
