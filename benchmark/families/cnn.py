"""The CNN family: the program's ``Trainer`` (loader, ``shard_batch``,
step, epoch-end fence) over an in-memory image set made from the seed."""

from __future__ import annotations

import os

import numpy as np

from benchmark import traffic
from benchmark.families.common import TrainCell
from benchmark.reference import cnn as reference

__all__ = ["build"]


class ArrayImages:
    """Decoded images held in host memory: what the loader indexes."""

    def __init__(self, images: np.ndarray, labels: np.ndarray) -> None:
        self.images, self.labels = images, labels

    def __len__(self) -> int:
        return len(self.labels)

    def __getitem__(self, idx: int):
        return self.images[idx], int(self.labels[idx])


class CNNCell(TrainCell):
    reference = reference

    def __init__(self, config: dict, workload: dict, seed: int, workdir: str) -> None:
        import jax

        from ddl_tpu.config import preset
        from ddl_tpu.train.trainer import Trainer

        m = self.model = dict(config["model"])
        self.opt = dict(workload["optimizer"])
        d = workload["data"]
        self.rows_per_step = int(workload["batch"])
        self.period_steps = int(d["num_train"]) // self.rows_per_step
        if self.period_steps != int(workload["period_steps"]):
            raise ValueError("period_steps does not match num_train // batch")
        self.train = ArrayImages(*traffic.generate(d, seed, split="train"))
        test = ArrayImages(*traffic.generate(d, seed, split="test"))
        o = self.opt
        cfg = preset(
            "single",
            **{
                "model.growth_rate": m["growth_rate"],
                "model.block_config": tuple(m["block_config"]),
                "model.num_init_features": m["num_init_features"],
                "model.bn_size": m["bn_size"],
                "model.num_classes": m["num_classes"],
                "model.compute_dtype": m["compute_dtype"],
                "model.dense_block_impl": m["dense_block_impl"],
                "data.image_size": d["image_size"],
                "data.num_classes": d["num_classes"],
                "data.global_batch_size": self.rows_per_step,
                "data.eval_batch_size": min(self.rows_per_step, len(test)),
                "data.num_workers": int(d.get("workers", 2)),
                "train.learning_rate": o["learning_rate"],
                "train.b1": o["b1"], "train.b2": o["b2"], "train.eps": o["eps"],
                "train.seed": traffic.fold_seed(seed),
                "train.max_epochs": 10**6,
                "train.log_dir": os.path.join(workdir, "logs"),
                "train.checkpoint_dir": os.path.join(workdir, "ckpt"),
                "train.auto_resume": False,
                "train.save_best_qwk": False,
                "train.preemption_save": False,
            },
        )
        self.sampler_seed = traffic.fold_seed(seed)  # cfg.train.seed, above
        self.key = jax.random.key(self.sampler_seed)
        self.trainer = Trainer(cfg, datasets=(self.train, test))
        self.install_weights(self.key)

    def _params(self):
        return self.trainer.state.params[0]

    def _set_params(self, tree) -> None:
        self.trainer.state = self.trainer.state.replace(params=(tree,))

    def _strip(self, tree):
        return tree[0]

    def first_batch(self, period: int):
        # Worked out here, not asked of the trainer (whose sampler is left
        # alone): an epoch is one period, its order is the sampler's under
        # train.seed, and its first step takes the first ``batch`` rows.
        idx = traffic.epoch_order(len(self.train), self.sampler_seed, period)
        idx = idx[: self.rows_per_step]
        return self.train.images[idx], self.train.labels[idx].astype(np.int32)

    def shapes(self) -> dict:
        return dict(self.model, batch=self.rows_per_step)


def build(config, workload, seed, workdir) -> CNNCell:
    return CNNCell(config, workload, seed, workdir)
