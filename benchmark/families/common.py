"""What the family adapters share.  An adapter builds the program's own
trainer for a cell and answers the train driver's few questions about it;
it is the only place the benchmark touches the program's objects."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["StopAfter", "flatten", "unflatten_like", "find_adam", "TrainCell"]


class StopAfter:
    """A preemption guard that asks for a stop after ``n`` steps: the
    trainers poll ``guard.requested`` once after every step and end the
    period when it is true.  The program's own way to end a period early,
    used to walk the first checked steps one at a time."""

    def __init__(self, n: int) -> None:
        self.n, self.polls = int(n), 0

    @property
    def requested(self) -> bool:
        self.polls += 1
        return self.polls >= self.n

    def request(self) -> None:  # the fault injector's hook; never fired here
        self.polls = self.n


def flatten(tree) -> dict:
    """``{"a/b/c": leaf}`` with flax's own path names."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out[jax.tree_util.keystr(path, simple=True, separator="/")] = leaf
    return out


def unflatten_like(template, flat: dict):
    """A tree shaped like ``template`` whose leaves come from ``flat`` by
    path name; a name or shape that differs is an error, not a default."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, old in paths:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        if name not in flat:
            raise KeyError(f"the benchmark's weights lack the program's leaf {name!r}")
        new = flat[name]
        if tuple(new.shape) != tuple(old.shape):
            raise ValueError(f"leaf {name!r}: benchmark {new.shape} vs program {old.shape}")
        leaves.append(new.astype(old.dtype))
    extra = set(flat) - {jax.tree_util.keystr(p, simple=True, separator="/") for p, _ in paths}
    if extra:
        raise KeyError(f"weights the program has no leaf for: {sorted(extra)[:5]}")
    return treedef.unflatten(leaves)


def find_adam(opt_state):
    """The optimizer state's Adam moments (the node with ``mu``/``nu``)."""
    for node in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    ):
        if hasattr(node, "mu"):
            return node
    raise ValueError("no Adam moments in the optimizer state")


class TrainCell:
    """One cell's trainer and what the driver asks of it.  Subclasses set
    ``trainer``, ``model``, ``opt``, ``period_steps``, ``rows_per_step``,
    ``reference`` (the family's plain reference module) and implement
    ``_params`` / ``_set_params`` / ``first_batch``."""

    trainer = None

    # -- the program's parameters, by flat name ------------------------
    def _params(self):
        raise NotImplementedError

    def _set_params(self, tree) -> None:
        raise NotImplementedError

    def first_batch(self, period: int):
        """The batch that period's first step has to be fed, worked out
        from the benchmark's own data and the feed's documented order."""
        raise NotImplementedError

    def install_weights(self, key) -> None:
        """Make the cell's weights on the device in one jitted call from
        the seed's key and put them where the program's own were."""
        template = self._params()
        shardings = jax.tree.map(lambda x: x.sharding, template)
        template_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), template
        )
        make = jax.jit(
            lambda k: unflatten_like(
                template_shapes, self.reference.init_params(k, self.model)
            ),
            out_shardings=shardings,
        )
        self._set_params(make(key))

    def leaf_norms(self, tree) -> dict:
        flat = flatten(tree)
        norms = jax.jit(
            lambda f: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                       for k, v in f.items()}
        )(flat)
        return {k: float(v) for k, v in norms.items()}

    def first_grad_norms(self) -> dict:
        """||g_1|| per leaf from Adam's first moment after ONE step:
        mu_1 = (1 - b1) g_1."""
        mu = find_adam(self.trainer.state.opt_state).mu
        scale = 1.0 / (1.0 - self.opt["b1"])
        return {k: v * scale for k, v in self.leaf_norms(self._strip(mu)).items()}

    def delta_norms(self, key) -> dict:
        """||theta_now - theta_0|| per leaf, theta_0 made again from the
        seed inside the same jitted call (no copy of it is kept)."""
        params = self._params()
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)

        @jax.jit
        def delta(p, k):
            start = unflatten_like(shapes, self.reference.init_params(k, self.model))
            d = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, start)
            return {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in flatten(d).items()}

        return {k: float(v) for k, v in delta(params, key).items()}

    def _strip(self, tree):
        return tree

    # -- the loop ------------------------------------------------------
    def run_period(self, period: int, guard=None):
        return self.trainer.run_period(period, guard)

    def events_path(self):
        obs = self.trainer.obs
        return obs.writer.path if obs is not None else None

    def free(self) -> None:
        """Drop the program's state so the reference has the chip."""
        self.trainer.state = None
        self.trainer = None
        import gc

        gc.collect()
