"""The training driver: one cell's trainer driven through its own
``run_period``.

``setup`` builds ONE object, the program's trainer with its compiled step
and its state.  ``warm`` drives it from the seed through its first three
steps, one period each, ended after a step by the program's own
preemption poll, through the window's own call and feed; it keeps what the
check needs (three losses, the first gradient's norms out of Adam's first
moment, the norms of the parameters' change) and runs one whole untimed
period.  ``window`` hands that same object on: ``run_period`` after
``run_period`` until the clock passes ``--seconds``.  ``check`` frees the
program's state, follows the same three steps with the plain reference in
float32 and compares.
"""

from __future__ import annotations

import importlib
import math
import time

from benchmark import metrics
from benchmark.families.common import StopAfter
from benchmark.reference import common as refcommon

__all__ = ["Driver", "CHECKED_STEPS"]

CHECKED_STEPS = 3


class Driver:
    def __init__(self, config: dict, workload: dict, seed: int, workdir: str, log=print) -> None:
        self.config, self.workload, self.seed, self.workdir = config, workload, seed, workdir
        self.log = log
        self.family = importlib.import_module(f"benchmark.families.{config['family']}")
        self.cell = None
        self.next_period = 0
        self.checked = None
        self.batches = None
        self.nonfinite = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        self.cell = self.family.build(self.config, self.workload, self.seed, self.workdir)

    def warm(self, whole_period: bool = True) -> None:
        cell = self.cell
        losses, grad_norms = [], None
        for period in range(CHECKED_STEPS):
            m, steps = cell.run_period(period, StopAfter(1))
            if steps != 1:
                raise RuntimeError(f"a checked period ran {steps} steps, not 1")
            losses.append(float(m["loss"]))
            if period == 0:
                grad_norms = cell.first_grad_norms()
        self.checked = {
            "losses": losses,
            "grad_norms": grad_norms,
            "delta_norms": cell.delta_norms(cell.key),
        }
        self.batches = [cell.first_batch(p) for p in range(CHECKED_STEPS)]
        self.next_period = CHECKED_STEPS
        if whole_period:
            # one whole period, untimed: the steady state's every shape
            cell.run_period(CHECKED_STEPS)
            self.next_period = CHECKED_STEPS + 1

    # ------------------------------------------------------------ window
    def window(self, seconds: float, max_periods: int = 0) -> dict:
        """Periods until the clock passes ``seconds`` (or ``max_periods``
        have run: the traced stretch is bounded in events, not only in
        time); every period and every second of the window counts."""
        cell, periods = self.cell, []
        t0 = time.perf_counter()
        wall0 = time.time()
        while True:
            ts = time.perf_counter()
            m, steps = cell.run_period(self.next_period)
            te = time.perf_counter()
            self.next_period += 1
            periods.append((ts - t0, te - t0, steps))
            if not math.isfinite(float(m.get("loss", float("nan")))):
                self.nonfinite += 1
            if te - t0 >= seconds or len(periods) == max_periods:
                break
        return {
            "periods": periods,
            "steps": sum(p[2] for p in periods),
            "elapsed": periods[-1][1] - periods[0][0],
            "wall_start": wall0,
            "wall_end": time.time(),
            "attempted": len(periods),
            "failed": self.nonfinite,
            "rows_per_step": cell.rows_per_step,
        }

    def end_to_end(self, win: dict) -> dict:
        return {"train_steps_per_s": metrics.steps_per_s(win["periods"])}

    def events(self) -> list:
        """The program's own event stream (``obs``: spans, ``hbm_plan``)."""
        import json

        path = self.cell.events_path() if self.cell is not None else None
        out = []
        if path is None:
            return out
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # a torn last line
        return out

    def shapes(self) -> dict:
        return self.cell.shapes()

    # ------------------------------------------------------------- check
    def free(self) -> None:
        if self.cell is not None:
            self.key = self.cell.key
            self.model, self.opt = self.cell.model, self.cell.opt
            self.reference = self.cell.reference
            self.cell.free()
            self.cell = None

    def reference_steps(self, precision: str = "f32", half_batch: bool = False) -> dict:
        import jax

        params0 = jax.jit(lambda k: self.reference.init_params(k, self.model))(self.key)
        return refcommon.three_steps(
            self.reference, self.model, self.opt, params0, self.batches,
            precision=precision, steps=CHECKED_STEPS, half_batch=half_batch,
            row_block=int(self.workload.get("reference", {}).get("row_block", 0)),
        )

    def check(self) -> dict:
        """``{"numbers": {name: gap}, "notes": {...}}`` of the timed
        object's first three steps against the plain reference."""
        self.free()
        t0 = time.perf_counter()
        ref = self.reference_steps("f32")
        out = metrics.training_numbers(self.checked, ref)
        out["numbers"]["nonfinite_losses"] = float(self.nonfinite)
        out["notes"]["reference_s"] = time.perf_counter() - t0
        out["notes"]["reference_step_s"] = ref["step_seconds"]
        out["notes"]["losses_program"] = self.checked["losses"]
        out["notes"]["losses_reference"] = ref["losses"]
        return out
