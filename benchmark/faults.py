"""The faults a training cell can have, planted underneath the timed path:
``python3 benchmark/faults.py <fault> <run.py's arguments>`` runs one cell with
the fault in place (on the chip at the cell's own size, to read what each
compared number makes of it), and ``test_check.py`` drives the same plants at
a small size on the CPU. The benchmark's own runs never import this file.

- ``unchanged``: a random-effect update that returns its state unchanged;
- ``half-batch``: half of the rows left out of training (weight 0), the
  objective taken over the rest;
- ``stale-small-waves``: the waves of the smallest buckets (entities of at
  most ``SMALL_ROWS`` rows) are fitted in the first sweep and left as they are
  in every later one: the fault ``small.<coordinate>`` exists to catch, since
  ``coef.<coordinate>`` compares few of those entities.
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL_ROWS = 16


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(HERE, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def planted(fault: str, run):
    if fault == "unchanged":
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        holder, name = RandomEffectCoordinate, "train_model"

        def broken(self, offsets, initial=None):
            return initial if initial is not None else self.initial_model()
    elif fault == "stale-small-waves":
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        holder, name = RandomEffectCoordinate, "train_model"
        train_model = RandomEffectCoordinate.train_model

        def broken(self, offsets, initial=None):
            sweep = self.__dict__.get("_fault_sweeps", 0)
            self._fault_sweeps = sweep + 1
            fit = self._fit_bucket
            if sweep:  # Xb is (lanes, rows, features)
                self._fit_bucket = lambda W, off, Xb, *rest: (
                    W if Xb.shape[1] <= SMALL_ROWS
                    else fit(W, off, Xb, *rest))
            try:
                return train_model(self, offsets, initial)
            finally:
                self._fit_bucket = fit
    elif fault == "half-batch":
        holder, name = run, "to_dataset"
        to_dataset = run.to_dataset

        def broken(data):
            ds = to_dataset(data)
            ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                                  ).astype(np.float32)
            return ds
    else:
        raise ValueError(f"unknown fault {fault!r}")
    sound = getattr(holder, name)
    setattr(holder, name, broken)
    try:
        yield
    finally:
        setattr(holder, name, sound)


if __name__ == "__main__":
    run = load_run()
    with planted(sys.argv[1], run):
        sys.exit(run.main(sys.argv[2:]))
