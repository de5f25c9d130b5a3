"""The one general generator: a configuration file and a traffic file in,
the data and a factory of ready-to-fit searches out.

Nothing here knows a configuration, a traffic mix or a cell by name.  A
configuration file says what is fitted (estimator, its parameters, the
data's shape, the folds); a traffic file says how it is searched (the
search class, the parameter grid, the search's own arguments).

The data set belongs to the configuration, as MNIST is one data set: it
is drawn from the configuration's ``base_seed``.  ``--seed`` decides the
traffic: the order in which the grid's values are handed to the search
(so where each score has to land in ``cv_results_``), and which
candidates the comparison samples.  Every seed is then the same work in
another order (the contract: "give every seed the same set of sizes and
arrivals, in another order").  The seed does not reorder the rows: on the
chip that alone moved the iterations at which the solver's gradient-norm
stop fires (51..54 and 91..96 in two of eight launches, 0.9 % of the
search's wall; PERF.md), and the spread over seeds would then be the
data's, not the machine's.
"""

from __future__ import annotations

import importlib

import numpy as np


def load_object(path):
    """``"package.module.Name"`` -> the object."""
    module, _, name = path.rpartition(".")
    return getattr(importlib.import_module(module), name)


def make_data(spec):
    """(X float32 (n, d), y int64 (n,)): the configuration's data set.

    Pixel-like features in [0, 1], about half of them 0: class centres
    and rows live in a ``latent``-dimensional space, are lifted to
    ``n_features`` by one random basis, get independent pixel noise and
    are clipped.  ``separation`` is the centres' spread over the rows'
    own, which sets how far the classes overlap.  Classes are balanced
    and interleaved at random."""
    n, d, k = spec["n_samples"], spec["n_features"], spec["n_classes"]
    latent = spec["latent"]
    rng = np.random.default_rng(spec["base_seed"])
    basis = rng.standard_normal((latent, d)).astype(np.float32)
    basis /= np.sqrt(latent)
    centres = (spec["separation"]
               * rng.standard_normal((k, latent))).astype(np.float32)
    y = rng.permutation(np.arange(n) % k)
    z = centres[y] + rng.standard_normal((n, latent), dtype=np.float32)
    u = z @ basis
    # uniform pixel noise of unit variance: a byte a pixel is the
    # cheapest 55 million random numbers numpy makes
    levels = (np.arange(256, dtype=np.float32) - 127.5) * np.float32(
        spec["pixel_noise"] / 73.9)
    u += levels[rng.integers(0, 256, (n, d), dtype=np.uint8)]
    u *= np.float32(0.35)
    return np.clip(u, 0.0, 1.0, out=u), y.astype(np.int64)


def expand_grid(grid, seed):
    """A traffic file's parameter grid with each parameter's values in
    the seed's order: a list is taken as it is, and
    ``{"logspace": [lo, hi, n]}`` is ``numpy.logspace(lo, hi, n)``."""
    rng = np.random.default_rng(int(seed))
    out = {}
    for name in sorted(grid):
        values = grid[name]
        if isinstance(values, dict):
            lo, hi, num = values["logspace"]
            values = np.logspace(lo, hi, int(num)).tolist()
        out[name] = [values[i] for i in rng.permutation(len(values))]
    return out


def search_factory(config, traffic, seed):
    """() -> a new, unfitted search object, as the traffic mix and the
    configuration describe it: the public call a user writes."""
    estimator_cls = load_object(config["estimator"]["class"])
    search_cls = load_object(traffic["search"]["class"])
    cv_cls = load_object(config["cv"]["class"])
    grid = expand_grid(traffic["param_grid"], seed)

    def new_search():
        return search_cls(
            estimator_cls(**config["estimator"]["params"]), grid,
            cv=cv_cls(**config["cv"]["params"]),
            **traffic["search"]["params"])

    return new_search, grid
