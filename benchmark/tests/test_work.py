"""The work counts against a count by hand on a tiny index."""

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np

from benchmark import cells
from benchmark.roofline import least_time, peaks


def _index():
    # Three lists along one axis: a query at 0 probes lists 0 and 1.
    centers = jnp.asarray([[0.0, 0.0], [1.0, 0.0], [10.0, 0.0]])
    return SimpleNamespace(centers=centers,
                           list_sizes=np.array([5, 7, 11]),
                           data=np.zeros((3, 11, 2), np.float32),
                           indices=np.zeros((3, 11), np.int32))


BATCHES = [np.array([[0.0, 0.0]], np.float32),
           np.array([[0.0, 0.0], [10.0, 0.0]], np.float32)]


def test_ivf_flat_by_hand():
    w = cells.load_module("work", "ivf_flat").count(
        _index(), {"n_probes": 2}, BATCHES, k=4)
    # batch 1: probes {0,1}; batch 2: {0,1} and {2,1}.
    flops = (2 * 1 * 3 * 2 + 2 * 2 * (5 + 7)) \
        + (2 * 2 * 3 * 2 + 2 * 2 * ((5 + 7) + (11 + 7)))
    row = 2 * 4 + 4
    nbytes = (4 * 3 * 2 + row * 12 + 4 * 1 * 2 + 8 * 1 * 4) \
        + (4 * 3 * 2 + row * 23 + 4 * 2 * 2 + 8 * 2 * 4)
    assert w == {"flops": float(flops), "bytes": float(nbytes)}


def test_least_time_names_its_bound():
    peak = peaks("TPU v5 lite")
    t = least_time({"flops": 197e12, "bytes": 1.0}, peak)
    assert t["bound"] == "flops" and abs(t["seconds"] - 1.0) < 1e-12
    t = least_time({"flops": 1.0, "bytes": 819e9}, peak)
    assert t["bound"] == "bytes" and abs(t["seconds"] - 1.0) < 1e-12


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        peaks("no such chip")
