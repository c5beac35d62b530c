"""The node model's true power (Eq. 7) is read on the host.

``PowerModel.at`` must return what the eager ``PowerModel.__call__``
returns, bit for bit, at every point the pools' nodes can run, and the
node model must never call ``__call__``: every seeded sample, fit and plan
downstream depends on both.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import governor
from repro.core.node_sim import FREQ_GRID, Node, RunResult
from repro.core.power import PAPER_COEFFS, PowerModel
from repro.fleet.cluster import DEFAULT_SPECS, DEVICE_COEFFS, TPU_SPECS

SPECS = DEFAULT_SPECS + TPU_SPECS
# every frequency any pool's node can be pinned at
FREQS = np.unique(
    np.concatenate([FREQ_GRID] + [np.asarray(s.freq_table) for s in SPECS])
)
SOCKET_SIZES = sorted({s.cores_per_socket for s in SPECS})


def _assert_host_matches_eager(model: PowerModel, max_cores: int) -> None:
    for cores_per_socket in SOCKET_SIZES:
        node = Node(cores_per_socket=cores_per_socket)
        for p in range(1, max_cores + 1):
            s = node.sockets(p)
            # one eager call per (p, s) over every frequency: p and s are the
            # Python ints the node passes, and each element of f goes through
            # the same one-op programs as a scalar f would
            eager = np.asarray(model(FREQS, p, s), np.float64)
            host = np.asarray([model.at(float(f), p, s) for f in FREQS])
            np.testing.assert_array_equal(host, eager, err_msg=f"p={p} s={s}")


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_host_truth_is_the_eager_truth_bit_for_bit(spec):
    assert SOCKET_SIZES == [16, 256]
    for coeffs in (spec.truth_coeffs(DEVICE_COEFFS[spec.device]), PAPER_COEFFS):
        _assert_host_matches_eager(PowerModel(*coeffs), spec.max_cores)


def test_host_truth_is_the_eager_truth_under_x64():
    spec = DEFAULT_SPECS[2]  # skewed coefficients
    with jax.enable_x64(True):
        model = PowerModel(*spec.truth_coeffs())
        assert model(1.2, 1, 1).dtype == np.float64
        _assert_host_matches_eager(model, spec.max_cores)


def _node_runs(seed: int):
    node = Node(seed=seed)
    return [
        node.run_fixed("raytrace", 1.8, 12, 3.0),
        node.measure_power(2.2, 32, n_samples=5),
        node.stress_grid(freqs=FREQ_GRID[:2], cores=range(1, 3)),
        node.run_governor("blackscholes", governor.OndemandGovernor(), 8, 1.0),
    ]


def _flatten(runs):
    out = []
    for r in runs:
        if isinstance(r, RunResult):
            out.extend(getattr(r, f.name) for f in dataclasses.fields(r))
        elif isinstance(r, tuple):
            out.extend(r)
        else:
            out.append(r)
    return out


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_node_model_never_dispatches_the_power_model(monkeypatch, seed):
    expected = _flatten(_node_runs(seed))

    def refuse(self, *args, **kwargs):
        raise AssertionError("the node model called PowerModel.__call__")

    monkeypatch.setattr(PowerModel, "__call__", refuse)
    got = _flatten(_node_runs(seed))
    assert len(got) == len(expected) == 6 + 1 + 4 + 6
    for a, b in zip(got, expected):
        np.testing.assert_array_equal(a, b)
