"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see what the chip's compiler
refuses: blocks off the (8, 128) tiling, layouts Mosaic cannot lower, or
more VMEM than a kernel may use. These tests compile each kernel of the
planner's main path for one chip of a described ``v5e:2x2`` topology,
with no chip attached, and check that the compiled program holds the
kernel (``tpu_custom_call``):

* ``rbf_gram_pallas`` at n = 1024, d = 3, and the vmapped batch form
  ``svr.fit_many`` builds for the ``cpu_space()`` families;
* ``plan_argmin_pallas`` and ``pareto_mask_pallas`` at B = 10,000 pending
  workloads, G = 66 (``tpu_space()``) and G = 352 (``cpu_space()``).

The topology is described inside a fixture (never at import or
collection), so every test worker collects the same tests and only the
worker running this file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import TIME_FLOOR
from repro.kernels import ops
from repro.kernels.plan_grid import pareto_mask_pallas, plan_argmin_pallas
from repro.kernels.rbf_gram import rbf_gram_pallas

BACKLOG = 10_000
GRID_WIDTHS = {"tpu_space": 66, "cpu_space": 352}  # nf * nc


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rbf_gram_compiles_at_n1024(one_chip):
    x = _spec(one_chip, (1024, 3))
    _assert_kernel_compiles(functools.partial(rbf_gram_pallas, gamma=0.5), x, x)


def test_rbf_gram_batch_form_compiles(one_chip):
    # 12 cpu_space families x 352 (f, cores) samples, through the dispatch
    # that svr.fit_many / predict_each call
    x = _spec(one_chip, (12, 352, 2))
    _assert_kernel_compiles(
        functools.partial(ops.rbf_gram, gamma=0.5, impl="pallas"), x, x
    )


@pytest.mark.parametrize("space", sorted(GRID_WIDTHS))
def test_plan_argmin_compiles_at_backlog_scale(one_chip, space):
    g = GRID_WIDTHS[space]
    _assert_kernel_compiles(
        functools.partial(plan_argmin_pallas, time_floor=TIME_FLOOR),
        _spec(one_chip, (BACKLOG, g)),
        _spec(one_chip, (1, g)),
        _spec(one_chip, (BACKLOG,)),
        _spec(one_chip, (BACKLOG, g)),
    )


@pytest.mark.parametrize("space", sorted(GRID_WIDTHS))
def test_pareto_mask_compiles_at_backlog_scale(one_chip, space):
    g = GRID_WIDTHS[space]
    spec = _spec(one_chip, (BACKLOG, g))
    _assert_kernel_compiles(pareto_mask_pallas, spec, spec, spec)
