"""Ahead-of-time compiles of the main-path solvers for a described TPU v5e.

The topology is described, not attached: the TPU compiler (Mosaic for the
Pallas kernel, XLA:TPU for the jnp solvers) runs here on the CPU host and
refuses what the chip would refuse.  Nothing executes.  Every compile
passes ``interpret=False`` explicitly, since ``ops.default_interpret()``
sees the CPU backend.

Describing the topology loads libtpu, which takes a per-host lockfile: run
this file in one process (``--dist loadfile`` under xdist) or set
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, or the fixture skips.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import single_task
from repro.core.dvfs import DvfsParams
from repro.kernels.dvfs_opt import dvfs_solve_kernel
from repro.kernels.layout import NCOL

#: One online chunk (``online.PIPELINE_CHUNK_TASKS``) on two classes.
MAIN_ROWS = 65536


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rows(n, cols, sharding):
    return jax.ShapeDtypeStruct((n, cols), jnp.float32, sharding=sharding)


@pytest.mark.parametrize("n", [128, MAIN_ROWS])
def test_dvfs_kernel_compiles_for_v5e(one_chip, n):
    fn = jax.jit(lambda m: dvfs_solve_kernel(m, interpret=False))
    compiled = fn.lower(_rows(n, NCOL, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("solver", [single_task.solve_with_deadline,
                                    single_task.solve_on_boundary])
def test_jnp_solver_compiles_for_v5e(one_chip, solver):
    col = jax.ShapeDtypeStruct((MAIN_ROWS,), jnp.float32, sharding=one_chip)
    params = DvfsParams(*([col] * len(dataclasses.fields(DvfsParams))))
    compiled = solver.lower(params, col).compile()
    assert compiled.as_text()
