"""The main path's device programs compile for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: each test lowers a kernel at ``LANE_QUANTUM``
lanes for one chip of a described ``v5e:2x2`` host and compiles it, which
refuses what interpret mode and XLA CPU accept (unaligned slices, fast
memory over budget, programs that do not fit).  Nothing runs, so this
says nothing about results or times; ``chip_smoke.py`` on the chip does.

The topology is described inside the module fixture, never at import
time: only one process may hold the TPU library, and every test worker
imports this file.  The persistent compile cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cpzk_tpu.ops import backend, curve, pallas_kernels, verify

LANES = backend.LANE_QUANTUM


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


def _coords(sharding, lanes=LANES):
    return jax.ShapeDtypeStruct((curve.NLIMBS, lanes), jnp.int32,
                                sharding=sharding)


def _point(sharding, lanes=LANES):
    return tuple(_coords(sharding, lanes) for _ in range(4))


def _windows(sharding):
    return jax.ShapeDtypeStruct((curve.NWINDOWS, LANES), jnp.int32,
                                sharding=sharding)


def test_pallas_add_compiles(one_chip):
    call = pallas_kernels._add_call(LANES, pallas_kernels.BLOCK, False)
    pts = _point(one_chip) + _point(one_chip)
    d2 = _coords(one_chip, lanes=1)
    compiled = jax.jit(call).lower(*pts, d2).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_double_k_compiles(one_chip):
    call = pallas_kernels._double_k_call(4, LANES, pallas_kernels.BLOCK, False)
    compiled = jax.jit(call).lower(*_point(one_chip)[:3]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_combined_partial_kernel_compiles(one_chip):
    pts = [_point(one_chip) for _ in range(4)]
    wins = [_windows(one_chip) for _ in range(4)]
    compiled = jax.jit(verify.combined_partial_kernel).lower(
        *pts, *wins).compile()
    out = compiled.out_info
    assert [o.shape for o in out] == [(curve.NLIMBS, 1)] * 4


def test_verify_each_kernel_compiles(one_chip):
    g, h = _point(one_chip, lanes=1), _point(one_chip, lanes=1)
    rows = [_point(one_chip) for _ in range(4)]
    compiled = jax.jit(verify.verify_each_kernel).lower(
        g, h, *rows, _windows(one_chip), _windows(one_chip)).compile()
    assert compiled.out_info.shape == (LANES,)
