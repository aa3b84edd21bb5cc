"""The main path's Pallas kernels compile for a TPU v5e chip.

The TPU compiler ships with jaxlib, so these tests compile (never run)
each kernel with ``interpret=False`` for a *described* v5e chip and check
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
They catch what interpret mode cannot: block shapes the chip's tiling
refuses, and kernels that overrun the chip's fast memory.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every pytest-xdist worker
imports this file.  The persistent compile cache is off around these
compiles (an entry compiled for an absent chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.distill_loss import _phi_psi_bwd_call, _phi_psi_fwd_call
from repro.kernels.mixup_kernel import mixup_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _phi_psi_args(lead, sharding, bwd):
    """(logits, labels, g_rows[, dphi, dpsi]) shapes for a (*lead, 10)
    local-SGD batch: 16 samples x 10 classes, as every device draws."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    rows = lead + (16,)
    args = [s(rows + (10,)), s(rows, jnp.int32), s(rows + (10,))]
    if bwd:
        args += [s(rows), s(rows)]
    return args


def _compile_phi_psi(kernel, lead, sharding, bwd):
    def call(*a):
        return kernel(*a, interpret=False)
    for _ in lead:  # the trainer vmaps local SGD over the device axis
        call = jax.vmap(call)
    return jax.jit(call).lower(*_phi_psi_args(lead, sharding, bwd)).compile()


@pytest.mark.parametrize("lead", [(), (10,)], ids=["one_device", "vmap10"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_distill_phi_psi_compiles_for_v5e(one_chip, no_persistent_cache,
                                          direction, lead):
    kernel = _phi_psi_fwd_call if direction == "fwd" else _phi_psi_bwd_call
    compiled = _compile_phi_psi(kernel, lead, one_chip, direction == "bwd")
    assert "tpu_custom_call" in compiled.as_text()


def test_mixup_pallas_compiles_for_v5e(one_chip, no_persistent_cache):
    """Seed-prep shape: 10 devices x 10 seeds of 28x28x1 digits."""
    a = jax.ShapeDtypeStruct((100, 784), jnp.float32, sharding=one_chip)
    lam = jax.ShapeDtypeStruct((100,), jnp.float32, sharding=one_chip)
    compiled = mixup_pallas.lower(a, a, lam, lam, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
