"""The main-path Pallas kernels compile for a TPU v5e chip.

Nothing here needs a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology, at the sizes the default plan dispatches to (b = 8,
nb = 256, n up to 1024 for the dense bulge and Q2 kernels, n = 4096 for the
band-strip bulge kernel, trailing views up to the fused kernel's VMEM
boundary m = 1280).  Each test asserts that the kernel is in the compiled
program, which is only true when Mosaic accepted its block shapes, its
lowering and its VMEM limit.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so a worker that is not given this file
must never touch it.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.backtransform import _sweep_shape
from repro.kernels.backtransform import backtransform_wy_pallas
from repro.kernels.bulge import bulge_strip_vmem_bytes, bulge_vmem_bytes, bulge_wavefront_pallas
from repro.kernels.limits import fits_vmem
from repro.kernels.fused_panel import fused_panel_update_pallas
from repro.kernels.syr2k import syr2k_lower_pallas

B = 8  # the TPU autotune bandwidth


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("m", [1024, 1280])
def test_fused_panel_update_compiles(one_chip, m):
    text = _compiled_text(
        lambda Bv: fused_panel_update_pallas(Bv, b=B, w=256, bm=128),
        _spec((m, m), one_chip),
    )
    assert "tpu_custom_call" in text


def test_bulge_wavefront_compiles(one_chip):
    text = _compiled_text(
        lambda Bb: bulge_wavefront_pallas(Bb, B), _spec((1024, 1024), one_chip)
    )
    assert "tpu_custom_call" in text


def test_bulge_wavefront_log_vmapped_compiles(one_chip):
    fn = jax.vmap(lambda Bb: bulge_wavefront_pallas(Bb, B, return_log=True))
    text = _compiled_text(fn, _spec((8, 1024, 1024), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("return_log", [False, True], ids=["values", "log"])
def test_bulge_strip_compiles_at_4096(one_chip, return_log):
    text = _compiled_text(
        lambda Bb: bulge_wavefront_pallas(Bb, B, return_log=return_log, strip=True),
        _spec((4096, 4096), one_chip),
    )
    assert "tpu_custom_call" in text
    assert "bulge_chase_strip" in text


@pytest.mark.parametrize("return_log", [False, True], ids=["values", "log"])
def test_bulge_vmem_counts_at_4096(return_log):
    # The strip fits the budget where the dense-resident matrix does not.
    assert fits_vmem(bulge_strip_vmem_bytes(4096, B, return_log=return_log))
    assert not fits_vmem(bulge_vmem_bytes(4096, B, return_log=return_log))


def test_backtransform_wy_compiles(one_chip):
    n = 1024
    S, K = _sweep_shape(n, B)
    text = _compiled_text(
        lambda X, vs, taus: backtransform_wy_pallas(X, vs, taus, b=B, group=16),
        _spec((n, n), one_chip), _spec((S, K, B), one_chip), _spec((S, K), one_chip),
    )
    assert "tpu_custom_call" in text


def test_syr2k_lower_compiles(one_chip):
    n, k = 3840, 256
    text = _compiled_text(
        lambda A, Bm, C: syr2k_lower_pallas(A, Bm, C, alpha=-1.0, bm=256, bk=256),
        _spec((n, k), one_chip), _spec((n, k), one_chip), _spec((n, n), one_chip),
    )
    assert "tpu_custom_call" in text
