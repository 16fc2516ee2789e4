"""Ahead-of-time compiles of the served path's Pallas kernels for one chip
of a described TPU v5e, at the widths ``chip_smoke.py`` runs: 1,000,000
records (not a block multiple, so the pad path is in), 7000 reps, D=128,
k=8, plus a crack's few dozen new reps.  Nothing runs: this proves only
that the TPU compiler accepts each kernel and that it fits the chip.

The topology is described inside a fixture, never at import, so only the
worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.distance_topk.ops import distance_topk
from repro.kernels.fpf_update.ops import fpf_update
from repro.kernels.propagate.ops import MODES, propagate

N, C, D, K = 1_000_000, 7000, 128, 8
CRACK_C = 40
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    # without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("mode", MODES)
def test_propagate_compiles_for_v5e(one_chip, mode):
    n_classes = 9 if mode == "categorical" else None

    def serve_call(scores, ids, d2):
        return propagate(scores, ids, d2, mode, n_classes=n_classes,
                         impl="pallas", donate=False)

    compiled = jax.jit(serve_call).lower(
        _shape(one_chip, (C,), jnp.float32),
        _shape(one_chip, (N, K), jnp.int32),
        _shape(one_chip, (N, K), jnp.float32)).compile()
    _assert_kernel_fits(compiled)


@pytest.mark.parametrize("n_reps", [C, CRACK_C], ids=["build", "crack"])
def test_distance_topk_compiles_for_v5e(one_chip, n_reps):
    compiled = distance_topk.lower(
        _shape(one_chip, (N, D), jnp.float32),
        _shape(one_chip, (n_reps, D), jnp.float32), K,
        impl="pallas").compile()
    _assert_kernel_fits(compiled)


def test_fpf_update_compiles_for_v5e(one_chip):
    compiled = fpf_update.lower(
        _shape(one_chip, (N, D), jnp.float32),
        _shape(one_chip, (D,), jnp.float32),
        _shape(one_chip, (N,), jnp.float32), impl="pallas").compile()
    _assert_kernel_fits(compiled)
