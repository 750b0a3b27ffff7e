"""Compile rehearsal for the TPU v5e: the moscore routing kernels built by
the chip's own compiler for a described (not attached) ``v5e:2x2``
topology, at the paper fleet's size and at city scale. A kernel that
interpret mode accepts but Mosaic refuses fails here, at no chip time.

The topology is described only inside a module fixture: only one process
at a time may load the TPU library, and every test worker imports this
file. Everything in this file therefore stays in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moscore.ops import _pallas_hoisted_route, _pallas_route
from repro.launch import compile_cache


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("route", [_pallas_route, _pallas_hoisted_route],
                         ids=["pallas", "pallas_hoisted"])
@pytest.mark.parametrize("n_pairs,n_groups,window",
                         [(5, 5, 1024), (1024, 8, 4096)],
                         ids=["paper_fleet", "city_scale"])
def test_moscore_kernel_compiles_for_v5e(one_chip, route, n_pairs,
                                         n_groups, window):
    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table = spec(n_pairs, n_groups)
    compiled = route.lower(table, table, table,
                           spec(window, dtype=jnp.int32), spec(n_pairs),
                           delta=20.0, gamma=0.5,
                           interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/cache/from/env")
        assert compile_cache.enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == before   # untouched

        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path      # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
