"""Compile the partitioner's Pallas kernels for a TPU v5e that is described,
not attached, with ``interpret=False``, at the tile shapes of two seeded
small-world SNN graphs (fanout 16): the 4096-neuron graph ``chip_smoke.py``
solves (kcap 4) and the 131072-neuron target (kcap 256), plus 8192
partition lanes for the kernels whose working set grows with k, and gains
at degree bounds well above these graphs'.

What interpret mode cannot show fails here: block shapes off the (8, 128)
tiling, scalar memory or VMEM beyond the chip's, DMA slices the TPU cannot
issue. The topology is described inside a module fixture, never at import
time, so every pytest-xdist worker collects the same tests and only the
worker running this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# rows (= nodes = edges), incidence slots H, neighbor / traversal lanes
# U = L, pin slots per edge dbar, and kcap, from Caps.for_host and the
# ops.tile_bounds / h_bound of each graph
GRAPHS = {
    "snn4096": dict(n=4096, h=32, u=512, dbar=24, kcap=4),
    "snn131072": dict(n=131072, h=40, u=640, dbar=24, kcap=256),
}
KERNEL_CASES = [(g, k) for g in GRAPHS
                for k in (GRAPHS[g]["kcap"], 8192)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's programs cannot be read back from the persistent
    # cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tpu_custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("graph", list(GRAPHS))
def test_pair_scores_compiles_for_v5e(one_chip, graph):
    from repro.kernels.pair_scores.kernel import pair_scores_pallas
    g = GRAPHS[graph]
    n, u = g["n"], g["u"]
    c = pair_scores_pallas.lower(
        _spec((n, u), jnp.int32, one_chip), _spec((n, u), jnp.int32, one_chip),
        _spec((n, u), jnp.float32, one_chip),
        _spec((n, u), jnp.int32, one_chip),
        tn=8, lc=128, interpret=False).compile()
    assert _tpu_custom_calls(c) >= 1


@pytest.mark.parametrize("graph,k", KERNEL_CASES)
def test_gains_compiles_for_v5e(one_chip, graph, k):
    from repro.kernels.gains.kernel import gains_pallas
    g = GRAPHS[graph]
    n, h = g["n"], g["h"]
    c = gains_pallas.lower(
        _spec((n * h,), jnp.int32, one_chip),
        _spec((n, h), jnp.float32, one_chip),
        _spec((n, k), jnp.float32, one_chip),
        h=h, interpret=False).compile()
    assert _tpu_custom_calls(c) >= 1


# degree bounds past one 64-slot DMA round: 72 (two rounds) and a 1024-way
# hub node; the (8, H) scalar-memory blocks grow with H
@pytest.mark.parametrize("h", [72, 1024])
def test_gains_compiles_for_v5e_high_degree(one_chip, h):
    from repro.kernels.gains.kernel import gains_pallas
    n, k = 4096, 256
    c = gains_pallas.lower(
        _spec((n * h,), jnp.int32, one_chip),
        _spec((n, h), jnp.float32, one_chip),
        _spec((n, k), jnp.float32, one_chip),
        h=h, interpret=False).compile()
    assert _tpu_custom_calls(c) >= 1


@pytest.mark.parametrize("graph,kdim", KERNEL_CASES)
def test_pins_count_compiles_for_v5e(one_chip, graph, kdim):
    from repro.kernels.pins_count.kernel import pins_count_pallas
    g = GRAPHS[graph]
    n, dbar = g["n"], g["dbar"]
    c = pins_count_pallas.lower(
        _spec((n, dbar), jnp.int32, one_chip),
        _spec((n, dbar), jnp.int32, one_chip),
        kdim=max(kdim, 8), te=8, dc=dbar, interpret=False).compile()
    assert _tpu_custom_calls(c) >= 1
