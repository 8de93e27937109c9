"""Compile every Pallas kernel a TPU selects, and the fused adjacency
program around them, for a described TPU v5e: Mosaic's refusals (tiling
alignment, unsupported slicing, VMEM) that interpret mode cannot show
surface here with no chip attached. Nothing runs; these are compiles.

The topology is described inside a module-scoped fixture — never at
import — and every compile happens in this process.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(jitted, *args, **static):
    compiled = jitted.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("adaptive", [True, False],
                         ids=["adaptive", "fixed"])
@pytest.mark.parametrize("n,batch,backend", [
    (64, 256, "pallas"),           # fused kernel: n <= 160
    (256, 32, "pallas_tiled"),     # tiled kernel above
    (576, 16, "pallas_tiled"),
])
def test_load_propagate_kernel_compiles(one_chip, n, batch, backend,
                                        adaptive):
    """Both hop-loop forms reach Mosaic: the loop that stops once the load
    pane drains, and the fixed n - 1 hops."""
    from repro.kernels.ops import _load_propagate, load_prop_tile

    nh = _spec((batch, n, n), jnp.int16, one_chip)
    l0 = _spec((batch, n, n), jnp.float32, one_chip)
    _compile(_load_propagate, nh, l0, max_hops=n - 1, adaptive=adaptive,
             backend=backend, tile=load_prop_tile(backend, n, batch))


@pytest.mark.parametrize("n,batch,backend", [
    (64, 16, "pallas"), (160, 8, "pallas"),
    (256, 8, "pallas_tiled"), (576, 4, "pallas_tiled"),
])
def test_apsp_kernel_compiles(one_chip, n, batch, backend):
    from repro.kernels.load_prop import pick_tile
    from repro.kernels.ops import _apsp

    tile = pick_tile(n, batch) if backend == "pallas_tiled" else None
    d = _spec((batch, n, n), jnp.float32, one_chip)
    _compile(_apsp, d, n_iters=None, backend=backend, tile=tile)


def test_minplus_and_flow_accum_compile(one_chip, monkeypatch):
    """The use_kernel=True paths and the APSP fallback past MAX_FUSED_N:
    compiled (not interpreted) on a TPU."""
    from repro.kernels.ops import flow_accumulate, minplus_matmul

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    a = _spec((2, 300, 200), jnp.float32, one_chip)
    b = _spec((2, 200, 260), jnp.float32, one_chip)
    _compile(minplus_matmul, a, b)
    for n, pairs in ((64, 300), (200, 5000)):
        _compile(flow_accumulate, _spec((n, n), jnp.float32, one_chip),
                 _spec((pairs,), jnp.int32, one_chip),
                 _spec((pairs,), jnp.int32, one_chip),
                 _spec((pairs,), jnp.float32, one_chip))


@pytest.mark.parametrize("n,pop", [(64, 256), (576, 16)])
def test_adjacency_program_compiles(topo, monkeypatch, n, pop):
    """The whole fused genome -> metrics program (decode, geometry,
    routing tables, load propagation) with the Pallas backend, on a
    one-device mesh of the described chip."""
    from repro.dse.genomes import AdjacencyPipeline, _adjacency_eval_fn
    from repro.opt.space import AdjacencySpace
    from repro.utils.jaxcompat import make_auto_mesh

    monkeypatch.setenv("REPRO_LOAD_PROP_BACKEND", "pallas")
    space = AdjacencySpace(n_chiplets=n, max_degree=8)
    # the host tables come from a pipeline on the local CPU device; only
    # their shapes travel to the described chip
    pipe = AdjacencyPipeline(space, make_auto_mesh(
        (1,), ("data",), devices=jax.devices()[:1]))
    tables = (pipe._pair_u, pipe._pair_v, pipe._pair_id, pipe._chain_slot,
              pipe._chain_eslot, pipe._inv_j, pipe._inv_c, pipe._col,
              pipe._row, pipe._side, pipe._phyx, pipe._phyy, pipe._cphyx,
              pipe._cphyy, pipe._bw, pipe._traffic, pipe._consts)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    bits = _spec((pop, space.genome_length), jnp.int32,
                 NamedSharding(mesh, P("data")))
    fn = _adjacency_eval_fn(mesh, n, pipe.k_phys, pipe._euclid,
                            pipe.max_hops, False)
    compiled = fn.lower(bits, *(_spec(t.shape, t.dtype, rep)
                                for t in tables)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fault_grid_program_compiles_and_fits_one_chip(topo, monkeypatch):
    """The fused [P, F] population x fault grid at the benchmark's fault
    cell size (n=64, P=256, F=33: 8,448 degraded designs in one program)
    compiles for one described v5e, and its buffers fit the chip's 16 GB
    with room for the rest of the process."""
    from repro.dse.genomes import AdjacencyPipeline, _adjacency_faults_fn
    from repro.opt.space import AdjacencySpace
    from repro.utils.jaxcompat import make_auto_mesh

    monkeypatch.setenv("REPRO_LOAD_PROP_BACKEND", "pallas")
    n, pop, F = 64, 256, 33
    space = AdjacencySpace(n_chiplets=n, max_degree=8)
    pipe = AdjacencyPipeline(space, make_auto_mesh(
        (1,), ("data",), devices=jax.devices()[:1]))
    tables = pipe._tables()
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    G = space.genome_length
    fn = _adjacency_faults_fn(mesh, n, pipe.k_phys, pipe._euclid,
                              pipe.max_hops, False)
    compiled = fn.lower(
        _spec((pop, G), jnp.int32, NamedSharding(mesh, P("data"))),
        _spec((F, G), jnp.bool_, rep), _spec((F, n), jnp.bool_, rep),
        *(_spec(t.shape, t.dtype, rep) for t in tables)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 8e9
