"""Compile rehearsals for a described TPU v5e (nothing runs).

The served forward (``_forward_blocks_multi``) and the GNN aggregate Pallas
kernels are compiled at PubMed widths for chips that are described, not
attached, so a change the TPU compiler would refuse fails here on the CPU.
The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler, and it skips where none is installed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.gnn.distributed import PlanConsts, _forward_blocks_multi
from repro.kernels.gnn_aggregate.ops import (fused_gather_aggregate,
                                             gather_aggregate,
                                             normalized_aggregate)

F_IN, HIDDEN, CLASSES = 500, 16, 3      # synth-pubmed widths
# plan shapes of the 300-user / 4,800-link stream: every user on one chip,
# or spread over four with a halo; K is the padded neighbor-slot count
PLAN_SHAPES = {1: dict(block=312, halo=8, k=56),
               4: dict(block=80, halo=64, k=56)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep the cache out of these tests
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                yield topologies.get_topology_desc(platform="tpu",
                                                   topology_name="v5e:2x2")
            except Exception as e:          # no TPU compiler installed
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _forward_args(mesh: Mesh, chips: int, aggregate: str):
    """Shapes (with shardings) of one batch-of-one multi-plan forward."""
    shp = PLAN_SHAPES[chips]
    block, halo, k = shp["block"], shp["halo"], shp["k"]
    ext = block + chips * halo
    row, rep = NamedSharding(mesh, P("servers")), NamedSharding(mesh, P())

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct((chips, 1) + shape, dt, sharding=row)

    agg = ((s((block, ext)),) if aggregate == "dense" else
           (s((block, k), jnp.int32), s((block, k))))
    consts = PlanConsts(s((halo,), jnp.int32), s((halo,)), s((block,)),
                        s((ext,)), s((block,)), agg)
    ws = tuple(jax.ShapeDtypeStruct(d, jnp.float32, sharding=rep)
               for d in ((F_IN, HIDDEN), (HIDDEN, CLASSES)))
    return s((block, F_IN)), consts, ws


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("aggregate", ["dense", "sparse", "fused"])
def test_served_forward_compiles(topo, chips, aggregate):
    mesh = Mesh(np.array(topo.devices[:chips]), ("servers",))
    x, consts, ws = _forward_args(mesh, chips, aggregate)
    compiled = _forward_blocks_multi.lower(mesh, "servers", aggregate, x,
                                           consts, ws).compile()
    if chips > 1:       # the halo exchange is a collective across chips
        assert "all-gather" in compiled.as_text()


@pytest.mark.parametrize("width", [F_IN, HIDDEN])
def test_dense_aggregate_kernel_compiles(one_chip, width):
    n = PLAN_SHAPES[1]["block"]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn = jax.jit(lambda a, x, r, c: normalized_aggregate(a, x, r, c,
                                                         impl="pallas"))
    compiled = fn.lower(s((n, n)), s((n, width)), s((n,)), s((n,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (rows, extended columns, K) of the neighbor-list kernels: the stream's
# one-chip plan, and synth-pubmed served whole (its hub sets K = 398, which
# sizes the SMEM index blocks and the resident [n_cols, 128] VMEM slab)
NEIGHBOR_SHAPES = {"stream": (312, 320, 56),
                   "synth_pubmed": (19717, 19718, 398)}


def _neighbor_args(one_chip, shape, width):
    n, n_cols, k = NEIGHBOR_SHAPES[shape]

    def s(dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    return (s((n, k), jnp.int32), s((n, k)), s((n_cols, width)), s((n,)),
            s((n_cols,)))


@pytest.mark.parametrize("shape", sorted(NEIGHBOR_SHAPES))
@pytest.mark.parametrize("width", [F_IN, HIDDEN])
def test_gather_aggregate_kernel_compiles(one_chip, shape, width):
    fn = jax.jit(lambda i, v, x, r, c: gather_aggregate(i, v, x, r, c,
                                                        impl="pallas"))
    compiled = fn.lower(*_neighbor_args(one_chip, shape, width)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(NEIGHBOR_SHAPES))
def test_fused_aggregate_kernel_compiles(one_chip, shape):
    w = jax.ShapeDtypeStruct((F_IN, HIDDEN), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda i, v, x, r, c, w_: fused_gather_aggregate(
        i, v, x, r, c, w_, impl="pallas"))
    compiled = fn.lower(*_neighbor_args(one_chip, shape, F_IN), w).compile()
    assert "tpu_custom_call" in compiled.as_text()
