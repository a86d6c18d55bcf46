"""Multi-device tests (subprocess with virtual CPU devices): distributed
GNN inference correctness + a reduced-mesh dry-run of the launch stack."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_distributed_gcn_matches_reference():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.gnn.layers import gcn_init, gcn_apply
        from repro.gnn.distributed import make_partition_plan, \\
            distributed_gcn_forward
        from repro.core.hicut import hicut_ref
        rng = np.random.default_rng(1)
        n, din, dh, dout = 80, 24, 16, 5
        adj = (rng.random((n, n)) < 0.08).astype(np.float32)
        adj = np.maximum(adj, adj.T); np.fill_diagonal(adj, 0)
        x = rng.normal(size=(n, din)).astype(np.float32)
        params = gcn_init(jax.random.PRNGKey(0), [din, dh, dout])
        ref = np.asarray(gcn_apply(params, jnp.asarray(x),
                                   jnp.asarray(adj), jnp.ones(n)))
        edges = np.transpose(np.nonzero(np.triu(adj)))
        assign = hicut_ref(n, edges) % 4
        plan = make_partition_plan(adj, assign, 4)
        mesh = Mesh(np.array(jax.devices()), ("servers",))
        out = distributed_gcn_forward(mesh, "servers", plan, params, x)
        print("ERR", float(np.abs(out - ref).max()))
    """, devices=4)
    err = float(out.split("ERR")[1])
    assert err < 1e-4


@pytest.mark.slow
def test_hicut_partition_reduces_halo_bytes():
    out = run_py("""
        import numpy as np
        from repro.core.hicut import hicut_ref
        from repro.gnn.distributed import make_partition_plan
        from repro.data.graphs import CORA, make_graph, sample_subgraph
        g = sample_subgraph(make_graph(CORA, seed=0), 200, 1200, seed=0)
        adj = g.adjacency()
        rng = np.random.default_rng(0)
        hic = hicut_ref(200, g.edges) % 4
        rand = rng.integers(0, 4, 200)
        bh = make_partition_plan(adj, hic, 4).bytes_per_aggregate(64)
        br = make_partition_plan(adj, rand, 4).bytes_per_aggregate(64)
        print("BYTES", bh, br)
    """, devices=4)
    bh, br = map(int, out.split("BYTES")[1].split())
    assert bh <= br


@pytest.mark.slow
def test_reduced_mesh_dryrun_lowers():
    """The launch-stack sharding rules lower + compile a reduced arch on a
    small (2,4) mesh — same code path as the 256/512-chip dry-run."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.models.config import reduced
        from repro.models import transformer as T
        from repro.launch.shardings import (param_shardings,
                                            batch_shardings,
                                            activation_shard_ctx)
        from repro.launch.shapes import params_specs
        from repro.optim.adamw import AdamWConfig
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = reduced(get_config("qwen3-0.6b"), d_model=128, d_ff=256,
                      vocab=512)
        p_sds = jax.eval_shape(lambda: T.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
        p_sh = param_shardings(p_sds, mesh)
        shard_ctx = activation_shard_ctx(cfg, mesh, 64, 8)
        b_sds = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        b_sh = batch_shardings(b_sds, mesh)
        step = T.make_train_step(cfg, AdamWConfig(lr=1e-3),
                                 shard_ctx=shard_ctx)
        from repro.optim.adamw import AdamState
        o_sds = jax.eval_shape(lambda: AdamState(
            step=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p_sds),
            nu=jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p_sds)))
        from repro.launch.shardings import opt_shardings
        o_sh = opt_shardings(p_sh, mesh)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))
        compiled = fn.lower(p_sds, o_sds, b_sds).compile()
        print("MEM", compiled.memory_analysis().temp_size_in_bytes)
    """, devices=8)
    assert "MEM" in out
