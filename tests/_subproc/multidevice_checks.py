"""Multi-device correctness checks (run in a subprocess with 8 host devices).

``python multidevice_checks.py <name>`` runs one check (``engine_equivalence``,
``pipeline_parallel``, ...) and prints ``OK <name>``, or raises.  Meshes
come from ``launch.mesh.make_host_mesh`` (Auto axes, the sharding mode
these paths use).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_host_mesh

assert len(jax.devices()) == 8, jax.devices()


def check_engine_equivalence():
    """MapReduce on a mesh == single-device run (kNN top-k merge)."""
    from repro.apps import knn
    from repro.core.engine import MapReduce, CombineSpec, shard_leading

    key = jax.random.PRNGKey(0)
    train_x = jax.random.normal(key, (512, 16))
    train_y = jax.random.randint(jax.random.fold_in(key, 1), (512,), 0, 5)
    test_x = jax.random.normal(jax.random.fold_in(key, 2), (32, 16))

    mesh = make_host_mesh((8,), ("data",))
    eng = MapReduce(mesh, axis="data")

    def map_fn(tx, ty):
        return knn.exact_map(tx, ty, test_x, k=4)

    def reduce_fn(gathered):
        return knn.merge_topk(gathered[0], gathered[1], 4)

    d_sh, l_sh = eng.run(
        map_fn, CombineSpec("all_gather", reduce_fn),
        shard_leading(mesh, "data", train_x),
        shard_leading(mesh, "data", train_y),
    )
    d_ref, l_ref = knn.exact_map(train_x, train_y, test_x, k=4)
    np.testing.assert_allclose(
        np.sort(np.asarray(d_sh), -1), np.sort(np.asarray(d_ref), -1),
        rtol=1e-5, atol=1e-5,
    )
    assert eng.last_shuffle_bytes > 0
    print("OK engine_equivalence")


def check_pipeline_parallel():
    from repro.parallel.pipeline_parallel import (
        pipeline_apply, sequential_reference,
    )
    mesh = make_host_mesh((4,), ("pipe",))
    key = jax.random.PRNGKey(0)
    stage_w = jax.random.normal(key, (4, 16, 16)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    micro = jax.random.normal(jax.random.fold_in(key, 1), (6, 8, 16))
    got = pipeline_apply(stage_fn, stage_w, micro, mesh)
    want = sequential_reference(stage_fn, stage_w, micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # gradients flow through the ppermute ring
    loss = lambda w: jnp.sum(pipeline_apply(stage_fn, w, micro, mesh) ** 2)
    g = jax.grad(loss)(stage_w)
    loss_ref = lambda w: jnp.sum(
        sequential_reference(stage_fn, w, micro) ** 2
    )
    g_ref = jax.grad(loss_ref)(stage_w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    print("OK pipeline_parallel")


def check_moe_ep_equivalence():
    """shard_map EP MoE == dense reference MoE on the same weights."""
    from repro.configs import get_config
    from repro.models import moe
    from repro.models.transformer import ParallelContext, _moe_ep_sharded

    cfg = get_config("moonshot-v1-16b-a3b", smoke=True).with_(
        n_experts=8, moe_top_k=2, capacity_factor=100.0,
    )
    key = jax.random.PRNGKey(3)
    p = moe.moe_init(key, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model))

    mesh = make_host_mesh((2, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), use_ep=True)
    got = jax.jit(lambda pp, xx: _moe_ep_sharded(pp, xx, cfg, ctx))(p, x)
    want = moe.moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("OK moe_ep_equivalence")


def check_moe_ep_a2a_equivalence():
    """all-to-all dispatch (§Perf A1) == dense reference at no-drop."""
    from repro.configs import get_config
    from repro.models import moe
    from repro.models.transformer import ParallelContext, _moe_ep_sharded

    cfg = get_config("moonshot-v1-16b-a3b", smoke=True).with_(
        n_experts=8, moe_top_k=2, capacity_factor=100.0,
        moe_dispatch="all_to_all",
    )
    key = jax.random.PRNGKey(5)
    p = moe.moe_init(key, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model))
    mesh = make_host_mesh((2, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), use_ep=True)
    got = jax.jit(lambda pp, xx: _moe_ep_sharded(pp, xx, cfg, ctx))(p, x)
    want = moe.moe_dense(p, x, cfg)
    # bf16 dispatch buffers: tolerance accordingly
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    print("OK moe_ep_a2a_equivalence")


def check_train_step_sharded():
    """One sharded train step on a 2x4 mesh runs and returns finite loss."""
    from repro import optim
    from repro.configs import get_config
    from repro.launch.train import make_train_step, synth_batch
    from repro.models import init_params, ParallelContext
    from repro.parallel import sharding as shard_lib

    cfg = get_config("qwen3-8b", smoke=True)
    mesh = make_host_mesh((2, 4), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, data_axes=("data",))
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    p_sh = shard_lib.param_shardings(params, cfg, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
    opt_state = optim.init_state(params)
    step = jax.jit(make_train_step(cfg, optim.AdamWConfig(lr=1e-3), ctx))
    batch = synth_batch(key, cfg, batch=4, seq=32)
    params, opt_state, metrics = step(params, opt_state, batch)
    l1 = float(metrics["loss"])
    for i in range(3):
        params, opt_state, metrics = step(params, opt_state, batch)
    l2 = float(metrics["loss"])
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1, (l1, l2)
    print("OK train_step_sharded")


def check_elastic_restore():
    """Checkpoint saved on 8-shard mesh restores onto a 4-shard mesh."""
    import tempfile
    from repro.checkpoint import Checkpointer

    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, (64, 8)),
            "b": jnp.arange(8.0)}
    mesh8 = make_host_mesh((8,), ("data",))
    sh8 = NamedSharding(mesh8, P("data"))
    tree8 = {"w": jax.device_put(tree["w"], sh8), "b": tree["b"]}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(7, tree8, extra={"step": 7})
        mesh4 = make_host_mesh((4,), ("elastic",))
        sh4 = {"w": NamedSharding(mesh4, P("elastic")),
               "b": NamedSharding(mesh4, P())}
        restored, extra = ck.restore(tree, shardings=sh4)
        assert extra["step"] == 7
        np.testing.assert_allclose(
            np.asarray(restored["w"]), np.asarray(tree["w"])
        )
        assert len(restored["w"].sharding.device_set) == 4
    print("OK elastic_restore")


CHECKS = {
    name[len("check_"):]: fn
    for name, fn in list(globals().items()) if name.startswith("check_")
}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
