"""Substrate tests: optimizer, grad compression, checkpointing, fault
tolerance, data pipeline, budget controller."""
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import Checkpointer
from repro.core.budget import BudgetPolicy, CostModel
from repro.data.pipeline import TokenPipeline
from repro.runtime import FailureInjector, Supervisor


# ------------------------------------------------------------ optimizer --

def test_adamw_reduces_quadratic():
    cfg = optim.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                            weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0, 1.5])}
    state = optim.init_state(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state = optim.apply_updates(params, g, state, cfg)
    assert float(loss(params)) < 0.05


def test_schedule_warmup_and_decay():
    cfg = optim.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    assert float(optim.schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(optim.schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    end = float(optim.schedule(cfg, jnp.asarray(100)))
    assert abs(end - 0.1) < 1e-6


def test_grad_clip():
    cfg = optim.AdamWConfig(lr=0.0, grad_clip=1.0)
    params = {"w": jnp.zeros(3)}
    state = optim.init_state(params)
    g = {"w": jnp.array([100.0, 0.0, 0.0])}
    _, state = optim.apply_updates(params, g, state, cfg)
    # after clipping, first moment magnitude is bounded by (1-b1)*clip
    assert float(jnp.abs(state.m["w"][0])) <= (1 - cfg.b1) * 1.0 + 1e-6


# ------------------------------------------------- gradient compression --

def test_error_feedback_conserves_information():
    """sent + residual == accumulated gradient (nothing discarded)."""
    key = jax.random.PRNGKey(0)
    g = {"a": jax.random.normal(key, (64,)),
         "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 8))}
    ef = optim.init_error_feedback(g)
    sent, ef2, stats = optim.compress_topk(g, ef, frac=0.25)
    for k in g:
        np.testing.assert_allclose(
            np.asarray(sent[k] + ef2.residual[k]), np.asarray(g[k]),
            rtol=1e-6,
        )
        nz = np.count_nonzero(np.asarray(sent[k]))
        assert nz <= max(1, int(0.25 * g[k].size)) + 1
    assert 0 < stats["kept_frac"] <= 0.3


def test_error_feedback_catches_up():
    """A coordinate ignored at step t is boosted at t+1 (deferred, not lost)."""
    g = {"w": jnp.array([1.0, 0.9])}
    ef = optim.init_error_feedback(g)
    sent1, ef, _ = optim.compress_topk(g, ef, frac=0.5)
    assert float(sent1["w"][1]) == 0.0
    sent2, ef, _ = optim.compress_topk(g, ef, frac=0.5)
    # accumulated 0.9+0.9 = 1.8 > 1.0 -> now transmitted
    assert float(sent2["w"][1]) == pytest.approx(1.8)


# ------------------------------------------------------------ checkpoint --

def test_checkpoint_roundtrip_and_latest():
    key = jax.random.PRNGKey(0)
    tree = {"layer": {"w": jax.random.normal(key, (4, 4)),
                      "b": jnp.arange(4.0)},
            "step_count": jnp.asarray(3)}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(10, tree, extra={"step": 10, "rng": 7})
        ck.save(20, tree, extra={"step": 20})
        assert ck.latest_step() == 20
        restored, extra = ck.restore(tree, step=10)
        assert extra == {"step": 10, "rng": 7}
        np.testing.assert_allclose(
            np.asarray(restored["layer"]["w"]),
            np.asarray(tree["layer"]["w"]),
        )


def test_checkpoint_async_save():
    tree = {"w": jnp.ones((128, 128))}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, tree, blocking=False)
        ck.wait()
        assert ck.latest_step() == 1
        restored, _ = ck.restore(tree)
        np.testing.assert_allclose(np.asarray(restored["w"]), 1.0)


def test_checkpoint_shape_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {"w": jnp.ones((4,))})
        with pytest.raises(ValueError):
            ck.restore({"w": jnp.ones((5,))})


# -------------------------------------------------------- fault tolerance --

def test_supervisor_recovers_from_node_failure():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        inj = FailureInjector({12: "node_failure"})
        sup = Supervisor(ck, save_every=5, injector=inj)
        state = {"x": jnp.asarray(0.0)}

        def step_fn(state, step):
            return {"x": state["x"] + 1.0}

        final, report = sup.run(state, step_fn, num_steps=20)
        assert report["restarts"] == 1
        assert report["final_step"] == 20
        # every step after the restored checkpoint was re-executed
        assert float(final["x"]) == 20.0


def test_supervisor_straggler_degrades_eps():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        inj = FailureInjector({3: "straggler"})
        sup = Supervisor(ck, save_every=100, injector=inj,
                         budget_policy=BudgetPolicy(eps_max=0.1))
        state = {"x": jnp.asarray(0.0)}
        _, report = sup.run(state, lambda s, i: s, num_steps=5)
        assert len(report["stragglers"]) == 1
        step, eps = report["stragglers"][0]
        assert 0.0 <= eps <= 0.1


# ------------------------------------------------------------- pipeline --

def test_pipeline_determinism_and_sharding():
    from repro.configs import get_config
    cfg = get_config("qwen3-8b", smoke=True)
    p0 = TokenPipeline(cfg, global_batch=8, seq_len=16, seed=1,
                       shard_index=0, shard_count=2)
    p1 = TokenPipeline(cfg, global_batch=8, seq_len=16, seed=1,
                       shard_index=1, shard_count=2)
    b0a = p0.batch_at(5)
    b0b = p0.batch_at(5)
    np.testing.assert_array_equal(np.asarray(b0a["tokens"]),
                                  np.asarray(b0b["tokens"]))
    b1 = p1.batch_at(5)
    assert not np.array_equal(np.asarray(b0a["tokens"]),
                              np.asarray(b1["tokens"]))
    assert b0a["tokens"].shape == (4, 16)
    # labels are next-token shifted
    np.testing.assert_array_equal(
        np.asarray(b0a["tokens"][:, 1:]), np.asarray(b0a["labels"][:, :-1])
    )


def test_pipeline_prefetch_iterator():
    from repro.configs import get_config
    cfg = get_config("deepseek-7b", smoke=True)
    pipe = TokenPipeline(cfg, global_batch=4, seq_len=8)
    it = pipe.iterate()
    batches = [next(it) for _ in range(3)]
    assert all(b["tokens"].shape == (4, 8) for b in batches)


# ---------------------------------------------------------------- budget --

def test_cost_model_inversion():
    model = CostModel(c_fixed=0.1, c_stage1=1e-4, c_stage2=1e-3)
    n, r = 10_000, 20.0
    t_full = model.predict(n, r, 0.08)
    eps = model.solve_eps(n, r, t_full, eps_max=1.0)
    assert eps == pytest.approx(0.08, rel=1e-6)
    # no budget -> no refinement
    assert model.solve_eps(n, r, 0.0, eps_max=1.0) == 0.0


def test_cost_model_fit():
    true = CostModel(c_fixed=0.05, c_stage1=2e-4, c_stage2=3e-3)
    n, r, eps1 = 5000, 10.0, 0.2
    fitted = CostModel.fit(
        n, r, true.predict(n, r, 0.0), true.predict(n, r, eps1), eps1,
        t_fixed=0.05,
    )
    assert fitted.c_stage1 == pytest.approx(true.c_stage1, rel=1e-6)
    assert fitted.c_stage2 == pytest.approx(true.c_stage2, rel=1e-6)


def test_budget_policy_reexecution_floor():
    pol = BudgetPolicy(degrade_floor=0.02)
    assert pol.should_reexecute(0.01)
    assert not pol.should_reexecute(0.05)


# ------------------------------------------------------- multi-device -----

@pytest.mark.parametrize("check", [
    "engine_equivalence", "pipeline_parallel", "moe_ep_equivalence",
    "moe_ep_a2a_equivalence", "train_step_sharded", "elastic_restore",
])
def test_multidevice_checks_subprocess(check):
    """Engine/PP/EP/sharded-train/elastic-restore on an 8-device mesh, one
    check per subprocess so a failure names its check."""
    script = Path(__file__).parent / "_subproc" / "multidevice_checks.py"
    r = subprocess.run(
        [sys.executable, str(script), check], capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert f"OK {check}" in r.stdout, r.stdout


def test_persistent_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to one fixed directory inside the checkout."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.use_persistent_cache()
        assert first == compile_cache.use_persistent_cache()
        assert jax.config.jax_compilation_cache_dir == first
        repo = Path(__file__).resolve().parents[1]
        assert Path(first) == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
