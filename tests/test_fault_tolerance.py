"""Checkpointing, crash recovery, elastic resharding, straggler detection,
quantized gradient compression, and pipeline parallelism - on host devices."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.checkpoint import ckpt
from repro.runtime.ftolerance import StragglerMonitor, Trainer
from repro.quant.gradcomp import (init_error_feedback,
                                  pod_quantized_allreduce)

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(len(jax.devices()) < 8,
                       reason="needs 8 host devices"),
]


# ------------------------------------------------------------- checkpoints

def _toy_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (16, 8)),
                       "b": jnp.zeros((8,))},
            "opt": {"m": {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))},
                    "count": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32)}


def test_ckpt_roundtrip_atomic_keep_last(tmp_path):
    d = str(tmp_path)
    s = _toy_state()
    for step in (10, 20, 30, 40):
        ckpt.save(s, d, step, keep_last=2)
    assert ckpt.latest_step(d) == 40
    assert sorted(os.listdir(d)) == ["step_00000030", "step_00000040"]
    restored, step = ckpt.restore(_toy_state(seed=1), d)
    assert step == 40
    np.testing.assert_allclose(restored["params"]["w"], s["params"]["w"])


def test_ckpt_reshard_on_load(tmp_path):
    """Save from one sharding, restore onto a different mesh layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh

    d = str(tmp_path)
    s = _toy_state()
    mesh_a = make_host_mesh(2, 4)
    sh_a = {"params": {"w": NamedSharding(mesh_a, P("data", "model")),
                       "b": NamedSharding(mesh_a, P(None))},
            "opt": {"m": {"w": NamedSharding(mesh_a, P("data", "model")),
                          "b": NamedSharding(mesh_a, P(None))},
                    "count": NamedSharding(mesh_a, P())},
            "step": NamedSharding(mesh_a, P())}
    s_sharded = jax.device_put(s, sh_a)
    ckpt.save(s_sharded, d, 5)
    mesh_b = make_host_mesh(4, 2)       # elastic: different mesh shape
    sh_b = jax.tree.map(
        lambda ns: NamedSharding(mesh_b, ns.spec), sh_a,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    restored, _ = ckpt.restore(_toy_state(1), d, shardings=sh_b)
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(s["params"]["w"]))
    assert restored["params"]["w"].sharding.mesh.shape["data"] == 4


# ------------------------------------------------------------- trainer

def _make_trainer(tmp_path, fail_at=None, total=None):
    def init_state():
        return {"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}

    @jax.jit
    def step_fn(state, batch):
        x = state["x"] + batch
        return {"x": x, "step": state["step"] + 1}, {"loss": x}

    def next_batch(step):
        return jnp.float32(step + 1)   # deterministic in step

    return Trainer(step_fn=step_fn, init_state_fn=init_state,
                   next_batch_fn=next_batch, ckpt_dir=str(tmp_path),
                   ckpt_every=5, fail_at=fail_at)


def test_trainer_crash_recovery_equivalence(tmp_path):
    """Run with injected failures == uninterrupted run (exact state)."""
    clean = _make_trainer(tmp_path / "clean").run(23)
    faulty_tr = _make_trainer(tmp_path / "faulty", fail_at={7, 12, 12, 19})
    faulty = faulty_tr.run(23)
    assert faulty_tr.restarts >= 2
    np.testing.assert_allclose(float(faulty["x"]), float(clean["x"]))
    assert int(faulty["step"]) == int(clean["step"]) == 23


def test_straggler_monitor_flags_slow_steps():
    m = StragglerMonitor(threshold=2.0, warmup=2)
    for i in range(10):
        m.record(i, 0.1)
    m.record(10, 0.5)      # 5x the EMA
    assert m.flagged and m.flagged[-1][0] == 10
    m.record(11, 0.1)      # EMA not poisoned by the outlier
    assert abs(m.ema - 0.1) < 0.02


# ------------------------------------------------- gradient compression

def test_quantized_allreduce_matches_exact_within_tolerance():
    """2-pod compressed all-reduce ~= exact mean; error feedback shrinks the
    bias across repeated applications."""
    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g_pods = np.random.default_rng(0).normal(size=(2, 64, 32)).astype(np.float32)

    def run(gs, err):
        return pod_quantized_allreduce(gs, err)

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=({"w": jax.sharding.PartitionSpec("pod")},
                                 {"w": jax.sharding.PartitionSpec("pod")}),
                       out_specs=({"w": jax.sharding.PartitionSpec("pod")},
                                  {"w": jax.sharding.PartitionSpec("pod")}),
                       check_vma=False)
    with jax.set_mesh(mesh):
        err0 = jnp.zeros((2, 64, 32), jnp.float32)
        out, err = fn({"w": jnp.asarray(g_pods)}, {"w": err0})
    exact = g_pods.mean(0)
    got = np.asarray(out["w"])[0]    # every pod shard holds the same mean
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel            # int8: ~1/127 quantization error
    assert np.abs(np.asarray(err["w"])).max() > 0   # feedback state active


# ------------------------------------------------- pipeline parallelism

def test_gpipe_pipeline_matches_sequential():
    from repro.runtime.pipeline import pipeline_forward

    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(n_stages, d, d)) / np.sqrt(d),
                     jnp.float32)
    x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    with jax.set_mesh(mesh):
        out = pipeline_forward(stage_fn, ws, x, mesh=mesh, n_stages=n_stages)
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
