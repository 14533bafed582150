"""Substrate tests: data determinism, checkpoint roundtrip + elasticity,
optimizer, compression, fault-tolerant runtime."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.data import DataConfig, SyntheticLM, make_batch_iterator
from repro.optim import (
    AdamWConfig, apply_updates, clip_by_global_norm, global_norm, init_state,
)
from repro.optim.compression import (
    compressed_reduce, dequantize_int8, init_error_state, quantize_int8,
)
from repro.runtime import ElasticPolicy, HealthTracker, StepEvent, TrainLoopRunner


# -- data ---------------------------------------------------------------------

def test_data_deterministic_and_seekable():
    cfg = DataConfig(vocab_size=1000, seq_len=64, global_batch=8, seed=7)
    src = SyntheticLM(cfg)
    a = src.batch_at(123)
    b = src.batch_at(123)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    it = make_batch_iterator(cfg, start_step=123)
    c = next(it)
    np.testing.assert_array_equal(a["tokens"], c["tokens"])  # O(1) seek


def test_data_shards_disjoint_and_stable():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=8)
    src = SyntheticLM(cfg)
    s0 = src.batch_at(5, shard=0, n_shards=4)
    s1 = src.batch_at(5, shard=1, n_shards=4)
    assert s0["tokens"].shape == (2, 32)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_labels_are_shifted_tokens():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=2)
    b = SyntheticLM(cfg).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# -- checkpoint ---------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save(tmp_path, 10, t, extra={"note": "x"})
    assert latest_step(tmp_path) == 10
    out = restore(tmp_path, 10, t)
    np.testing.assert_array_equal(out["a"], t["a"])
    assert out["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_atomic_ignores_torn(tmp_path):
    t = _tree()
    save(tmp_path, 5, t)
    # simulate a torn write: tmp dir without manifest
    (tmp_path / "step_00000009.tmp").mkdir()
    (tmp_path / "step_00000007").mkdir()  # committed dir without manifest
    assert latest_step(tmp_path) == 5


def test_checkpoint_keep_k(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.latest() == 4
    assert latest_step(tmp_path) == 4
    steps = sorted(int(d.name[5:]) for d in tmp_path.iterdir()
                   if d.name.startswith("step_"))
    assert steps == [3, 4]


def test_checkpoint_elastic_restore_new_sharding(tmp_path):
    """Restore under a different sharding (the re-mesh path)."""
    t = _tree()
    save(tmp_path, 1, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = {"a": NamedSharding(mesh, P()), "b": {"c": NamedSharding(mesh, P())}}
    out = restore(tmp_path, 1, t, shardings=sh)
    np.testing.assert_array_equal(out["a"], t["a"])


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save(tmp_path, 1, _tree())
    bad = {"a": jnp.zeros((3, 3)), "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    with pytest.raises(ValueError):
        restore(tmp_path, 1, bad)


# -- optimizer ----------------------------------------------------------------

def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    st = init_state(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, st = apply_updates(params, grads, st, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_grad_clip_scales_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) > 1.0


def test_moment_dtype_respected():
    cfg = AdamWConfig(moment_dtype=jnp.bfloat16)
    st = init_state({"w": jnp.zeros((4,))}, cfg)
    assert st["m"]["w"].dtype == jnp.bfloat16


# -- compression --------------------------------------------------------------

def test_int8_quant_roundtrip_error_bounded():
    x = jnp.array(np.random.default_rng(0).normal(size=(1000,)), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_sum():
    """With error feedback, quantization error does not accumulate: the sum
    of dequantized outputs over T steps tracks the sum of true gradients."""
    rng = np.random.default_rng(1)
    grads_seq = [jnp.array(rng.normal(size=(256,)) * (10 ** rng.uniform(-3, 0)),
                           jnp.float32) for _ in range(50)]
    err = init_error_state({"g": grads_seq[0]})
    total_true = jnp.zeros((256,))
    total_sent = jnp.zeros((256,))
    for g in grads_seq:
        out, err = compressed_reduce({"g": g}, err)
        total_true += g
        total_sent += out["g"]
    resid = err["g"]
    np.testing.assert_allclose(total_sent + resid, total_true, rtol=1e-4, atol=1e-4)


# -- fault tolerance ----------------------------------------------------------

def test_health_tracker_flags_stragglers():
    tr = HealthTracker(n_hosts=4, straggler_factor=2.0, patience=2)
    for step in range(5):
        for h in range(4):
            sec = 1.0 if h != 3 else 5.0
            tr.observe(StepEvent(step, h, sec))
        slow = tr.stragglers()
    assert slow == [3]


def test_elastic_policy_ladder():
    pol = ElasticPolicy()
    assert pol.remesh(512) == {"multi_pod": True}
    assert pol.remesh(300) == {"multi_pod": False}
    assert pol.remesh(100) is None


def test_runner_restarts_and_resumes(tmp_path):
    """Crash at step 7 -> restart -> resume from checkpoint at step 5."""
    saves = {}
    calls = {"n": 0, "crashed": False}

    def build(mesh_kwargs):
        def step_fn(state, batch):
            return state + 1, {"loss": float(state)}

        return 0, step_fn, lambda step: step

    def save_fn(step, state):
        saves[step] = state

    def restore_fn(mesh_kwargs):
        if not saves:
            return None
        s = max(saves)
        return saves[s], s

    def fault(step, tracker):
        if step == 7 and not calls["crashed"]:
            calls["crashed"] = True
            tracker.observe(StepEvent(step, 0, 0.0, ok=False))
            raise RuntimeError("simulated host failure")

    pol = ElasticPolicy(feasible_meshes=((0, {}),))
    tr = HealthTracker(n_hosts=2)
    runner = TrainLoopRunner(build, save_fn, restore_fn, ckpt_every=5,
                             policy=pol, tracker=tr)
    out = runner.run(12, fault_hook=fault)
    assert out["steps"] == 12
    assert runner.restarts == 1
    assert 5 in saves and 10 in saves and 12 in saves


def test_runner_remesh_on_pod_loss():
    """Losing hosts below the 512 threshold must trigger a mesh downgrade."""
    events = []

    def build(mesh_kwargs):
        events.append(dict(mesh_kwargs))

        def step_fn(state, batch):
            return state + 1, {}

        return 0, step_fn, lambda step: step

    saves = {}
    crashed = {"done": False}

    def fault(step, tracker):
        if step == 3 and not crashed["done"]:
            crashed["done"] = True
            for h in range(256):  # a whole pod dies
                tracker.failed.add(h)
            raise RuntimeError("pod failure")

    tr = HealthTracker(n_hosts=512)
    runner = TrainLoopRunner(
        build, lambda s, st: saves.__setitem__(s, st),
        lambda mk: (max(saves.values()), max(saves)) if saves else None,
        ckpt_every=2, tracker=tr)
    out = runner.run(6, fault_hook=fault, mesh_kwargs={"multi_pod": True})
    assert out["steps"] == 6
    assert runner.remesh_events == [{"healthy": 256, "mesh": {"multi_pod": False}}]
    assert events[0] == {"multi_pod": True} and events[-1] == {"multi_pod": False}
