"""GPipe pipeline parallelism: multi-device equivalence vs sequential apply
(subprocess: 8 host devices), plus the bubble-fraction model."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.sharding.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 12) - 3 / 15) < 1e-12
    assert bubble_fraction(4, 4) < bubble_fraction(8, 4)


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.pipeline import pipeline_apply

    mesh = jax.make_mesh((4, 2), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    S, M, MB, D = 4, 6, 3, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (S, D, D)) * 0.3
    b = jax.random.normal(jax.random.fold_in(key, 1), (S, D)) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 2), (M, MB, D))

    def stage(params, xm):
        wi, bi = params
        return jnp.tanh(xm @ wi + bi)

    with mesh:
        y = jax.jit(lambda p, x: pipeline_apply(stage, p, x, mesh, "pod"))((w, b), x)

    # sequential oracle
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s] + b[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # gradients flow through the schedule (ppermute transpose)
    def loss(p):
        return jnp.sum(pipeline_apply(stage, p, x, mesh, "pod") ** 2)
    def loss_ref(p):
        w_, b_ = p
        r = x
        for s in range(S):
            r = jnp.tanh(r @ w_[s] + b_[s])
        return jnp.sum(r ** 2)
    with mesh:
        g = jax.jit(jax.grad(loss))((w, b))
    g_ref = jax.grad(loss_ref)((w, b))
    for a, c in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-4)
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_pipeline_matches_sequential_and_grads():
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=str(root), timeout=600,
    )
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr
