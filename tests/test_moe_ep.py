"""Expert-parallel (shard_map + all-to-all) MoE vs the local oracle.

Runs in a subprocess so the 8 host devices don't leak into the rest of the
test session (jax locks device count at first init)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import moe as M
    from repro.models.common import init_params
    from repro.sharding import activation_ctx, make_plan

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch, over in [("granite-moe-1b-a400m", {}),
                       ("granite-moe-3b-a800m", {"n_experts": 6, "top_k": 2})]:
        cfg = get_config(arch).reduced()
        # generous capacity: no drops => EP must match the oracle EXACTLY
        cfg = dataclasses.replace(cfg, capacity_factor=8.0, **over)
        p = init_params(jax.random.PRNGKey(0), M.param_specs(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model)) * 0.5
        out_l, aux_l = M._moe_local(x.reshape(-1, cfg.d_model), p["router"],
                                    p["wi"], p["wg"], p["wo"], cfg,
                                    cfg.n_experts)
        out_l = out_l.reshape(x.shape)
        plan = make_plan(cfg, mesh)
        with mesh, activation_ctx(plan):
            out_ep, aux_ep = jax.jit(lambda p, x: M.moe(p, x, cfg))(p, x)
            g = jax.jit(jax.grad(lambda p: jnp.sum(M.moe(p, x, cfg)[0] ** 2)))(p)
        np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_l),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_l), rtol=1e-3)
        assert all(np.isfinite(np.asarray(v, np.float32)).all()
                   for v in jax.tree.leaves(g))
        print(arch, "EP==local OK")
    print("ALL_EP_OK")
""")


@pytest.mark.slow
def test_moe_ep_matches_local_oracle():
    import os
    import pathlib

    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=str(root), timeout=600,
    )
    assert "ALL_EP_OK" in out.stdout, out.stdout + out.stderr
