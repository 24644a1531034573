"""Expert-parallel shard_map MoE vs the dense oracle (subprocess: needs
multiple host devices; this process must keep seeing 1)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.models import moe

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = moe.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                        capacity_factor=8.0, ep_axis="data")
    p = moe.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
    y_ref = moe.apply_reference(p, x, dataclasses.replace(cfg, ep_axis=None))
    moe.set_ep_mesh(mesh)
    y_ep, aux = jax.jit(
        lambda p, x: moe.apply_expert_parallel(p, x, cfg, cf2=8.0))(p, x)
    err = float(jnp.abs(y_ep - y_ref).max())
    assert err < 1e-4, err
    g = jax.grad(
        lambda p: moe.apply_expert_parallel(p, x, cfg, cf2=8.0)[0].sum())(p)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
    assert float(aux) > 0
    print("EP_OK", err)
""")


def test_expert_parallel_matches_dense_oracle():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "EP_OK" in out.stdout
