"""Launch-layer integration: lower + compile the federated train/serve
steps on a small 8-host-device mesh, in a SUBPROCESS (this process must
keep seeing exactly 1 device — forcing device count is process-global).

This is the CI-sized replica of the 512-chip production dry-run.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.configs.base import InputShape
    from repro.launch import dryrun, mesh as meshlib, roofline

    arch, kind = "%s", "%s"
    cfg = configs.get(arch).reduced()
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = InputShape("t", 64, 8, kind)
    compiled, meta = dryrun.lower_one(cfg, shape, mesh, agg="user_centric")
    roof = roofline.analyze(compiled, cfg, shape, mesh_name="test",
                            chips=8, agg="user_centric",
                            abs_params_one=meta["abs_params_one"])
    print(json.dumps({
        "flops": roof.hlo_flops_per_chip,
        "coll": roof.collective_bytes_per_chip,
        "dom": roof.dominant,
    }))
""")


@pytest.mark.parametrize("arch,kind", [
    ("stablelm-1.6b", "train"),
    ("mamba2-1.3b", "train"),
    ("mixtral-8x7b", "train"),
    ("gemma2-9b", "decode"),
    ("zamba2-2.7b", "decode"),
    ("whisper-large-v3", "prefill"),
])
def test_small_mesh_lower_compile(arch, kind):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % (arch, kind)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0
    if kind == "train":
        # the user-centric mixing collective must be present
        assert res["coll"] > 0
