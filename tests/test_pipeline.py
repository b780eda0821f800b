"""Pipeline parallelism: GPipe schedule equals sequential application."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.sharding.pipeline import bubble_fraction

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.sharding import api as shard_api
    from repro.sharding.pipeline import pipeline_apply, sequential_apply

    mesh = make_mesh((4, 2), ("stage", "data"))
    n_stages, b, d = 4, 8, 16
    key = jax.random.key(0)
    ws = 0.3 * jax.random.normal(key, (n_stages, d, d))
    bs = 0.1 * jax.random.normal(jax.random.key(1), (n_stages, d))
    params = {"w": ws, "b": bs}
    x = jax.random.normal(jax.random.key(2), (b, d))

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    with shard_api.use_mesh(mesh):
        y_pipe = jax.jit(lambda pp, xx: pipeline_apply(
            stage_fn, pp, xx, axis="stage", n_micro=4))(params, x)
    y_seq = sequential_apply(stage_fn, params, x)
    err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
    assert err < 1e-5, f"pipeline != sequential: {err}"
    print("PIPELINE_OK", err)
""")


@pytest.mark.slow
def test_pipeline_matches_sequential_subprocess():
    env = dict(os.environ)
    # pin the subprocess to CPU: the fake 8-device mesh is a host-platform
    # feature, and autodetect hangs probing TPU metadata in network-isolated
    # containers
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=300, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "PIPELINE_OK" in out.stdout


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-9
    assert bubble_fraction(4, 28) < 0.1      # planner sizing rule
