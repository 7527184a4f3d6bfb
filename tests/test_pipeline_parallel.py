"""Pipeline-parallel wrapper == sequential stage application (subprocess:
needs multiple host devices)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.dist.pipeline import pipeline_apply, bubble_fraction

    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(0, 0.5, (n_stages, d, d)), jnp.float32)
    bs = jnp.asarray(rng.normal(0, 0.1, (n_stages, d)), jnp.float32)
    xs = jnp.asarray(rng.normal(0, 1, (n_micro, mb, d)), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    got = pipeline_apply(stage_fn, {"w": ws, "b": bs}, xs, mesh=mesh,
                         axis="stage")
    # sequential reference
    want = xs
    for s in range(n_stages):
        want = jnp.tanh(want @ ws[s] + bs[s])
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err
    assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
    print("PIPELINE_OK", err)
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PIPELINE_OK" in r.stdout
