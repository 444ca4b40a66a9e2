"""Pipeline parallelism: GPipe schedule == sequential stage application
(numerical equality on 4 fake devices, subprocess)."""

import json
import os
import subprocess
import sys
import textwrap

from repro.training.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 12) - 3 / 15) < 1e-12
    assert bubble_fraction(4, 4) == 3 / 7


_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.training.pipeline import pipeline_apply

    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(0)
    S, M, MB, D = 4, 6, 2, 8
    w = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((S, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    params = {"w": w, "b": b}

    # sequential reference
    ref = x
    for s in range(S):
        ref = stage_fn({"w": w[s], "b": b[s]}, ref)

    with mesh:
        out = jax.jit(lambda p, xx: pipeline_apply(
            stage_fn, p, xx, mesh=mesh, axis="stage"))(params, x)

    err = float(jnp.max(jnp.abs(out - ref)))
    print(json.dumps({"err": err}))
""")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    err = json.loads(out.stdout.strip().splitlines()[-1])["err"]
    assert err < 1e-5, err
