"""Sharding rules: divisibility fallback, EP-vs-TP selection, and a
multi-device numerical equivalence check (subprocess with 4 fake devices)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_production_mesh
from repro.models import param_shapes
from repro.sharding import rules


def _spec_of(tree, *path):
    node = tree
    for k in path:
        node = node[k]
    return node


@pytest.fixture(scope="module")
def prod_mesh():
    # the test process has 1 device; build an abstract mesh instead
    devs = jax.devices()
    if len(devs) >= 256:
        return make_production_mesh()
    return AbstractMesh((16, 16), ("data", "model"))


def test_dense_tp_fsdp_specs(prod_mesh):
    cfg = get_config("codeqwen1.5-7b")
    specs = rules.param_specs(cfg, param_shapes(cfg), prod_mesh)
    lay = specs["layers"]
    assert _spec_of(lay, "attn", "wq") == P(None, "data", "model")
    assert _spec_of(lay, "attn", "wo") == P(None, "model", "data")
    assert _spec_of(lay, "mlp", "w1") == P(None, "data", "model")
    assert _spec_of(lay, "mlp", "w2") == P(None, "model", "data")
    assert specs["embed"] == P("model", "data")
    assert _spec_of(lay, "ln1", "scale") == P()  # replicated


def test_moe_expert_parallel_when_divisible(prod_mesh):
    cfg = get_config("qwen3-moe-235b-a22b")  # 128 experts % 16 == 0 -> EP
    specs = rules.param_specs(cfg, param_shapes(cfg), prod_mesh)
    assert _spec_of(specs["layers"], "moe", "w1") == P(None, "model", "data", None)
    cfg2 = get_config("grok-1-314b")         # 8 experts, no EP -> TP on d_ff
    specs2 = rules.param_specs(cfg2, param_shapes(cfg2), prod_mesh)
    assert _spec_of(specs2["layers"], "moe", "w1") == P(None, None, "data", "model")


def test_divisibility_fallback_reported(prod_mesh):
    cfg = get_config("whisper-large-v3")     # vocab 51866 % 16 != 0
    specs = rules.param_specs(cfg, param_shapes(cfg), prod_mesh)
    assert specs["embed"][0] is None         # vocab dim fell back
    report = rules.fallback_report()
    assert any("embed" in r for r in report)


def test_no_axis_used_twice(prod_mesh):
    for arch in ("yi-34b", "qwen3-moe-235b-a22b", "xlstm-1.3b", "zamba2-7b"):
        cfg = get_config(arch)
        specs = rules.param_specs(cfg, param_shapes(cfg), prod_mesh)
        for leaf in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            axes = [a for a in leaf if a is not None]
            assert len(axes) == len(set(axes)), leaf


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models import init_params, param_shapes, loss_fn
    from repro.sharding import rules
    from repro.launch.mesh import make_local_mesh

    cfg = reduced(get_config("codeqwen1.5-7b"), n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                  vocab_size=256)
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}

    loss_1dev = float(loss_fn(cfg, params, batch)[0])

    mesh = make_local_mesh(2, 2)
    specs = rules.param_specs(cfg, param_shapes(cfg), mesh)
    with mesh:
        sharded = jax.device_put(params, rules.named(mesh, specs))
        loss_sharded = float(jax.jit(
            lambda p, b: loss_fn(cfg, p, b)[0])(sharded, batch))
    print(json.dumps({"single": loss_1dev, "sharded": loss_sharded}))
""")


def test_sharded_loss_matches_single_device():
    """Numerical equivalence of the sharded computation (4 fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(vals["single"] - vals["sharded"]) < 1e-3 * max(
        1.0, abs(vals["single"]))
