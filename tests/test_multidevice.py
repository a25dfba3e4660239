"""Multi-device numerical equivalence (8 forced host devices, subprocess).

The H1 optimization routes MoE dispatch through shard_map when a mesh is
active; this must be bit-close to the meshless vmap path.  Also checks
elastic mesh replanning.  Runs in a subprocess because the device count
must be forced before jax initializes.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import build_model, shardctx
from repro.launch.elastic import replan_mesh

cfg = get_smoke_config("grok-1-314b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)

# meshless (vmap dispatch)
ref = np.asarray(model.forward(params, toks))

# on a (2, 4) mesh with train rules (shard_map dispatch)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with shardctx.use_mesh(mesh, shardctx.train_rules(False)):
    got = np.asarray(jax.jit(model.forward)(params, toks))
np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
print("moe shard_map == vmap OK")

# elastic: lose half the chips, keep model parallel degree
m2 = replan_mesh(4, model_parallel=4)
assert dict(zip(m2.axis_names, m2.devices.shape)) == {"data": 1, "model": 4}
with shardctx.use_mesh(m2, shardctx.train_rules(False)):
    got2 = np.asarray(jax.jit(model.forward)(params, toks))
np.testing.assert_allclose(got2, ref, rtol=2e-4, atol=2e-4)
print("elastic remesh forward OK")
"""


@pytest.mark.slow
def test_moe_shard_map_equivalence_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "moe shard_map == vmap OK" in r.stdout
    assert "elastic remesh forward OK" in r.stdout
