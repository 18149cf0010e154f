"""shard_map expert-parallel MoE (P10): numerical equivalence with the
GSPMD path, replica placement, and gradient flow through all-to-all.
Runs in a subprocess with 8 forced host devices."""
import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, sys
import numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, r"{repo}/src")
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.common import moe_layer
from repro.dist import moe_ep

mesh = make_mesh((2, 4), ("data", "model"))
out = {{}}

# divisible-EP path (E=4, M=4) and replica path (E=2, M=4)
for tag, (E, k) in {{"ep": (4, 2), "replica": (2, 1)}}.items():
    cfg = dataclasses.replace(smoke_config("grok1_314b"), n_experts=E,
                              topk=k, capacity_factor=4.0,
                              n_shared_experts=0)
    rng = np.random.default_rng(0)
    G, Tg, D = 4, 64, cfg.d_model
    x = jnp.asarray(rng.standard_normal((G, Tg, D)), jnp.float32) * 0.3
    p = {{"router": jnp.asarray(rng.standard_normal((D, E)), jnp.float32)*0.3,
         "wg": jnp.asarray(rng.standard_normal((E, D, cfg.d_ff_moe)), jnp.float32)*0.1,
         "wu": jnp.asarray(rng.standard_normal((E, D, cfg.d_ff_moe)), jnp.float32)*0.1,
         "wd": jnp.asarray(rng.standard_normal((E, cfg.d_ff_moe, D)), jnp.float32)*0.1}}
    y_ref, _ = jax.jit(lambda x, p: moe_layer(cfg, x, p))(x, p)
    with jax.sharding.set_mesh(mesh):
        assert moe_ep.supported(cfg)
        y_ep, _ = jax.jit(lambda x, p: moe_ep.moe_layer_ep(cfg, x, p))(x, p)
    out[tag] = float(jnp.max(jnp.abs(y_ep - y_ref)))

    def loss(p):
        y, _ = moe_ep.moe_layer_ep(cfg, x, p)
        return jnp.sum(y * y)
    with jax.sharding.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(p)
    gn = float(jnp.sqrt(sum(jnp.sum(v.astype(jnp.float32)**2)
                            for v in jax.tree_util.tree_leaves(g))))
    out[tag + "_gnorm"] = gn
print(json.dumps(out))
"""


def test_moe_ep_matches_gspmd_and_has_grads():
    script = SCRIPT.format(repo=REPO)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ep"] < 1e-4
    assert out["replica"] < 1e-4
    assert out["ep_gnorm"] > 0 and out["replica_gnorm"] > 0
