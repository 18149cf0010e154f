"""repro.dist + sharded serving (DESIGN.md §7), explicit-mesh path.

Unlike tests/test_system.py and tests/test_moe_ep.py (which drive the
``jax.sharding.set_mesh`` ambient-mesh API), these tests pass meshes
explicitly.  The
multi-device cases run in subprocesses: the forced host device count
must be set before jax initializes.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import FusionCompiler, PlanCache
from repro.dist import moe_ep, sharding
from repro.serving import ServingEngine, ShardedServingEngine, replica_fill

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, timeout: int = 600, env_extra: dict | None = None):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               **(env_extra or {}))
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# routing (pure functions, no devices)
# ---------------------------------------------------------------------------

def test_replica_fill_even():
    assert replica_fill(8, 8, 4) == [2, 2, 2, 2]
    assert replica_fill(8, 8, 8) == [1] * 8
    assert replica_fill(16, 16, 1) == [16]


def test_replica_fill_uneven():
    # uneven queues front-load: partial replicas, then pure-padding ones
    assert replica_fill(5, 8, 4) == [2, 2, 1, 0]
    assert replica_fill(1, 8, 8) == [1, 0, 0, 0, 0, 0, 0, 0]
    assert replica_fill(9, 16, 4) == [4, 4, 1, 0]
    assert replica_fill(3, 8, 2) == [3, 0]
    assert all(sum(replica_fill(k, 16, 8)) == k for k in range(1, 17))


def test_fsdp_entry_divisibility():
    """The pspec rule only shards evenly-divisible dims and prefers the
    largest one."""
    e = sharding._fsdp_entry
    dp = ("pod", "data")
    # largest dim divisible -> sharded over dp
    assert e((6, 64, 128), dp, 4, 1, False) == jax.sharding.PartitionSpec(
        None, None, dp)
    # nothing divisible -> fully replicated
    assert e((3, 5), dp, 4, 1, False) == jax.sharding.PartitionSpec(
        None, None)
    # model picks the largest *remaining* divisible dim
    assert e((6, 64, 128), dp, 4, 2, True) == jax.sharding.PartitionSpec(
        None, "model", dp)
    # single dp axis stays a bare name
    assert e((8,), ("data",), 2, 1, False) == jax.sharding.PartitionSpec(
        "data")


def test_supported_needs_mesh():
    from repro.configs import smoke_config
    import dataclasses
    cfg = dataclasses.replace(smoke_config("grok1_314b"), n_experts=4)
    assert not moe_ep.supported(cfg)          # no ambient mesh
    with pytest.raises(ValueError):
        moe_ep.moe_layer_ep(cfg, np.zeros((1, 8, 64), np.float32), {})


def test_sharded_engine_single_device_fallback():
    """On a 1-device ('data',) mesh the sharded engine degrades to the
    base engine: same results, plain batched programs."""
    from repro.blas import REGISTRY, make_inputs
    from repro.launch.mesh import make_data_mesh
    if len(jax.devices()) != 1:
        pytest.skip("needs the default single-device CPU runtime")
    mesh = make_data_mesh(1)
    base = ServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                         max_batch=4, min_bucket=64)
    shd = ShardedServingEngine(mesh, compiler=FusionCompiler(cache=PlanCache()),
                               max_batch=4, min_bucket=64)
    assert shd.n_replicas == 1 and shd.max_batch == 4
    wl = [("AXPYDOT", 100, make_inputs(REGISTRY["AXPYDOT"], 100, seed=i))
          for i in range(6)]
    r1 = {r.rid: r for r in base.serve(wl)}
    r2 = {r.rid: r for r in shd.serve(wl)}
    for k in r1:
        for a, b in zip(r1[k].outputs, r2[k].outputs):
            assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# multi-device subprocess tests (8 forced host devices)
# ---------------------------------------------------------------------------

MOE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import numpy as np, jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.common import moe_layer
from repro.dist import moe_ep

mesh = make_mesh((2, 4), ("data", "model"))
out = {}
for tag, (E, k) in {"ep": (4, 2), "replica": (2, 1)}.items():
    cfg = dataclasses.replace(smoke_config("grok1_314b"), n_experts=E,
                              topk=k, capacity_factor=4.0,
                              n_shared_experts=0)
    rng = np.random.default_rng(0)
    G, Tg, D = 4, 64, cfg.d_model
    x = jnp.asarray(rng.standard_normal((G, Tg, D)), jnp.float32) * 0.3
    p = {"router": jnp.asarray(rng.standard_normal((D, E)), jnp.float32)*0.3,
         "wg": jnp.asarray(rng.standard_normal((E, D, cfg.d_ff_moe)), jnp.float32)*0.1,
         "wu": jnp.asarray(rng.standard_normal((E, D, cfg.d_ff_moe)), jnp.float32)*0.1,
         "wd": jnp.asarray(rng.standard_normal((E, cfg.d_ff_moe, D)), jnp.float32)*0.1}
    y_ref, _ = jax.jit(lambda x, p: moe_layer(cfg, x, p))(x, p)
    assert moe_ep.supported(cfg, mesh)
    y_ep, _ = jax.jit(lambda x, p: moe_ep.moe_layer_ep(cfg, x, p, mesh=mesh))(x, p)
    out[tag] = float(jnp.max(jnp.abs(y_ep - y_ref)))

    def loss(p):
        y, _ = moe_ep.moe_layer_ep(cfg, x, p, mesh=mesh)
        return jnp.sum(y * y)
    g = jax.jit(jax.grad(loss))(p)
    out[tag + "_gnorm"] = float(jnp.sqrt(sum(
        jnp.sum(v.astype(jnp.float32)**2)
        for v in jax.tree_util.tree_leaves(g))))
    # `with mesh:` ambient resolution (the context-manager form)
    with mesh:
        assert moe_ep.supported(cfg)
        y_amb, _ = jax.jit(lambda x, p: moe_ep.moe_layer_ep(cfg, x, p))(x, p)
    out[tag + "_ambient"] = float(jnp.max(jnp.abs(y_amb - y_ref)))
print(json.dumps(out))
"""


def test_moe_ep_matches_gspmd_explicit_mesh():
    """Explicit-mesh twin of tests/test_moe_ep.py: EP and replica paths
    match the GSPMD layer and carry gradients, on any supported jax."""
    out = _run(MOE_SCRIPT)
    for tag in ("ep", "replica"):
        assert out[tag] < 1e-4
        assert out[tag + "_ambient"] < 1e-4
        assert out[tag + "_gnorm"] > 0


PSPEC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import models
from repro.configs import ShapeConfig, smoke_config
from repro.dist import sharding
from repro.launch.mesh import make_mesh
from repro.launch import analysis
from repro.optim import AdamWHyper, abstract_opt_state
from repro.train import steps

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
out = {}
for arch, kind in [("llama3_8b", "train"), ("llama3_8b", "decode")]:
    cfg = smoke_config(arch)
    shape = ShapeConfig("t", 64, 8, kind)
    aps = models.abstract_params(cfg)
    pspecs = sharding.param_pspecs(cfg, aps, mesh)
    assert (jax.tree_util.tree_structure(pspecs)
            == jax.tree_util.tree_structure(aps))
    if kind == "train":
        step = steps.make_train_step(cfg, AdamWHyper())
        oabs = abstract_opt_state(cfg, aps)
        ospecs = sharding.opt_pspecs(cfg, oabs, mesh, aps)
        babs = steps.abstract_batch(cfg, shape)
        bspecs = sharding.batch_pspecs(cfg, babs, mesh)
        low = jax.jit(step,
                      in_shardings=({"params": pspecs, "opt": ospecs}, bspecs),
                      donate_argnums=(0,)).lower(
            {"params": aps, "opt": oabs}, babs)
    else:
        step = steps.make_decode_step(cfg)
        dec = steps.abstract_decode_inputs(cfg, shape)
        cspecs = sharding.cache_pspecs(cfg, dec["cache"], mesh)
        rep = NamedSharding(mesh, P())
        low = jax.jit(step, in_shardings=(pspecs, cspecs, rep, rep),
                      donate_argnums=(1,)).lower(
            aps, dec["cache"], dec["tokens"], dec["pos"])
    info = analysis.analyze(low, low.compile(),
                            body_multiplier=cfg.n_layers)
    out[f"{arch}/{kind}"] = info["collectives"]["by_kind"]
print(json.dumps(out))
"""


def test_pspecs_lower_with_collectives():
    """param/opt/batch/cache pspecs drive real train/decode lowerings on
    a (2,2,2) pod/data/model mesh; SPMD collectives must appear."""
    out = _run(PSPEC_SCRIPT)
    for cell, by_kind in out.items():
        assert by_kind, f"no collectives in {cell}"


EQ_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.blas import REGISTRY, make_inputs
from repro.core import FusionCompiler, PlanCache
from repro.serving import ServingEngine, ShardedServingEngine

# 16 requests per (sequence, bucket) on 8 replicas -> 2-row blocks per
# replica, the bit-stable regime (see ShardedServingEngine docstring)
wl, i = [], 0
for name in REGISTRY:
    for _ in range(16):
        wl.append((name, 100, make_inputs(REGISTRY[name], 100, seed=i)))
        i += 1

single = ServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                       max_batch=16, min_bucket=64)
shard = ShardedServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                             max_batch=16, min_bucket=64)
r1 = {r.rid: r for r in single.serve(wl)}
r2 = {r.rid: r for r in shard.serve(wl)}
mismatch = []
for k in r1:
    if not all(np.array_equal(a, b)
               for a, b in zip(r1[k].outputs, r2[k].outputs)):
        mismatch.append(r1[k].sequence)
ref_bad = []
for rid, (name, n, inputs) in enumerate(wl):
    ref = REGISTRY[name].reference(
        **{k: np.asarray(v, np.float64) for k, v in inputs.items()})
    for o, r in zip(r2[rid].outputs, ref):
        if not np.allclose(np.asarray(o, np.float64), r, rtol=1e-4,
                           atol=1e-4 * max(1.0, np.abs(r).max())):
            ref_bad.append(name)
st = shard.stats()
print(json.dumps({"mismatch": sorted(set(mismatch)),
                  "ref_bad": sorted(set(ref_bad)),
                  "n": len(r2), "n_replicas": st["n_replicas"],
                  "replica_rows": st["replica_rows"]}))
"""


def test_sharded_engine_bitwise_equal_all_sequences():
    """Every REGISTRY sequence served through the 8-replica sharded
    engine returns bitwise-identical outputs to the single-device
    engine, and matches the float64 numpy oracle."""
    out = _run(EQ_SCRIPT, timeout=1200)
    assert out["n_replicas"] == 8
    assert out["n"] == 16 * len(__import__("repro.blas",
                                           fromlist=["REGISTRY"]).REGISTRY)
    assert not out["mismatch"], f"bitwise mismatch: {out['mismatch']}"
    assert not out["ref_bad"], f"oracle mismatch: {out['ref_bad']}"
    assert all(r > 0 for r in out["replica_rows"])   # every replica used


UNEVEN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.blas import REGISTRY, make_inputs
from repro.core import FusionCompiler, PlanCache
from repro.serving import ShardedServingEngine

eng = ShardedServingEngine(compiler=FusionCompiler(cache=PlanCache()),
                           max_batch=8, min_bucket=64)
wl = [("AXPYDOT", 100, make_inputs(REGISTRY["AXPYDOT"], 100, seed=i))
      for i in range(5)]          # 5 requests over 8 replicas: uneven
for name, n, inputs in wl:
    eng.submit(name, n, inputs)
res = {r.rid: r for r in eng.drain()}
bad = []
for rid, (name, n, inputs) in enumerate(wl):
    ref = REGISTRY[name].reference(
        **{k: np.asarray(v, np.float64) for k, v in inputs.items()})
    for o, r in zip(res[rid].outputs, ref):
        if not np.allclose(np.asarray(o, np.float64), r, rtol=1e-4,
                           atol=1e-4 * max(1.0, np.abs(r).max())):
            bad.append(rid)
st = eng.stats()
(one,) = eng.serve([wl[0]])                    # single-request path
print(json.dumps({"bad": bad, "replica_rows": st["replica_rows"],
                  "n_dispatches": st["n_dispatches"],
                  "one_ok": bool(np.allclose(
                      np.asarray(one.outputs[0]),
                      np.asarray(res[0].outputs[0]), atol=1e-5))}))
"""


def test_sharded_engine_uneven_routing():
    """A queue smaller than the replica count still dispatches once,
    pads with pure-padding replicas, and returns correct slices."""
    out = _run(UNEVEN_SCRIPT)
    assert not out["bad"]
    assert out["n_dispatches"] == 1          # one padded 8-row dispatch
    # 5 real rows over 8 one-row blocks: front-loaded fill
    assert out["replica_rows"] == [1, 1, 1, 1, 1, 0, 0, 0]
    assert out["one_ok"]


CACHE_WARM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
from repro.blas import REGISTRY
from repro.core import FusionCompiler, PlanCache

cache = PlanCache(disk_dir=sys.argv[1] if len(sys.argv) > 1 else None)
cc = FusionCompiler(cache=cache)
for name in ("GEMVER", "AXPYDOT", "ATAX", "BiCGK"):
    seq = REGISTRY[name]
    cc.compile(seq.script, seq.shapes(64))
print(json.dumps(cache.stats.as_dict()))
"""


def test_plan_cache_concurrent_writers(tmp_path):
    """Two processes warming the same REPRO_PLAN_CACHE_DIR concurrently
    leave a consistent cache: every entry parses, no temp litter, and a
    fresh compiler is served from disk without re-searching."""
    from repro.blas import REGISTRY
    from repro.core.plan import ExecutionPlan

    d = str(tmp_path / "plans")
    env = dict(os.environ, REPRO_PLAN_CACHE_DIR=d)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [subprocess.Popen([sys.executable, "-c", CACHE_WARM_SCRIPT],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]

    files = os.listdir(d)
    assert not [f for f in files if f.endswith(".tmp")], files
    plans = [f for f in files if f.endswith(".plan.json")]
    assert len(plans) >= 4
    for f in plans:
        with open(os.path.join(d, f)) as fh:
            ExecutionPlan.from_json(fh.read())   # parses

    # a fresh in-process compiler warms from disk: plan hits, no writes
    cache = PlanCache(disk_dir=d)
    cc = FusionCompiler(cache=cache)
    for name in ("GEMVER", "AXPYDOT", "ATAX", "BiCGK"):
        seq = REGISTRY[name]
        cc.compile(seq.script, seq.shapes(64))
    st = cache.stats
    assert st.disk_hits == 4 and st.plan_misses == 0
    assert st.disk_writes == 0               # idempotent: nothing rewritten
