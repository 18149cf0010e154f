"""End-to-end behaviour tests for the whole system: the fusion compiler
driving real BLAS workloads, and the distributed step functions lowering
with shardings on a multi-device mesh (subprocess: needs forced device
count before jax init)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.blas import REGISTRY
from repro.core import FusionCompiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Entry points keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says (and then set nothing themselves),
    else in the fixed <checkout>/.jax_cache."""
    from repro.launch import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(CHECKOUT / ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert (CHECKOUT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_needs_a_checkout(monkeypatch):
    """An installed package has no checkout to keep .jax_cache in: the
    helper then asks for JAX_COMPILATION_CACHE_DIR instead of writing
    next to the installation."""
    import repro.launch as launch
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(launch, "CHECKOUT", None)
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        launch.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_end_to_end_bicg_solver_iteration():
    """A realistic composite: one biconjugate-gradient iteration built
    from compiled fused sequences (BiCGK + AXPYDOT pieces)."""
    n = 512
    rng = np.random.default_rng(0)
    A = rng.standard_normal((n, n)).astype(np.float32) / np.sqrt(n)
    p = rng.standard_normal(n).astype(np.float32)
    r = rng.standard_normal(n).astype(np.float32)

    cc = FusionCompiler()
    bicgk = cc.compile(REGISTRY["BiCGK"].script, REGISTRY["BiCGK"].shapes(n))
    q, s = bicgk(A=A, p=p, r=r)
    np.testing.assert_allclose(np.asarray(q), A @ p, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), A.T @ r, rtol=1e-4, atol=1e-4)

    axpydot = cc.compile(REGISTRY["AXPYDOT"].script,
                         REGISTRY["AXPYDOT"].shapes(n))
    alpha = np.float32(0.3)
    z, rr = axpydot(w=r, v=np.asarray(q), u=p, alpha=alpha)
    np.testing.assert_allclose(np.asarray(z), r - alpha * np.asarray(q),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(rr),
                               float((r - alpha * np.asarray(q)) @ p),
                               rtol=1e-3)


def test_compile_report_stages():
    seq = REGISTRY["GEMVER"]
    cc = FusionCompiler()
    prog, rep = cc.compile(seq.script, seq.shapes(512), report=True)
    assert rep.n_fusions >= 5
    assert rep.n_combinations >= 2
    assert rep.predicted_speedup > 1.2   # GEMVER is the paper's best case


SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
sys.path.insert(0, r"{repo}/src")
from repro import models
from repro.configs import ShapeConfig, smoke_config
from repro.dist import sharding
from repro.launch.mesh import make_mesh
from repro.launch import analysis
from repro.optim import AdamWHyper, abstract_opt_state
from repro.train import steps

cfg = smoke_config("{arch}")
shape = ShapeConfig("t", 64, 8, "{kind}")
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
aps = models.abstract_params(cfg)
pspecs = sharding.param_pspecs(cfg, aps, mesh)
with jax.sharding.set_mesh(mesh):
    if "{kind}" == "train":
        step = steps.make_train_step(cfg, AdamWHyper())
        oabs = abstract_opt_state(cfg, aps)
        ospecs = sharding.opt_pspecs(cfg, oabs, mesh, aps)
        babs = steps.abstract_batch(cfg, shape)
        bspecs = sharding.batch_pspecs(cfg, babs, mesh)
        low = jax.jit(step, in_shardings=({{"params": pspecs, "opt": ospecs}}, bspecs),
                      donate_argnums=(0,)).lower(
            {{"params": aps, "opt": oabs}}, babs)
    else:
        step = steps.make_decode_step(cfg)
        dec = steps.abstract_decode_inputs(cfg, shape)
        cspecs = sharding.cache_pspecs(cfg, dec["cache"], mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        low = jax.jit(step, in_shardings=(pspecs, cspecs, rep, rep),
                      donate_argnums=(1,)).lower(
            aps, dec["cache"], dec["tokens"], dec["pos"])
    comp = low.compile()
info = analysis.analyze(low, comp, body_multiplier=cfg.n_layers)
print(json.dumps({{"ok": True,
                  "collectives": info["collectives"]["by_kind"],
                  "mem": info["memory"].get("total_bytes_per_device")}}))
"""


@pytest.mark.parametrize("arch,kind", [
    ("llama3_8b", "train"), ("grok1_314b", "train"),
    ("deepseek_v2_lite", "train"), ("mamba2_2p7b", "decode"),
    ("llama3_8b", "decode"), ("whisper_medium", "decode"),
])
def test_multipod_lowering_smoke(arch, kind):
    """(2,2,2) pod/data/model mesh on 8 host devices: lower+compile the
    real step functions for reduced configs; collectives must appear."""
    script = SUBPROC_SCRIPT.format(repo=REPO, arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["ok"]
    assert data["collectives"], "expected SPMD collectives on a 2x2x2 mesh"


def test_dryrun_artifacts_complete():
    """If the full dry-run sweep has been run, every supported cell must
    have passed on both meshes (the multi-pod deliverable)."""
    from repro.configs import ARCHS, supported_cells
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("dry-run sweep not executed yet")
    missing, failed = [], []
    for a in ARCHS:
        for s in supported_cells(a):
            for m in ("pod1", "pod2"):
                p = os.path.join(d, f"{a}__{s}__{m}.json")
                if not os.path.exists(p):
                    missing.append((a, s, m))
                    continue
                with open(p) as f:
                    if not json.load(f).get("ok"):
                        failed.append((a, s, m))
    assert not failed, f"dry-run failures: {failed}"
    assert not missing, f"dry-run cells missing: {missing}"
