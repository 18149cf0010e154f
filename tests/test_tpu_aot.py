"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is needed: the TPU compiler that ships with JAX compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip's compiler would refuse (tile-misaligned blocks, Mosaic
layouts, VMEM overruns).  One case per class of kernel Mosaic used to
refuse, at the sizes the chip smoke run serves:

* AXPYDOT at 2**24 — rank-1 operands (XLA's 1-D tiling vs Mosaic's);
* GEMVER at 8192 — vectors sharing a matrix's sublane axis;
* LM_RMSNORM at 4096 — a consumed scalar reduction in VMEM scratch;
* LM_DECODE_ATTN at 32768 — a (n, 48) operand blocked in memory order;
* ATAX at 16384 — a 1 GiB matrix with a consumed vector reduction;
* MLA_DECODE_ATTN at 131072 — depth-3 contractions over (heads, cache,
  latent) and a per-head (1, 16) vector carried in VMEM scratch, all in
  one online-softmax kernel (also cut to 128 steps over the cache).

Each Pallas compile must contain a Mosaic kernel (``tpu_custom_call``).
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import FusionCompiler, codegen
from repro.programs import REGISTRY
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_codegen(monkeypatch):
    """Compile Mosaic kernels from this CPU process: codegen asks
    ``jax.default_backend()`` whether a TPU is there, and a described
    chip is not.  The persistent compilation cache is off — it could
    not read these executables back without a chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(prog, shapes, sharding, lead=()):
    args = [jax.ShapeDtypeStruct(lead + tuple(shapes[k]), jnp.float32,
                                 sharding=sharding)
            for k in prog.plan.input_names]
    return prog.fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("name,n", [
    ("AXPYDOT", 1 << 24),
    ("GEMVER", 8192),
    ("LM_RMSNORM", 4096),
    ("LM_DECODE_ATTN", 32768),
    ("ATAX", 16384),
    ("MLA_DECODE_ATTN", 131072),
])
def test_pallas_program_compiles_for_v5e(name, n, one_chip, tpu_codegen):
    prog = REGISTRY[name]
    cc = FusionCompiler(backend="pallas", cache=None)
    compiled = cc.compile(prog.script, prog.shapes(n))
    hlo = _compile(compiled, prog.shapes(n), one_chip)
    assert hlo.count("tpu_custom_call") >= compiled.n_groups


def test_online_softmax_kernel_compiles_for_v5e(one_chip, tpu_codegen):
    """MLA_DECODE_ATTN's plan at the benchmark's size is one
    online-softmax kernel; it compiles as the predictor blocks it and
    with the cache cut into 128 steps of 1024 positions."""
    prog, n = REGISTRY["MLA_DECODE_ATTN"], 131072
    cc = FusionCompiler(backend="pallas", cache=None)
    compiled = cc.compile(prog.script, prog.shapes(n))
    (im,) = compiled.group_impls
    t = im.fusion.stream_root
    assert t is not None
    (gp,) = compiled.plan.groups
    blocks = tuple(1024 if r == t else b for r, b in zip(im.order, im.blocks))
    plan = dataclasses.replace(
        compiled.plan, groups=(dataclasses.replace(gp, blocks=blocks),))
    for p in (compiled, codegen.compile_plan(compiled.graph, plan)):
        assert "tpu_custom_call" in _compile(p, prog.shapes(n), one_chip)


def test_jnp_block_compiles_for_v5e(one_chip, tpu_codegen):
    prog = REGISTRY["LM_BLOCK"]
    compiled = FusionCompiler(backend="jnp", cache=None).compile(
        prog.script, prog.shapes(4096))
    hlo = _compile(compiled, prog.shapes(4096), one_chip)
    assert "tpu_custom_call" not in hlo


def test_engine_masked_batch_compiles_for_v5e(one_chip, tpu_codegen):
    """The serving path: the engine's vmap-batched, per-lane-masked
    decode attention program at a 32768 bucket, batch 2."""
    engine = ServingEngine(compiler=FusionCompiler(cache=None),
                           registry=REGISTRY, backend="pallas")
    script, shapes, _, masked = engine._compile_specs("LM_DECODE_ATTN",
                                                      32768)
    assert masked
    prog = engine.compiler.compile_batched(script, shapes, max_batch=2,
                                           backend="pallas")
    assert "tpu_custom_call" in _compile(prog, shapes, one_chip, lead=(2,))


def test_sharded_gemver_compiles_for_four_chips(topo, tpu_codegen):
    """The four-chip serving path: GEMVER shard_map-lifted over a
    ('data', 4) mesh of described chips — one kernel per group on each
    chip, no collectives."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    prog = REGISTRY["GEMVER"]
    sharded = FusionCompiler(backend="pallas", cache=None).compile_sharded(
        prog.script, prog.shapes(4096), mesh=mesh, max_batch=8)
    hlo = _compile(sharded, prog.shapes(4096),
                   NamedSharding(mesh, P("data")), lead=(8,))
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo
