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
  one online-softmax kernel (also cut to 128 steps over the cache);
* KDA_DECODE_STEP at 4096 — a fully indexed (rows, key, value) state
  read by broadcasts and sublane sums, and a rank-3 output written in
  (rows, 128, 128) blocks, in one sweep whose state read finishes in
  each grid step (the block-local barrier);
* GEMVER at 16384 — the block-local barrier over (16384, 128) column
  blocks: A read once and B written once by one kernel.

Each Pallas compile must contain a Mosaic kernel (``tpu_custom_call``).
A narrow matrix reaches its kernel in XLA's own layout, as a bitcast
with no relayout copy before the kernel (``predictor.carrier_swapped``,
checked against XLA's entry layouts here).
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import V5E, FusionCompiler, codegen
from repro.core.predictor import carrier_swapped
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


def _copies(hlo: str) -> list[tuple[str, str]]:
    """``(result type, opcode)`` of every copy and transpose in ``hlo``."""
    return re.findall(r"= (.+?) (copy|copy-start|transpose)\(", hlo)


def _relayouts(hlo: str, rows: int, cols: int) -> list[tuple[str, str]]:
    """The copies and transposes of a ``(rows, cols)`` matrix (batched
    or not), in either order."""
    return [c for c in _copies(hlo)
            if f"{rows},{cols}]" in c[0] or f"{cols},{rows}]" in c[0]]


def _kernel_operands(hlo: str) -> str:
    """The operand layout constraints of every Mosaic kernel."""
    return " ".join(re.findall(
        r"tpu_custom_call\", operand_layout_constraints=\{(.*?)\}, \w+=", hlo))


@pytest.mark.parametrize("name,n,width", [
    ("MLA_DECODE_ATTN", 131072, 64),
    ("LM_DECODE_ATTN", 32768, 48),
])
def test_narrow_operands_reach_kernel_without_copy(name, n, width,
                                                   one_chip, tpu_codegen):
    """MLA's kr (n, 64) and decode attention's K and V (n, 48) are
    stored by XLA with their long axis as lanes; carried swapped, each
    reaches its kernel as a bitcast, with no relayout copy before it,
    and no entry parameter of rank 2 is copied."""
    prog = REGISTRY[name]
    compiled = FusionCompiler(backend="pallas", cache=None).compile(
        prog.script, prog.shapes(n))
    hlo = _compile(compiled, prog.shapes(n), one_chip)
    assert _relayouts(hlo, n, width) == []
    assert not re.findall(r"= f32\[\d+,\d+\]\S* copy\(%input_vals", hlo)
    assert f"f32[{width},{n}]{{1,0}}" in _kernel_operands(hlo)


@pytest.mark.parametrize("name,n,copies", [
    ("GEMVER", 8192, {"copy": 2}),
    ("AXPYDOT", 1 << 24, {"copy": 1}),
    ("ATAX", 16384, {}),
])
def test_wide_programs_keep_their_copies(name, n, copies, one_chip,
                                         tpu_codegen):
    """No operand of the BLAS programs is narrow, so their compiled
    programs copy no matrix and transpose nothing: two scalars on
    GEMVER (whose x no longer moves between kernels), a scalar on
    AXPYDOT."""
    prog = REGISTRY[name]
    compiled = FusionCompiler(backend="pallas", cache=None).compile(
        prog.script, prog.shapes(n))
    assert compiled.transposed_operands == ()
    hlo = _compile(compiled, prog.shapes(n), one_chip)
    ops = [op for _, op in _copies(hlo)]
    assert {op: ops.count(op) for op in set(ops)} == copies


@pytest.mark.parametrize("shape", [
    (131072, 64), (32768, 48), (1024, 64), (256, 64), (128, 64), (136, 64),
    (131072, 8), (4096, 100), (8, 32768, 48),
    (16, 64), (16, 512), (1, 64), (131072, 127), (131072, 128),
], ids=str)
def test_carrier_orientation_is_xla_entry_layout(shape, one_chip,
                                                 tpu_codegen):
    """XLA stores a float32 array with its last two dims swapped exactly
    when ``carrier_swapped`` carries it so (a tie keeps row-major).  A
    JAX whose XLA chooses otherwise fails here, rather than quietly
    bringing back a relayout copy before every kernel."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fmt = jax.jit(lambda a: a * 2).lower(x).compile().input_formats[0][0]
    lead, r = tuple(range(len(shape) - 2)), len(shape)
    want = (r - 1, r - 2) if carrier_swapped(shape, jnp.float32, V5E) \
        else (r - 2, r - 1)
    assert fmt.layout.major_to_minor == lead + want


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


def test_kda_state_reaches_its_kernels_without_copy(one_chip, tpu_codegen):
    """KDA_DECODE_STEP at the benchmark's 4096 rows: two Mosaic kernels,
    the (4096, 128, 128) state S read once by one of them, which writes
    S_new, with no relayout copy or transpose of either."""
    prog, n = REGISTRY["KDA_DECODE_STEP"], 4096
    compiled = FusionCompiler(backend="pallas", cache=None).compile(
        prog.script, prog.shapes(n))
    assert compiled.input_passes["S"] == 1
    hlo = _compile(compiled, prog.shapes(n), one_chip)
    assert hlo.count("tpu_custom_call") >= compiled.n_groups == 2
    assert _relayouts(hlo, 128, 128) == []
    assert "f32[4096,128,128]{2,1,0}" in _kernel_operands(hlo)


def test_one_pass_gemver_compiles_without_relayout(one_chip, tpu_codegen):
    """GEMVER at the benchmark's 16384: the block-local group (rank-2
    update, both matrix-vector products and xpay over (16384, 128)
    column blocks) and scal compile as two Mosaic kernels, A reaching
    the first as it is stored and B leaving it so, with no copy or
    transpose of either."""
    prog, n = REGISTRY["GEMVER"], 16384
    compiled = FusionCompiler(backend="pallas", cache=None).compile(
        prog.script, prog.shapes(n))
    assert compiled.local_reductions == ("t",)
    hlo = _compile(compiled, prog.shapes(n), one_chip)
    assert hlo.count("tpu_custom_call") >= compiled.n_groups == 2
    assert _relayouts(hlo, n, n) == []
    assert f"f32[{n},{n}]{{1,0}}" in _kernel_operands(hlo)


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
    hlo = _compile(prog, shapes, one_chip, lead=(2,))
    assert "tpu_custom_call" in hlo
    # K and V, (2, 32768, 48) stored {1,2,0}, reach their kernels as
    # bitcasts to (2, 48, 32768): no copy of them, and no transpose
    assert "f32[2,48,32768]{2,1,0}" in _kernel_operands(hlo)
    assert _relayouts(hlo, 32768, 48) == []
    assert "transpose" not in [op for _, op in _copies(hlo)]


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
