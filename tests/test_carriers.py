"""A narrow matrix crosses the Pallas kernel boundary with its last two
dims swapped (``predictor.carrier_swapped``), as XLA's TPU layout stores
it, so no relayout copy runs before the kernel (DESIGN.md §2).

The rule is checked on shapes alone; block legality and the VMEM count
follow the carrier's order; every registered program's swapped values
are pinned; and programs over narrow matrices run in the Pallas
interpreter, re-blocked to several steps along the long axis, against
float64 references.  ``tests/test_tpu_aot.py`` holds the rule to XLA's
own entry layouts and shows the copies gone on a described v5e.
"""
import numpy as np
import pytest

from repro.core import V5E, FusionCompiler, codegen, trace
from repro.core.plan import build_plan
from repro.core.predictor import (block_granules, carrier_swapped, cost_impl,
                                  operand_carrier, padded_bytes)
from repro.core.scheduler import Combination, best_combination, build_space
from repro.programs import REGISTRY

F32 = np.float32


@pytest.mark.parametrize("shape,swapped", [
    ((131072, 64), True), ((32768, 48), True), ((1024, 64), True),
    ((256, 64), True), ((128, 64), True), ((136, 64), True),
    ((131072, 8), True), ((4096, 100), True), ((8, 32768, 48), True),
    ((16, 64), False), ((16, 512), False), ((1, 64), False),
    ((131072, 127), False), ((131072, 128), False), ((16384, 16384), False),
], ids=str)
def test_swapped_exactly_when_it_pads_to_fewer_tiles(shape, swapped):
    """(131072, 127) pads to 16384 tiles either way: a tie keeps the
    natural order."""
    assert carrier_swapped(shape, F32, V5E) is swapped


def test_vectors_and_scalars_keep_their_carriers():
    assert operand_carrier((), (), F32, V5E) == ((1, 1), (1, 1), False)
    assert operand_carrier((4096,), (1024,), F32, V5E) == (
        (32, 128), (8, 128), False)
    assert operand_carrier((48,), (48,), F32, V5E) == ((1, 48), (1, 48), False)


def test_swapped_carrier_and_block_and_vmem():
    """MLA's kr block of 4096 rows: (64, 4096), 1 MiB a buffer, where
    the natural (4096, 64) pads to 128 lanes, 2 MiB."""
    c = operand_carrier((131072, 64), (4096, 64), F32, V5E)
    assert c == ((64, 131072), (64, 4096), True)
    assert padded_bytes(c.block, F32, V5E) == 1 << 20
    assert c.natural == ((131072, 64), (4096, 64), False)
    assert padded_bytes(c.natural.block, F32, V5E) == 2 << 20


def test_block_granules_follow_the_carrier_order():
    """A (1024, 48) matrix alone in a group: its long axis is the
    carrier's lane axis (128), its narrow axis the sublane axis (8)."""
    prog = REGISTRY["MADD"]
    g = trace(prog.script, {"A": (1024, 48), "B": (1024, 48)})
    (f,) = build_space(g).fusions
    i, j = (g.axis_root(a) for a in g.inputs[0].axis_ids)
    assert block_granules(f, g, V5E) == {i: 128, j: 8}
    square = trace(prog.script, prog.shapes(1024))
    (f,) = build_space(square).fusions
    i, j = (square.axis_root(a) for a in square.inputs[0].axis_ids)
    assert block_granules(f, square, V5E) == {i: 8, j: 128}


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_transposed_operands_of_every_program(name):
    """Only MLA's rotary key cache and decode attention's K and V are
    narrow; every BLAS program keeps its natural carriers."""
    want = {"MLA_DECODE_ATTN": ("kr",), "LM_DECODE_ATTN": ("K", "V")}
    prog = REGISTRY[name]
    cp = FusionCompiler(backend="pallas", interpret=True,
                        cache=None).compile(prog.script, prog.shapes(1024))
    assert cp.transposed_operands == want.get(name, ())
    jnp_cp = FusionCompiler(backend="jnp", cache=None).compile(
        prog.script, prog.shapes(1024))
    assert jnp_cp.transposed_operands == ()


def _reblocked(g, long_block):
    """The predictor's plan for ``g``, every block of a 1024-long axis
    cut to ``long_block``, for the Pallas interpreter."""
    impls = []
    for im in best_combination(build_space(g)).impls:
        sizes = dict(zip(im.fusion.axis_roots, im.fusion.axis_sizes))
        blocks = tuple(long_block if sizes[r] == 1024 else b
                       for r, b in zip(im.order, im.blocks))
        impls.append(cost_impl(im.fusion, g, im.order, blocks, V5E))
    combo = Combination(tuple(impls), sum(i.t_pred for i in impls))
    return codegen.compile_plan(g, build_plan(g, combo, backend="pallas"),
                                interpret=True)


@pytest.mark.parametrize("long_block", [128, 256, 1024])
def test_narrow_matrix_program_in_interpreter(long_block):
    """BiCGK over a (1024, 48) A: q = A p and s = A^T r read A carried
    (48, 1024), with blocks of the long axis moving along its lanes."""
    prog = REGISTRY["BiCGK"]
    shapes = {"A": (1024, 48), "p": (48,), "r": (1024,)}
    cp = _reblocked(trace(prog.script, shapes), long_block)
    assert cp.transposed_operands == ("A",)
    assert max(im.grid_steps for im in cp.group_impls) >= 1024 // long_block
    rng = np.random.default_rng(3)
    A = rng.standard_normal((1024, 48)).astype(F32)
    p = rng.standard_normal(48).astype(F32)
    r = rng.standard_normal(1024).astype(F32)
    q, s = cp(A=A, p=p, r=r)
    A64 = A.astype(np.float64)
    np.testing.assert_allclose(q, A64 @ p, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(s, A64.T @ r, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("long_block", [128, 1024])
def test_narrow_output_in_interpreter(long_block):
    """MADD's C = A + B on (1024, 48): the output leaves the kernel
    swapped too, and comes back in its natural order."""
    prog = REGISTRY["MADD"]
    shapes = {"A": (1024, 48), "B": (1024, 48)}
    cp = _reblocked(trace(prog.script, shapes), long_block)
    assert cp.transposed_operands == ("A", "B", "C")
    rng = np.random.default_rng(4)
    A, B = (rng.standard_normal((1024, 48)).astype(F32) for _ in "AB")
    C = cp(A=A, B=B)
    assert C.shape == (1024, 48)
    np.testing.assert_array_equal(np.asarray(C), A + B)
