"""The names the program gives its work in a profiler trace
(``repro.core.tracing``): each fused group's label in the lowered
program's op metadata, stable from one compile to the next, and one
``repro.dispatch`` host span per call."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FusionCompiler, tracing
from repro.programs import REGISTRY

N = 256
#: the labels of each program's plan at ``N``, in topological order
LABELS = {
    "GEMVER": ["g0_rank2_update_gemtv_xpay_gemv_scal"],
    "AXPYDOT": ["g0_axmy_ew_mul_sum_reduce"],
}
BACKENDS = [("jnp", False), ("pallas", True)]


def _compile(name, backend, interpret):
    prog = REGISTRY[name]
    cc = FusionCompiler(backend=backend, interpret=interpret, cache=None)
    return cc.compile(prog.script, prog.shapes(N))


def _inputs(name):
    rng = np.random.default_rng(0)
    return {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
            for k, s in REGISTRY[name].shapes(N).items()}


@pytest.mark.parametrize("backend,interpret", BACKENDS)
@pytest.mark.parametrize("name", sorted(LABELS))
def test_group_labels_reach_the_lowered_program(name, backend, interpret):
    compiled = _compile(name, backend, interpret)
    assert compiled.group_labels == LABELS[name]
    args = [_inputs(name)[k] for k in compiled.plan.input_names]
    text = compiled.fn.lower(*args).as_text(debug_info=True)
    for label in compiled.group_labels:
        assert f"/{label}/" in text, label


@pytest.mark.parametrize("backend,interpret", BACKENDS)
@pytest.mark.parametrize("name", sorted(LABELS))
def test_group_labels_are_stable_across_compiles(name, backend, interpret):
    first = _compile(name, backend, interpret)
    second = _compile(name, backend, interpret)
    assert first is not second
    assert first.group_labels == second.group_labels
    assert all(label.isidentifier() for label in first.group_labels)


def test_one_dispatch_span_per_call(tmp_path):
    compiled = _compile("AXPYDOT", "jnp", False)
    inputs = _inputs("AXPYDOT")
    jax.block_until_ready(compiled(**inputs))      # compiled before the trace
    calls = 5
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(calls):
            jax.block_until_ready(compiled(**inputs))
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    spans = [e for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == tracing.DISPATCH]
    assert len(spans) == calls
