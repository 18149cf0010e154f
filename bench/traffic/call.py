"""Traffic kind ``call``: a closed loop over one compiled program.

A mix of this kind gives ``n`` and ``input_sets``.  That many seeded input
sets of size ``n`` are made on the device in one jitted call and called
in turn, so no call repeats the one before it; each call ends in
``block_until_ready``, as one step of an iterative solver would.  Set-up
calls each set once (the first call compiles or loads the kernels), then
keeps calling for ``WARM_S`` before the window opens.

End-to-end metric: ``call_ms``, the window's length over the calls
completed in it.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import bytes as req
from bench import harness, refs

SPANS = ("window", "call")
#: seconds of calls in set-up after each input set's first call
WARM_S = 2.0


def device_inputs(shapes: dict, seed: int, count: int) -> list[dict]:
    """``count`` input sets, made on the device in one jitted call, set
    ``j`` from ``harness.device_key(seed, j)``: scalars uniform in
    [0.5, 1.5) (scale factors that neither vanish nor flip a sign),
    arrays standard normal, all float32."""
    import jax
    import jax.numpy as jnp

    def make(keys):
        sets = []
        for key in keys:
            out = {}
            for i, (name, shape) in enumerate(sorted(shapes.items())):
                k = jax.random.fold_in(key, i)
                out[name] = (jax.random.uniform(k, (), jnp.float32, 0.5, 1.5)
                             if shape == () else
                             jax.random.normal(k, shape, jnp.float32))
            sets.append(out)
        return sets

    keys = [harness.device_key(seed, j) for j in range(count)]
    return jax.block_until_ready(jax.jit(make)(keys))


def call_window(fn, sets: list[dict], seconds: float):
    """Call ``fn`` on the input sets in turn until ``seconds`` have
    passed.  Returns ``(calls, elapsed_s, last, ends)``: ``last[j]`` is
    the outputs of the last call on set ``j``, ``ends`` the seconds from
    the start at which each call ended."""
    import jax
    calls, last, ends = 0, [None] * len(sets), []
    t0 = time.perf_counter()
    with harness.span("window"):
        while True:
            j = calls % len(sets)
            with harness.span("call"):
                out = jax.block_until_ready(fn(**sets[j]))
            last[j] = out
            calls += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                return calls, elapsed, last, ends


def tenths_ms(ends: list[float]) -> list[float]:
    """Mean milliseconds a call over each tenth of the window's calls."""
    d = np.diff(np.asarray(ends), prepend=0.0)
    return [1e3 * float(c.mean()) for c in np.array_split(d, 10) if len(c)]


def run(config: dict, mix: dict, seed: int, seconds: float,
        tracing: bool, t_start: float, swap=None) -> dict:
    """One run over ``FusionCompiler.compile``'s program for the
    configuration.  ``swap(program, ref)``, when given, returns what the
    window calls in the program's place (the control, or a fault)."""
    import jax
    from repro.core import FusionCompiler
    from repro.programs import REGISTRY

    prog = REGISTRY[config["program"]]
    ref = refs.load(config["program"])
    n = mix["n"]
    shapes = refs.shapes(ref.INPUTS, n)
    if prog.shapes(n) != shapes:
        raise ValueError(f"{config['program']} takes {prog.shapes(n)}, "
                         f"the reference {shapes}")
    t0 = harness.note("start", t_start)
    cc = FusionCompiler(backend=config["backend"], dtype=config["dtype"])
    compiled = cc.compile(prog.script, prog.shapes(n))
    plan_s = time.perf_counter() - t0
    t0 = harness.note("plan", t0)
    impls = [{"traffic_bytes": i.traffic_bytes, "t_pred": i.t_pred}
             for i in compiled.group_impls]
    fn = compiled if swap is None else swap(compiled, ref)
    sets = device_inputs(shapes, seed, mix["input_sets"])
    t0 = harness.note("inputs", t0)
    outs = [jax.block_until_ready(fn(**s)) for s in sets]
    required = req.required_bytes(sets[0], outs[0])
    del outs          # freed: the warm-up calls keep outputs of their own
    t0 = harness.note("first calls", t0)
    warm_calls = call_window(fn, sets, WARM_S)[0]
    harness.note(f"warm ({warm_calls} calls)", t0)
    setup_s = harness.open_window(t_start)

    with harness.profiled(tracing) as log_dir:
        calls, elapsed, last, ends = call_window(fn, sets, seconds)
    harness.close_window()
    mem = harness.memory_peak(jax.devices())
    trace = harness.reduce_trace(log_dir, SPANS)
    print("[bench] call_ms by tenth of the window's calls: " + " ".join(
        f"{t:.4f}" for t in tenths_ms(ends)), file=sys.stderr)

    host_in = [{k: np.asarray(v) for k, v in s.items()} for s in sets]
    host_out = [tuple(np.asarray(o) for o in (out if isinstance(out, tuple)
                                               else (out,)))
                for out in last]
    del sets, last, fn, compiled
    errors = {}
    for j, (inp, out) in enumerate(zip(host_in, host_out)):
        want = ref.reference(**inp)
        for name, err in refs.errors(ref, out, inp, want).items():
            errors[f"set{j}.{name}"] = err
        del want
    return {
        "attempted": calls, "failed": 0, "setup_s": setup_s,
        "e2e": {"call_ms": 1e3 * elapsed / calls},
        "memory_peak_bytes": mem, "trace": trace, "errors": errors,
        "facts": {"call": {"calls": calls, "impls": impls,
                           "required_bytes": required,
                           "flops": ref.flops(n)},
                  "plan_s": plan_s},
    }
