"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: program,
dtype, backend) and a traffic mix (``bench/traffic/<mix>.json``), whose
``kind`` names the loop that drives it (``bench/traffic/<kind>.py``).
The loop sets up (compiles or loads from the compile cache at
``<checkout>/.jax_cache``, makes its inputs from ``--seed``, warms every
shape the cell uses), measures for ``--seconds``, then compares what the
window produced with the float64 reference (``bench/refs``); each number
compared is held to its limit in ``bench/limits/<cell>.json``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window by ``bench/trace.py`` and ``bench/metrics``), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number
compared, with its limit.  The same numbers end standard error.

Exits non-zero with no result when JAX finds no TPU, or fewer chips than
the cell asks for, or the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, metrics, peaks, refs, traffic  # noqa: E402


def start_jax() -> list:
    """Put the program (``<checkout>/src``) on the path and JAX's
    persistent compile cache at the fixed ``<checkout>/.jax_cache``,
    which the program takes from ``JAX_COMPILATION_CACHE_DIR``, caching
    every compile; returns JAX's devices."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.devices()


def read_json(*parts: str):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(spec: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """The cell, its configuration, traffic mix and limits, by name."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(configs[cell["config"]]["file"])
    mix = read_json("bench", "traffic", f"{cell['traffic']}.json")
    limits = read_json("bench", "limits", f"{workload}.json")
    return cell, config, mix, limits


def cell_metrics(spec: dict, workload: str, key: str) -> list[dict]:
    return [m for m in spec[key]
            if "workloads" not in m or workload in m["workloads"]]


def checks_of(ref, errors: dict, limits: dict) -> dict:
    """Each number compared, with its limit: the widest gap over the
    outputs that the reference module assigns to it (``refs.check_of``).
    A missing or non-finite answer reads 1e308, as JSON has no infinity."""
    worst: dict[str, float] = {}
    for label, err in errors.items():
        name = refs.check_of(ref, label.rsplit(".", 1)[1])
        worst[name] = max(worst.get(name, 0.0), err)
    return {name: {"value": v if math.isfinite(v) else 1e308,
                   "limit": limits[name]}
            for name, v in sorted(worst.items())}


def run_cell(spec: dict, workload: str, config: dict, mix: dict,
             limits: dict, seed: int, seconds: float, tracing: bool,
             t_start: float, swap=None) -> dict:
    """One run of ``workload`` with its configuration, traffic mix and
    limits as loaded by ``load_cell``; returns the result object."""
    import jax
    out = traffic.kind(mix["kind"]).run(config, mix, seed, seconds, tracing,
                                        t_start, swap)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    checks = checks_of(refs.load(config["program"]), out["errors"], limits)
    correct = (out["failed"] == 0 and
               all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if tracing:
        trace = out["trace"]
        facts = dict(out["facts"], trace=trace, peak=peaks.peak(device["kind"]))
        values = {}
        for m in cell_metrics(spec, workload, "per_layer"):
            v = metrics.read(m["name"], facts)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        device |= {"busy_s": trace["busy_s"], "window_s": trace["window_s"]}
        result |= {"metrics": values, "device": device,
                   "breakdown": trace["breakdown"]}
    else:
        measured = dict(out["e2e"], setup_s=out["setup_s"])
        result |= {"metrics": {m["name"]: {"value": measured[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell_metrics(spec, workload, "end_to_end")},
                   "device": device}
    for name, err in sorted(out["errors"].items()):
        print(f"[bench] error {name} {err!r}", file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = read_json("BENCHMARK.json")
    cell, config, mix, limits = load_cell(spec, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro", "core")):
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    devices = start_jax()
    harness.note("jax", T_START)
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    result = run_cell(spec, args.workload, config, mix, limits,
                      args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
