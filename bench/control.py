"""Readings from which a cell's limits are set (not part of a run).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--control | --fault <name>]

For each seed, drives the whole run of ``bench/run.py`` in this one
process (set-up is paid once per seed, the compile cache once) and
prints each number compared.  Without an option these are the program's
readings, the lower ones of the limits.  ``--control`` puts the control
in the program's place: the reference computed on the chip one step
below the precision the configuration states (``bench/refs/<program>.py``,
``control``), jitted and called in the window.  ``--fault`` plants one
of ``FAULTS`` in the program's timed path.  Their smallest readings are
the upper ones.  The last line is one JSON object with every reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402

#: terms a dropped block of a reduce leaves out: one grid step of the
#: ``(1, 128)`` blocks the AXPYDOT kernel reduces
BLOCK = 128


def control_swap(program, ref):
    """The control, jitted, in the program's place."""
    import jax
    return jax.jit(ref.control)


def dropped_block(program, ref):
    """AXPYDOT as the program computes it, but its dot r without the
    last ``BLOCK`` terms z_i u_i: a reduce that skips one grid step."""
    import jax.numpy as jnp

    def call(**inputs):
        z, r = program(**inputs)
        lost = jnp.sum(z.reshape(-1)[-BLOCK:] * inputs["u"][-BLOCK:])
        return z, r - lost.reshape(r.shape)
    return call


FAULTS = {"dropped_block": dropped_block}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--control", action="store_true",
                      help="read the control, not the program")
    mode.add_argument("--fault", choices=sorted(FAULTS),
                      help="read the program with this fault planted")
    args = ap.parse_args(argv)

    spec = run.read_json("BENCHMARK.json")
    cell, config, mix, limits = run.load_cell(spec, args.workload)
    if run.start_jax()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    swap, label = None, "program"
    if args.control:
        swap, label = control_swap, "control"
    elif args.fault:
        swap, label = FAULTS[args.fault], args.fault
    readings = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(spec, args.workload, config, mix, limits, seed,
                           args.seconds, False, time.perf_counter(), swap)
        readings[seed] = {k: c["value"] for k, c in res["checks"].items()}
        print(f"[control] {args.workload} {label} seed {seed} "
              f"{json.dumps(readings[seed])} metrics "
              f"{json.dumps(res['metrics'])}", flush=True)
    print(json.dumps({"workload": args.workload, "mode": label,
                      "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
