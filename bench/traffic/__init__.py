"""Traffic: mixes as data, and the loops that drive them, found by name.

A mix is ``bench/traffic/<mix>.json``: its parameters, and ``kind``, the
name of the loop that reads them.  A kind is ``bench/traffic/<kind>.py``,
which defines

* ``SPANS``: the names of the host spans it opens (one is ``window``);
* ``run(config, mix, seed, seconds, tracing, t_start, swap) -> dict``: one
  run of the cell (set-up, the measured window, the outputs compared with
  the reference), returning ``attempted``, ``failed``, ``setup_s``,
  ``e2e`` (its end-to-end metrics by name), ``memory_peak_bytes``,
  ``trace``, ``errors`` (``"<label>.<output>": gap``) and ``facts`` (what
  the per-layer readers of ``bench/metrics`` read).

A new arrival process or entry point is a new kind file, and a new mix of
an existing kind a new data file: neither edits a file that is there.
"""
import importlib.util
import os

DIR = os.path.dirname(os.path.abspath(__file__))


def kind(name: str):
    """The loop module of traffic kind ``name``."""
    path = os.path.join(DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no traffic kind {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
