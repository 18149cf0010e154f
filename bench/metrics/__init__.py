"""Per-layer metrics, one reader per file, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(facts) -> float | None``.
``facts`` is what one run gathered: a traffic kind's ``facts`` (see
``bench/traffic/__init__.py``), with ``trace`` and ``peak`` added by
``bench/run.py``.  A reader that finds nothing to read returns None and
the metric is left out of the result line.
"""
import importlib.util
import os

DIR = os.path.dirname(os.path.abspath(__file__))


def read(name: str, facts: dict):
    """Value of per-layer metric ``name`` in this run, or None."""
    path = os.path.join(DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(facts)
