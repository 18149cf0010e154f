"""Mesh construction.  Functions, not module-level constants — importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: one v5e pod = (16 data × 16 model)
    = 256 chips; multi-pod adds a leading DCN 'pod' axis (2 pods = 512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-mesh after failures)."""
    return _mk(tuple(shape), tuple(axes))


def make_data_mesh(n_devices: int | None = None):
    """1-D ``('data',)`` replica mesh over ``n_devices`` (default: all
    local devices) — what the sharded serving engine spreads request
    batches over (DESIGN.md §7)."""
    n = n_devices or len(jax.devices())
    return _mk((n,), ("data",))


def make_host_mesh(model_parallel: int = 1):
    """Best-effort mesh over whatever devices exist (CPU smoke tests,
    degraded/elastic operation after node loss)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    while n % mp:
        mp -= 1
    return _mk((n // mp, mp), ("data", "model"))
