"""Compiled-artifact analysis: memory, HLO cost, collective inventory.

Used by the dry-run.  No device-state side
effects — safe to import from tests.

Scan caveat (measured, see EXPERIMENTS.md §Dry-run): XLA's
``cost_analysis()`` counts a while-loop body ONCE, so flops/bytes of
scanned layer stacks are under-reported.  We therefore (a) parse
collectives per HLO computation and multiply ops inside loop bodies by
the known trip count, and (b) pair the HLO numbers with closed-form
analytic terms (``costmodel.py``) — the compiled artifact proves *what*
collectives/memory the program needs, the analytic model supplies the
*per-step totals*.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_KIND_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([\d,]*)\]")

# collective traffic factors (bytes on the wire per result byte, ring)
_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


@dataclasses.dataclass
class Collective:
    kind: str
    dtype: str
    shape: tuple[int, ...]
    bytes: int            # result bytes (per-device, post-SPMD)
    computation: str
    multiplier: int       # loop trip-count correction

    @property
    def wire_bytes(self) -> float:
        return _FACTOR[self.kind] * self.bytes * self.multiplier


_WHILE_RE = re.compile(r"while\(.*?\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"[su]32\[\]\s+constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    comp = "entry"
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and (s.startswith(("ENTRY", "%"))
                                or re.match(r"^[\w.\-]+\s", s)):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", s)
            comp = m.group(1) if m else "?"
            comps.setdefault(comp, [])
            continue
        comps.setdefault(comp, []).append(line)
    return comps


def _loop_multipliers(comps: dict[str, list[str]]) -> dict[str, int]:
    """computation name -> product of enclosing while-loop trip counts.

    Trip counts are recovered from the loop-condition computation (a
    ``lax.scan`` compiles to ``i < constant(N)``); the largest s32
    constant in the condition is taken as N.  Nested loops multiply."""
    body_of: dict[str, tuple[str, str]] = {}   # parent -> (cond, body) list
    parents: dict[str, tuple[str, int]] = {}   # body -> (parent comp, trip)
    for name, lines in comps.items():
        for line in lines:
            m = _WHILE_RE.search(line)
            if not m:
                continue
            cond, body = m.group(1), m.group(2)
            consts = [int(c) for ln in comps.get(cond, [])
                      for c in _CONST_RE.findall(ln)]
            trip = max(consts) if consts else 1
            parents[body] = (name, max(trip, 1))
    mult: dict[str, int] = {}

    def resolve(comp: str, seen=()) -> int:
        if comp in mult:
            return mult[comp]
        if comp in seen:
            return 1
        if comp in parents:
            parent, trip = parents[comp]
            m = resolve(parent, seen + (comp,)) * trip
        else:
            m = 1
        mult[comp] = m
        return m

    for name in comps:
        resolve(name)
    # called computations (fusions etc.) inherit their caller's multiplier
    # only when unambiguous; we conservatively leave them at 1 unless they
    # are loop bodies — collectives live in partitioned while bodies.
    return mult


def parse_collectives(hlo_text: str, body_multiplier: int = 1
                      ) -> list[Collective]:
    """Scan SPMD-partitioned HLO for collective ops.

    Each op's multiplier is the product of the trip counts of the while
    loops whose body computation (transitively) contains it, recovered
    from the HLO itself.  ``body_multiplier`` is only the fallback when a
    loop's trip count cannot be parsed."""
    comps = _split_computations(hlo_text)
    mult = _loop_multipliers(comps)
    out: list[Collective] = []
    for comp, lines in comps.items():
        m_comp = mult.get(comp, 1)
        for line in lines:
            if "=" not in line:
                continue
            m = _COLL_KIND_RE.search(line)
            if not m or m.group(2) == "-done":   # -done repeats the shape
                continue
            kind = m.group(1)
            # result type is everything between '=' and the op name; it
            # may be a TUPLE (grouped gradient all-reduces) — sum elements
            lhs = line.split("=", 1)[1][: m.start() - line.index("=") - 1]
            nbytes = 0
            dtype0, shape0 = "f32", ()
            for dtype, dims in _SHAPE_RE.findall(lhs):
                if dtype not in _DTYPE_BYTES:
                    continue
                shape = tuple(int(d) for d in dims.split(",") if d) \
                    if dims else ()
                nbytes += int(np.prod(shape, dtype=np.int64)) \
                    * _DTYPE_BYTES[dtype]
                dtype0, shape0 = dtype, shape
            if nbytes == 0:
                continue
            out.append(Collective(
                kind=kind, dtype=dtype0, shape=shape0, bytes=nbytes,
                computation=comp, multiplier=m_comp))
    return out


def collective_summary(colls: list[Collective]) -> dict[str, Any]:
    by_kind: dict[str, float] = {}
    for c in colls:
        by_kind[c.kind] = by_kind.get(c.kind, 0.0) + c.wire_bytes
    return {
        "count": len(colls),
        "wire_bytes_per_device": sum(c.wire_bytes for c in colls),
        "by_kind": by_kind,
    }


def memory_summary(compiled) -> dict[str, Any]:
    """Best-effort memory_analysis extraction (CPU backend compatible)."""
    out: dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}
    if ma is None:
        return {"error": "memory_analysis unavailable"}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    if "argument_size_in_bytes" in out:
        out["total_bytes_per_device"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out


def cost_summary(compiled) -> dict[str, Any]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}
    if not ca:
        return {"error": "cost_analysis unavailable"}
    return {"hlo_flops": float(ca.get("flops", 0.0)),
            "hlo_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "hlo_transcendentals": float(ca.get("transcendentals", 0.0))}


def analyze(lowered, compiled, *, body_multiplier: int = 1) -> dict[str, Any]:
    hlo = compiled.as_text()
    colls = parse_collectives(hlo, body_multiplier=body_multiplier)
    per_comp: dict[str, int] = {}
    for c in colls:
        per_comp[c.computation] = per_comp.get(c.computation, 0) + 1
    return {
        "memory": memory_summary(compiled),
        "cost": cost_summary(compiled),
        "collectives": collective_summary(colls),
        "collectives_by_computation": per_comp,
        "collective_detail": [
            {"kind": c.kind, "dtype": c.dtype, "shape": list(c.shape),
             "bytes": c.bytes, "computation": c.computation,
             "multiplier": c.multiplier}
            for c in colls[:200]],
    }
