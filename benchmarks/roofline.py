"""Roofline analysis: combine dry-run artifacts (collectives, memory,
HLO cost) with the closed-form cost model into the §Roofline table.

    PYTHONPATH=src python -m benchmarks.roofline [--mesh pod1|pod2] [--md out.md]
"""
from __future__ import annotations

import argparse
import json
import pathlib

from repro.configs import ARCHS, SHAPES, get_config, supported_cells
from repro.launch import costmodel

DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def load_cell(arch: str, shape: str, mesh_tag: str) -> dict | None:
    p = DRYRUN_DIR / f"{arch}__{shape}__{mesh_tag}.json"
    if not p.exists():
        return None
    return json.loads(p.read_text())


def cell_roofline(arch: str, shape_name: str, mesh_tag: str = "pod1") -> dict | None:
    info = load_cell(arch, shape_name, mesh_tag)
    if info is None or not info.get("ok", False):
        return {"arch": arch, "shape": shape_name, "ok": False,
                "error": (info or {}).get("error", "missing")[:200]}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = 512 if mesh_tag == "pod2" else 256
    est = costmodel.estimate(cfg, shape)
    wire = info["collectives"]["wire_bytes_per_device"]
    terms = est.terms(chips, wire)
    mem = info.get("memory", {})
    cost = info.get("cost", {})
    return {
        "arch": arch, "shape": shape_name, "ok": True, "chips": chips,
        "model_flops": est.model_flops, "impl_flops": est.impl_flops,
        "hbm_bytes": est.hbm_bytes,
        "hlo_flops_per_dev": cost.get("hlo_flops"),
        "hlo_bytes_per_dev": cost.get("hlo_bytes_accessed"),
        "bytes_per_device": mem.get("total_bytes_per_device"),
        "collective_wire_bytes_per_dev": wire,
        "collectives_by_kind": info["collectives"]["by_kind"],
        **terms,
    }


def fmt_s(x):
    if x is None:
        return "?"
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.2f}ms"
    return f"{x*1e6:.1f}us"


def make_table(mesh_tag: str = "pod1") -> str:
    rows = []
    head = ("| arch | shape | compute | memory | collective | dominant | "
            "MODEL/HLO flops ratio | roofline frac | HBM/dev |")
    sep = "|" + "---|" * 9
    rows.append(head)
    rows.append(sep)
    for arch in ARCHS:
        for s in supported_cells(arch):
            r = cell_roofline(arch, s, mesh_tag)
            if r is None:
                continue
            if not r["ok"]:
                rows.append(f"| {arch} | {s} | FAILED | | | | | | |")
                continue
            ratio = r["flops_utilization"]
            mem_dev = r["bytes_per_device"]
            mem_s = f"{mem_dev/2**30:.2f}GiB" if mem_dev else "?"
            rows.append(
                f"| {arch} | {s} | {fmt_s(r['t_compute_s'])} | "
                f"{fmt_s(r['t_memory_s'])} | {fmt_s(r['t_collective_s'])} | "
                f"**{r['dominant']}** | {ratio:.2f} | "
                f"{r['roofline_fraction']:.2f} | {mem_s} |")
    return "\n".join(rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--md", default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    table = make_table(args.mesh)
    print(table)
    if args.md:
        pathlib.Path(args.md).write_text(table + "\n")
    if args.json:
        data = [cell_roofline(a, s, args.mesh)
                for a in ARCHS for s in supported_cells(a)]
        pathlib.Path(args.json).write_text(json.dumps(data, indent=1))


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
