"""Docs check: every fenced ``python`` code block in README.md must
execute, and every ``bash`` block's referenced module must import.

    PYTHONPATH=src python tools/check_readme.py

Python blocks run in one shared namespace, in order, so later blocks
may build on earlier ones.  Bash blocks are not executed verbatim (they
may be long-running serving loops); instead each ``python -m <module>``
is imported and each one tagged ``--quick``/``--requests`` is smoke-run
with its own arguments when ``--run-bash`` is passed (CI does).
"""
from __future__ import annotations

import argparse
import importlib
import re
import shlex
import subprocess
import sys
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FENCE = re.compile(r"```(\w+)\n(.*?)```", re.S)


def blocks(text: str, lang: str) -> list[str]:
    return [b for l, b in FENCE.findall(text) if l == lang]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-bash", action="store_true",
                    help="also smoke-run the bash blocks' commands")
    args = ap.parse_args()

    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()

    sys.path.insert(0, os.path.join(REPO, "src"))

    ns: dict = {}
    py = blocks(text, "python")
    assert py, "README has no python blocks"
    for i, b in enumerate(py):
        print(f"-- python block {i + 1}/{len(py)}")
        exec(compile(b, f"<README block {i + 1}>", "exec"), ns)  # noqa: S102

    bash = blocks(text, "bash")
    mods = set()
    cmds = []
    for b in bash:
        for line in b.replace("\\\n", " ").splitlines():
            line = line.split("#")[0].strip()
            if "python -m " not in line:
                continue
            argv = shlex.split(line.split("python -m ", 1)[1])
            mods.add(argv[0])
            cmds.append(argv)
    for m in sorted(mods):
        print(f"-- import {m}")
        importlib.import_module(m)

    if args.run_bash:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        for argv in cmds:
            if not any(a.startswith(("--quick", "--requests")) for a in argv):
                continue            # only smoke-sized commands
            print("-- run python -m", " ".join(argv))
            r = subprocess.run([sys.executable, "-m"] + argv, env=env,
                               capture_output=True, text=True, timeout=900)
            if r.returncode != 0:
                print(r.stdout[-2000:], r.stderr[-2000:], file=sys.stderr)
                return 1

    print("README check OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
