#!/usr/bin/env python
"""Generate docs/api.md from the exported serving/kernel API docstrings.

Covered modules (the surface README.md points users at):

* ``repro.kernels.ops``       — jit'd kernel wrappers (the GEMM API)
* ``repro.runtime.serve_loop`` — ServeEngine + one-shot generate
* ``repro.runtime.scheduler`` — continuous-batching scheduler + policies
* ``repro.runtime.kv_cache``  — paged-KV accounting + radix prefix cache
* ``repro.runtime.draft``     — speculative-decode draft sources + acceptance
* ``repro.runtime.spans``     — the engine's host trace spans + step names
* ``repro.core.precision``    — precision recipes (int8/fp8/w4 pipelines)
* ``repro.core.compressed``   — packed (2N-2):2N operand + (de)compression
* ``repro.kernels.roofline``  — analytic bytes/flops cost model + peaks
* ``benchmarks.roofline``     — harness-facing roofline adapters
* ``benchmarks.run``          — benchmark harness + perf diff gate

The output is deterministic (source order, plain signatures, no memory
addresses), so CI can diff it against the committed file:

    python tools/gen_api_docs.py           # (re)write docs/api.md
    python tools/gen_api_docs.py --check   # exit 1 if docs/api.md drifted

Run from the repo root (or anywhere: paths are resolved relative to this
file).  ``tools/ci.sh`` runs the --check mode, so a signature or
docstring change without a regenerated docs/api.md fails CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "docs", "api.md")

MODULES = [
    "repro.kernels.ops",
    "repro.runtime.serve_loop",
    "repro.runtime.scheduler",
    "repro.runtime.kv_cache",
    "repro.runtime.draft",
    "repro.runtime.spans",
    "repro.runtime.faults",
    "repro.core.precision",
    "repro.core.compressed",
    "repro.kernels.paged_attention",
    "repro.kernels.roofline",
    "benchmarks.roofline",
    "benchmarks.run",
]

HEADER = """\
# API reference

Generated by `tools/gen_api_docs.py` from the docstrings of the exported
serving / kernel surface — do not edit by hand; regenerate with
`python tools/gen_api_docs.py` (CI fails when this file drifts from the
source).
"""


def _public_symbols(mod):
    """Public functions/classes defined in ``mod``, in source order."""
    syms = []
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue
        try:
            _, line = inspect.getsourcelines(obj)
        except (OSError, TypeError):
            line = 1 << 30
        syms.append((line, name, obj))
    return [(n, o) for _, n, o in sorted(syms)]


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else "*(undocumented)*"


def _emit_class(lines, name, cls):
    lines.append(f"### `{name}`\n")
    if dataclasses.is_dataclass(cls):
        fields = ", ".join(
            f"{f.name}" for f in dataclasses.fields(cls))
        lines.append(f"*dataclass* — fields: `{fields}`\n")
    lines.append(_doc(cls) + "\n")
    for mname, m in _public_symbols_of_class(cls):
        kind = "property" if isinstance(m, property) else "method"
        sig = "" if isinstance(m, property) else _signature(m)
        lines.append(f"#### `{name}.{mname}{sig}` *({kind})*\n")
        lines.append(_doc(m.fget if isinstance(m, property) else m) + "\n")


def _public_symbols_of_class(cls):
    out = []
    for mname, m in vars(cls).items():
        if mname.startswith("_"):
            continue
        if isinstance(m, property) or inspect.isfunction(m):
            try:
                _, line = inspect.getsourcelines(
                    m.fget if isinstance(m, property) else m)
            except (OSError, TypeError):
                line = 1 << 30
            out.append((line, mname, m))
        elif isinstance(m, (staticmethod, classmethod)):
            out.append((1 << 30, mname, m.__func__))
    return [(n, o) for _, n, o in sorted(out)]


def generate() -> str:
    lines = [HEADER]
    for modname in MODULES:
        mod = importlib.import_module(modname)
        lines.append(f"\n## `{modname}`\n")
        lines.append(_doc(mod) + "\n")
        for name, obj in _public_symbols(mod):
            if inspect.isclass(obj):
                _emit_class(lines, name, obj)
            else:
                lines.append(f"### `{name}{_signature(obj)}`\n")
                lines.append(_doc(obj) + "\n")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="diff against the committed docs/api.md; exit 1 "
                         "on drift instead of writing")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(REPO, "src"))
    sys.path.insert(0, REPO)  # the benchmarks package lives at the root

    text = generate()
    if args.check:
        try:
            with open(OUT) as f:
                committed = f.read()
        except OSError:
            print(f"gen_api_docs: {OUT} missing — run "
                  "`python tools/gen_api_docs.py`", file=sys.stderr)
            return 1
        if committed != text:
            print("gen_api_docs: docs/api.md drifted from docstrings — "
                  "run `python tools/gen_api_docs.py` and commit the "
                  "result", file=sys.stderr)
            return 1
        print("gen_api_docs: docs/api.md is up to date")
        return 0
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        f.write(text)
    print(f"gen_api_docs: wrote {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
