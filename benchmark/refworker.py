#!/usr/bin/env python3
"""The reference answers of one run, in a process beside set-up.

    python3 benchmark/refworker.py <job.json> <out.json>

Makes the same tables from the same seed as run.py does and evaluates
each statement's reference (statements/<name>.py: numpy, integer
arithmetic) at every parameter set. Imports neither the program nor
JAX, so it shares nothing with the code under test but the seed.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}", os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def answers(job: dict) -> dict:
    gen = load_module("generators", job["generator"])
    tables = {t: gen.generate(t, job["sf"], job["seed"])
              for t in job["tables"]}
    out = {}
    for cls in job["classes"]:
        ref = load_module("statements", cls["statement"])
        out[cls["name"]] = [ref.reference(tables, p) for p in cls["sets"]]
    return out


def main(argv) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    out = {"answers": answers(job),
           "modules": sorted(m for m in sys.modules if m.split(".")[0]
                             in ("jax", "jaxlib", "cockroach_tpu"))}
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
