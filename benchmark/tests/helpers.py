"""Shared by the self-tests: run one cell's rehearsal in a process of
its own and hand back what it printed."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def run_cell(workload, *extra, root=ROOT, devices=1, rehearse=True,
             seconds=2, seed=5):
    """(exit code, last stdout line parsed or None, all output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices}")
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", "0", *extra]
    if rehearse:
        cmd += ["--rehearse-cpu-sf", "0.01"]
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    result = None
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return p.returncode, result, p.stdout + p.stderr
