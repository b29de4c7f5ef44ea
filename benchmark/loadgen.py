#!/usr/bin/env python3
"""One session of the load: a client process of its own.

    python3 benchmark/loadgen.py <plan.json> <session> <out.json>

The plan (written by run.py) holds the server's address, the mix, the
seed, every statement's text and the digest of its verified reply, and
the window as two CLOCK_MONOTONIC stamps, one clock for every process
on the machine. The session connects, waits for the start, sends until
the end, and writes one sample per statement:
[class index, set index, due_ns, send_ns, recv_ns, ok, session]. `due_ns` is
when the statement was due (its send in a closed loop, its scheduled
arrival in an open one), so an open-loop latency counts the time a
late generator kept the statement waiting. Imports the benchmark's
pgwire client and traffic generator only: never the program, never JAX.
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

import pgclient
import traffic


def run_session(plan: dict, session: int) -> dict:
    mix, seed = plan["mix"], plan["seed"]
    statements = plan["statements"]  # [class][set] -> {"sql", "digest"}
    n_sets = [len(sets) for sets in statements]
    start_ns, end_ns = plan["start_ns"], plan["end_ns"]
    client = pgclient.MiniClient(plan["host"], plan["port"],
                                 timeout=plan["timeout_s"])
    samples = []

    def one(ci: int, si: int, due_ns: int) -> bool:
        """False once the connection is of no more use."""
        st = statements[ci][si]
        t0 = time.monotonic_ns()
        try:
            send_ns, recv_ns, reply, error = client.exchange(st["sql"])
        except (OSError, ConnectionError):  # timed out or cut
            samples.append([ci, si, due_ns or t0, t0, time.monotonic_ns(),
                            False, session])
            return False
        if mix["classes"][ci].get("writes"):
            ok = error is None  # acknowledged; read back by a read class
        else:
            ok = (error is None
                  and hashlib.sha1(reply).hexdigest() == st["digest"])
        samples.append([ci, si, due_ns or send_ns, send_ns, recv_ns,
                        bool(ok), session])
        return True

    time.sleep(max(0.0, (start_ns - time.monotonic_ns()) / 1e9))
    if mix["loop"] == "closed":
        think_s = float(mix.get("think_time_ms", 0)) / 1e3
        for ci, si in traffic.session_statements(mix, seed, session,
                                                  n_sets):
            if time.monotonic_ns() >= end_ns or not one(ci, si, 0):
                break
            if think_s:
                time.sleep(think_s)
    else:
        seconds = (end_ns - start_ns) / 1e9
        for off_s, sess, ci, si in traffic.arrivals(mix, seed, seconds,
                                                   n_sets):
            if sess != session:
                continue
            due_ns = start_ns + int(off_s * 1e9)
            wait = (due_ns - time.monotonic_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            if not one(ci, si, due_ns):
                break
    client.close()
    return {"session": session, "samples": samples}


def main(argv) -> int:
    plan_path, session, out_path = argv[1], int(argv[2]), argv[3]
    with open(plan_path) as f:
        plan = json.load(f)
    out = run_session(plan, session)
    out["modules"] = sorted(m for m in sys.modules
                            if m.split(".")[0] in ("jax", "jaxlib",
                                                   "cockroach_tpu"))
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
