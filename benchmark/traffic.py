"""The one general traffic generator: a mix file in, seeded work out.

A traffic mix is data (`traffic/<mix>.json`): statement classes as SQL
templates with declared parameter domains, the loop kind, sessions or
rate, think time and bursts. Everything drawn here comes from the seed
through `random.Random`, so the same seed gives the same statements in
the same order in every session. This module imports nothing of the
program and nothing of JAX; the session processes use it too.

Mix file keys:
  loop            "closed" (a session sends, waits, sends) or "open"
                  (arrivals at `rate_per_s` whether or not replies came)
  sessions        connections, one client process each
  think_time_ms   closed loop: pause between a reply and the next send
  rate_per_s      open loop: offered statements a second, all sessions
  arrivals        open loop: "poisson" or "uniform"
  burst           open loop, optional: {"every_s", "for_s", "factor"} —
                  the rate is multiplied by `factor` for `for_s` seconds
                  at the start of every `every_s`
  parameter_sets  K: distinct parameter sets drawn per class (fewer
                  where the domain has fewer points), all warmed and
                  verified in set-up; the window draws among them
  classes         [{"name", "statement", "params": {name: domain},
                    "writes": false}]; `statement` names
                  statements/<statement>.sql and its reference .py
Domains: {"kind": "fixed", "value"}, {"kind": "int", "lo", "hi"},
{"kind": "date", "lo", "hi", "step_days"}, {"kind": "choice",
"values"}, {"kind": "zipf", "n", "theta"} (an integer in 1..n).
"""

from __future__ import annotations

import bisect
import datetime
import itertools
import random

_ZIPF_CDF: dict = {}


def _zipf_cdf(n: int, theta: float) -> list:
    key = (n, theta)
    if key not in _ZIPF_CDF:
        weights = [1.0 / (i ** theta) for i in range(1, n + 1)]
        total = sum(weights)
        _ZIPF_CDF[key] = list(itertools.accumulate(w / total
                                                   for w in weights))
    return _ZIPF_CDF[key]


def draw(domain: dict, rng: random.Random):
    """One value of a declared parameter domain."""
    kind = domain["kind"]
    if kind == "fixed":
        return domain["value"]
    if kind == "int":
        return rng.randint(int(domain["lo"]), int(domain["hi"]))
    if kind == "choice":
        return rng.choice(domain["values"])
    if kind == "date":
        lo = datetime.date.fromisoformat(domain["lo"])
        hi = datetime.date.fromisoformat(domain["hi"])
        step = int(domain.get("step_days", 1))
        return (lo + datetime.timedelta(
            days=step * rng.randint(0, (hi - lo).days // step))).isoformat()
    if kind == "zipf":
        cdf = _zipf_cdf(int(domain["n"]), float(domain["theta"]))
        return min(bisect.bisect_left(cdf, rng.random()), len(cdf) - 1) + 1
    raise ValueError(f"unknown parameter domain kind {kind!r}")


def parameter_sets(mix: dict, seed: int) -> dict:
    """{class name: up to K distinct parameter dicts}, drawn in the
    order of the mix's classes."""
    k = int(mix["parameter_sets"])
    out = {}
    for cls in mix["classes"]:
        rng = random.Random(f"params:{seed}:{cls['name']}")
        sets: list = []
        for _ in range(64 * k):
            if len(sets) == k:
                break
            p = {name: draw(dom, rng)
                 for name, dom in sorted(cls.get("params", {}).items())}
            if p not in sets:
                sets.append(p)
        out[cls["name"]] = sets
    return out


def session_statements(mix: dict, seed: int, session: int, n_sets: list):
    """Endless (class index, set index) of one closed-loop session: the
    mix's classes in their fixed order from a seeded offset, the
    parameter set drawn from the seed among the class's n_sets."""
    rng = random.Random(f"session:{seed}:{session}")
    n = len(mix["classes"])
    at = rng.randrange(n)
    while True:
        yield at, rng.randrange(n_sets[at])
        at = (at + 1) % n


def arrivals(mix: dict, seed: int, seconds: float, n_sets: list) -> list:
    """Open loop: [(offset_s, session, class index, set index)] for the
    whole window, every session's share of one seeded schedule."""
    rng = random.Random(f"arrivals:{seed}")
    rate = float(mix["rate_per_s"])
    burst = mix.get("burst")
    n = len(mix["classes"])
    sessions = int(mix["sessions"])
    out, t, i = [], 0.0, 0
    while True:
        r = rate
        if burst and (t % float(burst["every_s"])) < float(burst["for_s"]):
            r = rate * float(burst["factor"])
        t += (rng.expovariate(r) if mix.get("arrivals", "poisson")
              == "poisson" else 1.0 / r)
        if t >= seconds:
            return out
        out.append((t, i % sessions, i % n, rng.randrange(n_sets[i % n])))
        i += 1
