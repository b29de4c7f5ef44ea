"""From a profiler trace (.xplane.pb) to numbers: the yardstick's half
of every device metric, kept here so that no later PR changes how a
number is computed.

What a v5e trace looks like (looked at by hand, PR 22): one plane per
chip named `/device:TPU:<n>` with the lines `XLA Modules` (one event per
executed program, `jit_fn(<fingerprint>)`), `XLA Ops` (one event per
executed HLO op, named by the op's whole HLO text; a `while` holds its
body's ops nested inside it), `Async XLA Ops` (copy-start..copy-done
spans, which overlap compute) and two empty ones. Busy time is the union
of `XLA Ops` alone. Host threads are lines of the plane `/host:CPU`
(`PjitFunction(fn)` is a dispatch, `np.asarray(jax.Array)` a result
pull); run.py writes `bench_sync` annotations there, each beside a
CLOCK_MONOTONIC stamp, and that pairs the trace's clock (nanoseconds
from the start of the trace) with the clients' send and receive stamps.

Every time in this module is in nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SYNC_NAME = "bench_sync"
# an op that is a Pallas (Mosaic) kernel: the HLO custom call's target
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
GAP_STATES = ("no_statement_in_flight", "before_first_device_op",
              "between_device_ops", "after_last_device_op")


class NoDevicePlane(ValueError):
    """The trace holds no TPU plane: it was not taken on the chip."""


def short_name(hlo: str) -> str:
    """`%fusion.16 = s32[8388608]{0:T(1024)} fusion(...), kind=kCustom`
    becomes `fusion.16 fusion s32[8388608]` (op numbers repeat from one
    program to the next, the result's shape tells them apart); a custom
    call keeps its target."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    m = _OPCODE.search(rhs)
    out = lhs.lstrip("%") + (" " + m.group(1) if m else "")
    t = _TARGET.search(rhs)
    shape = _SHAPE.match(rhs.lstrip("("))
    return (out + (":" + t.group(1) if t else "")
            + (" " + shape.group(0) if shape else ""))


def read_xplane(path: str) -> dict:
    """{"devices": {chip: [(start, end, name)] by start}, "sync":
    [start of each bench_sync annotation, in order]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    sync: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[int(m.group(1))] = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                sync.extend(int(e.start_ns) for e in line.events
                            if e.name == SYNC_NAME)
    return {"devices": devices, "sync": sorted(sync)}


def clock_offset(trace_sync: list, monotonic_sync: list) -> int:
    """trace clock = CLOCK_MONOTONIC + offset: the median over the
    annotations written by run.py, paired in order."""
    if not trace_sync or len(trace_sync) != len(monotonic_sync):
        raise ValueError(
            f"{len(trace_sync)} bench_sync annotations in the trace, "
            f"{len(monotonic_sync)} stamps from the run")
    return int(statistics.median(t - m for t, m in
                                 zip(trace_sync, monotonic_sync)))


def merged(ops: list, lo: int, hi: int) -> list:
    """Union of the ops' intervals clipped to [lo, hi): disjoint
    (start, end) in order."""
    out: list = []
    for start, end, _ in ops:
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_ns(ops: list, lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(ops, lo, hi))


def self_time_by_name(ops: list, lo: int, hi: int) -> dict:
    """Time per op name with nested ops taken out of their parent, so
    that a `while` is not counted once more for its body."""
    total: dict = {}
    stack: list = []  # [end, name, start, time taken by children]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, start, inner = stack.pop()
            total[name] = total.get(name, 0) + (end - start) - inner
            if stack:
                stack[-1][3] += end - start

    for start, end, name in ops:
        if start < lo or end > hi:
            continue
        close(start)
        if stack and end > stack[-1][0]:
            end = stack[-1][0]  # overlaps its parent's end: clip
        stack.append([end, name, start, 0])
    close(hi + 1)
    return total


def _in_window(ops: list, lo: int, hi: int) -> list:
    """The ops (sorted by start) that start in [lo, hi)."""
    return ops[bisect.bisect_left(ops, (lo,)):bisect.bisect_left(ops, (hi,))]


def attribute_statement(devices: dict, send: int, recv: int) -> dict:
    """One statement that had the device to itself: its device time
    (mean over chips) and the three host stretches around it."""
    busy, first, last = [], None, None
    for ops in devices.values():
        mine = _in_window(ops, send, recv)
        busy.append(busy_ns(mine, send, recv))
        if mine:
            first = mine[0][0] if first is None else min(first, mine[0][0])
            end = max(e for _, e, _ in mine)
            last = end if last is None else max(last, min(end, recv))
    device = sum(busy) / max(len(busy), 1)
    if first is None:
        return {"device_ns": 0.0, "before_first_device_op": recv - send,
                "between_device_ops": 0.0, "after_last_device_op": 0.0}
    return {"device_ns": device,
            "before_first_device_op": first - send,
            "between_device_ops": max((last - first) - device, 0.0),
            "after_last_device_op": recv - last}


def reduce_trace(trace: dict, offset: int, segments: dict,
                 top: int = 10) -> dict:
    """The reduced trace every trace-reading metric takes its number
    from. `segments` is {"mix": {"lo", "hi", "statements"}, "single":
    {...}} on CLOCK_MONOTONIC: the slice under the cell's own traffic
    and the slice under one session, each with its completed
    statements as (class, send_ns, recv_ns)."""
    devices = trace["devices"]
    if not devices:
        raise NoDevicePlane("the trace holds no /device:TPU:<n> plane "
                            f"with a line {OPS_LINE!r}")
    out: dict = {"chips": len(devices)}
    mix = segments["mix"]
    lo, hi = mix["lo"] + offset, mix["hi"] + offset
    per_chip = [busy_ns(ops, lo, hi) for ops in devices.values()]
    busy = sum(per_chip) / len(per_chip)
    out["window_s"] = (hi - lo) / 1e9
    out["busy_s"] = busy / 1e9
    out["busy_s_per_chip"] = [b / 1e9 for b in per_chip]
    out["idle_share"] = 1.0 - busy / (hi - lo)
    done = [s for s in mix["statements"] if lo <= s[2] + offset < hi]
    out["statements_in_mix_slice"] = len(done)
    out["device_ms_per_stmt"] = (busy / 1e6 / len(done)) if done else None
    by_name: dict = {}
    custom = 0
    for ops in devices.values():
        for name, ns in self_time_by_name(ops, lo, hi).items():
            short = short_name(name)
            by_name[short] = by_name.get(short, 0) + ns / len(devices)
            if PALLAS_CALL in name:
                custom += ns / len(devices)
    out["custom_call_share"] = custom / busy if busy else None
    out["device_ops"] = [[n, ns / 1e9] for n, ns in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:top]]

    single = segments["single"]
    per_class: dict = {}
    gaps: dict = {}
    prev_recv = None
    for cls, send, recv in single["statements"]:
        send, recv = send + offset, recv + offset
        a = attribute_statement(devices, send, recv)
        c = per_class.setdefault(cls, {"device_ms": [], "tail_host_ms": [],
                                       "head_host_ms": []})
        c["device_ms"].append(a["device_ns"] / 1e6)
        c["tail_host_ms"].append(a["after_last_device_op"] / 1e6)
        c["head_host_ms"].append(a["before_first_device_op"] / 1e6)
        for state in GAP_STATES[1:]:
            key = f"{cls}:{state}"
            gaps[key] = gaps.get(key, 0) + a[state]
        if prev_recv is not None and send > prev_recv:
            gaps[GAP_STATES[0]] = (gaps.get(GAP_STATES[0], 0)
                                   + send - prev_recv)
        prev_recv = recv
    out["per_class"] = {
        cls: {k: statistics.median(v) for k, v in c.items()}
        | {"statements": len(c["device_ms"])}
        for cls, c in per_class.items()}
    tails = [t for c in per_class.values() for t in c["tail_host_ms"]]
    out["tail_host_ms"] = statistics.median(tails) if tails else None
    out["idle_gaps"] = [[n, ns / 1e9] for n, ns in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:top]]
    return out
