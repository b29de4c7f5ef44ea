"""Device-utilization plane: per-device HBM, per-statement device
seconds, and dispatcher queue pressure as one scrapeable family.

The engine's existing device telemetry is scattered — `utils/mon.py`
accounts *reserved* HBM (what the budget admitted), the compile/execute
split lives in sqlstats, and queue depth is a gauge written only at
enqueue. This module samples the *actual* device state:

- ``hbm_bytes()`` — allocator-reported bytes in use summed over
  devices (JAX ``device.memory_stats()`` where the backend exposes
  it — TPU and GPU do, CPU usually doesn't), falling back to the
  BytesMonitor's reservation accounting so the metric is never absent;
- ``hbm_watermark()`` — the high-water mark of the above, the number
  an admission controller sizes against;
- ``util_seconds()`` — cumulative per-statement device-execute
  seconds: the engine feeds ``note_execute(dt - compile_s)`` after
  each statement (the round-9 compile-vs-execute split), so the
  counter integrates "time the device was doing query work" without
  a profiler;
- ``queue_depth()`` — live sum of the per-mesh dispatcher queues
  (parallel/distagg), the back-pressure signal.

``register()`` exposes them as the ``exec.device.*`` metric family;
the status server's maintenance loop snapshots the registry into the
KV-backed time-series store (server/ts.py), so ``/ts/query`` can
graph utilization history — the telemetry substrate Tailwind-style
multi-query multiplexing reads from (PAPERS.md).
"""

from __future__ import annotations

import threading
from typing import Optional


class DeviceStats:
    """Process-wide device utilization sampler (one per Engine; all
    engines in a process see the same devices, so values agree)."""

    def __init__(self, hbm=None):
        # utils/mon.BytesMonitor fallback for backends whose
        # allocator doesn't report memory_stats (CPU)
        self._hbm_monitor = hbm
        self._lock = threading.Lock()
        self._util_seconds = 0.0
        self._watermark = 0
        self._mem_stats_ok: Optional[bool] = None  # lazy capability

    # -- HBM ---------------------------------------------------------
    def _device_memory_bytes(self) -> Optional[int]:
        """Allocator-reported bytes in use across devices, or None
        when no device exposes memory_stats (then the reservation
        accounting stands in)."""
        if self._mem_stats_ok is False:
            return None
        import jax
        try:
            total = 0
            seen = False
            for d in jax.devices():
                ms = getattr(d, "memory_stats", None)
                ms = ms() if callable(ms) else None
                if not ms:
                    continue
                v = ms.get("bytes_in_use", ms.get("bytes_in_use_",
                                                  None))
                if v is None:
                    v = ms.get("peak_bytes_in_use")
                if v is not None:
                    total += int(v)
                    seen = True
            self._mem_stats_ok = seen
            return total if seen else None
        except Exception:
            self._mem_stats_ok = False
            return None

    def hbm_bytes(self) -> int:
        v = self._device_memory_bytes()
        if v is None:
            v = int(self._hbm_monitor.used) if self._hbm_monitor \
                else 0
        with self._lock:
            if v > self._watermark:
                self._watermark = v
        return v

    def hbm_watermark(self) -> int:
        self.hbm_bytes()  # ratchet before reading
        with self._lock:
            return self._watermark

    # -- device-execute seconds --------------------------------------
    def note_execute(self, seconds: float) -> None:
        """Credit one statement's device-execute time (its wall time
        net of the XLA compile bill — exec/coldstart.py's split)."""
        if seconds > 0:
            with self._lock:
                self._util_seconds += seconds

    def util_seconds(self) -> float:
        with self._lock:
            return self._util_seconds

    # -- dispatcher queue pressure -----------------------------------
    def queue_depth(self) -> int:
        """Sum of queued collective executions across every per-mesh
        dispatcher alive in the process (parallel/distagg)."""
        try:
            from ..parallel import distagg
            return sum(d.depth()
                       for d in list(distagg._DISPATCHERS.values()))
        except Exception:
            return 0

    # -- registration ------------------------------------------------
    def register(self, metrics) -> "DeviceStats":
        metrics.func_gauge(
            "exec.device.hbm.bytes", self.hbm_bytes,
            "device memory in use, allocator-reported via JAX "
            "memory_stats when the backend exposes it, else the HBM "
            "budget's reservation accounting (utils/mon.py)")
        metrics.func_gauge(
            "exec.device.hbm.watermark", self.hbm_watermark,
            "high-water mark of exec.device.hbm.bytes since process "
            "start")
        metrics.func_counter(
            "exec.device.util.seconds", self.util_seconds,
            "cumulative per-statement device-execute seconds "
            "(statement wall time net of the XLA compile split)")
        metrics.func_gauge(
            "exec.device.queue.depth", self.queue_depth,
            "live queued collective executions summed over per-mesh "
            "dispatchers (back-pressure on the device)")
        return self
