"""Kernel & memory observability plane: per-kernel readback-wait attribution,
HBM accounting, and roofline analytics.

The rest of the observability stack (traces, the 31 Hz profiler, the cluster
hub) stops at the host boundary: it measures wall time. This module is the
device-side counterpart — the TPU-native equivalent of the reference's
per-operator `Tracing` SPI / `ExecutionStatistics` accounting:

- `KernelRegistry`: every jitted / pallas root registers under a stable name
  with a bytes-moved / FLOPs cost model. An invocation is timed on the host:
  the wall from the call to its result being ready (`block_until_ready`),
  as it is. For the served path that is the readback's wait — the transfer
  and whatever PJRT still had queued in front of the launch — not the
  device's time, which only a profiler trace tells (PERF.md). The number is
  called `deviceMs` and folded into labelled
  `engine.kernel.*{kernel=,shape=}` Timer/Meter families, per-query
  device-ms + peak-HBM totals in the accountant, and `kernel.execute` span
  events on the active trace. A kernel traced into an outer jit has nothing
  concrete to time; it is counted as inlined instead.
- HBM accounting: live/peak bytes from `device.memory_stats()` on an
  accelerator; on the CPU backend, which reports none, a deterministic
  host-side estimator so CPU tier-1 sees the same math the TPU path uses.
- `roofline()`: per-(kernel, shape-bucket) bytes moved over that wait vs. the
  HBM peak of the device the process runs on (`DEVICE_PEAKS`, keyed by `device_kind`;
  a device without an entry gets no percentage), arithmetic intensity, and
  the top roofline-gap offenders — served as `GET /debug/roofline` and
  merged into the controller's `/debug/cluster`.

Shape labels are power-of-two buckets, never raw shapes, so metric label
cardinality stays bounded no matter what the workload looks like.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

from pinot_tpu.common.accounting import default_accountant
from pinot_tpu.common.metrics import server_metrics
from pinot_tpu.common.trace import ServerQueryPhase, active_trace, trace_event

#: published HBM peak bandwidth per `device_kind`, with its source. The
#: roofline compares against the entry of the device the process runs on; a
#: device that is not listed has no roof here, and gets no percentage.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbmGBps": 819.0,
        "source": "Google Cloud documentation, TPU v5e: 16 GB HBM2e at 819 GB/s per chip",
    },
}

# -- shape buckets ----------------------------------------------------------


def shape_bucket(n) -> str:
    """Power-of-two bucket label for a row count: 2^k covers [2^k, 2^(k+1)).

    Bounds `shape=` label cardinality: a query stream touching thousands of
    distinct segment sizes produces at most ~40 buckets.
    """
    try:
        n = int(n)
    except (TypeError, ValueError):
        return "0"
    if n <= 0:
        return "0"
    return f"2^{n.bit_length() - 1}"


def _has_tracer(out) -> bool:
    """True when `out` contains jax tracers (we are inside an outer trace;
    there is nothing concrete to fence or time)."""
    import jax

    return any(isinstance(leaf, jax.core.Tracer) for leaf in jax.tree_util.tree_leaves(out))


# -- HBM accounting ---------------------------------------------------------


class HostHbmEstimator:
    """Deterministic host-side HBM model used when the backend exposes no
    `memory_stats()` (CPU tier-1). Kernels report their working-set bytes as
    transient footprints; long-lived residency (device segments) uses
    alloc/free. live/peak then mirror what `bytes_in_use` /
    `peak_bytes_in_use` report on a real TPU."""

    def __init__(self):
        self._live = 0
        self._peak = 0
        self._lock = threading.Lock()

    def alloc(self, nbytes: int) -> None:
        n = max(int(nbytes), 0)
        with self._lock:
            self._live += n
            self._peak = max(self._peak, self._live)

    def free(self, nbytes: int) -> None:
        n = max(int(nbytes), 0)
        with self._lock:
            self._live = max(self._live - n, 0)

    def transient(self, nbytes: int) -> int:
        """One kernel invocation's working set: allocated and freed within
        the call. Moves peak, not live. Returns the modeled footprint
        (live-at-peak) for per-query peak-HBM attribution."""
        n = max(int(nbytes), 0)
        with self._lock:
            footprint = self._live + n
            self._peak = max(self._peak, footprint)
            return footprint

    @property
    def live(self) -> int:
        with self._lock:
            return self._live

    @property
    def peak(self) -> int:
        with self._lock:
            return self._peak

    def reset(self) -> None:
        with self._lock:
            self._live = 0
            self._peak = 0


def device_hbm_stats() -> dict | None:
    """live/peak bytes summed over `jax.local_devices()`. None on the CPU
    backend, which reports no memory stats; an accelerator that reports none
    is an error, not a reason to fall back to the estimator."""
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() for d in devices]
    if any(not isinstance(s, dict) or "bytes_in_use" not in s for s in stats):
        if devices[0].platform == "cpu":
            return None
        raise RuntimeError(
            f"{devices[0].platform} device reports no memory_stats(): {stats!r}"
        )
    return {
        "liveBytes": sum(int(s.get("bytes_in_use", 0)) for s in stats),
        "peakBytes": sum(
            int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))) for s in stats
        ),
    }


# -- the registry -----------------------------------------------------------


@dataclass
class RegisteredKernel:
    """One jitted / pallas root. `cost_model(shape_kwargs) -> (bytes, flops)`
    prices a single invocation from its shape signature."""

    name: str
    root: object = None
    cost_model: Callable[[dict], tuple[float, float]] | None = None
    description: str = ""


@dataclass
class _KernelStats:
    calls: int = 0
    device_ms: float = 0.0
    bytes_moved: float = 0.0
    flops: float = 0.0


class KernelRegistry:
    """Registry + device-time ledger for every compiled kernel root."""

    def __init__(self):
        self._lock = threading.Lock()
        self._enabled = True
        self._kernels: dict[str, RegisteredKernel] = {}
        self._stats: dict[tuple[str, str], _KernelStats] = {}
        #: kernel -> times it was traced into an outer jitted program
        self._inlined: dict[str, int] = {}
        #: (program, rows) -> {kernel: {"calls", "bytes", "flops"}}: the static
        #: work of the registered kernels traced into a named outer program
        self._program_work: dict[tuple[str, int], dict] = {}
        self._building = threading.local()
        self.hbm = HostHbmEstimator()

    # -- configuration ------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, enabled: bool | None = None) -> None:
        with self._lock:
            if enabled is not None:
                self._enabled = bool(enabled)

    # -- registration -------------------------------------------------------

    def register(
        self,
        name: str,
        root: object = None,
        cost_model: Callable[[dict], tuple[float, float]] | None = None,
        description: str = "",
    ) -> RegisteredKernel:
        """Register a kernel root under a stable name. Double registration is
        a programming error (two kernels would alias one ledger row)."""
        k = RegisteredKernel(name, root, cost_model, description)
        with self._lock:
            if name in self._kernels:
                raise ValueError(f"kernel {name!r} already registered")
            self._kernels[name] = k
        return k

    def is_registered(self, name: str) -> bool:
        with self._lock:
            return name in self._kernels

    def kernel_names(self) -> list[str]:
        with self._lock:
            return sorted(self._kernels)

    # -- recording ----------------------------------------------------------

    def _cost(self, name: str, shape: dict) -> tuple[float, float]:
        """(bytes, flops) of one invocation by the kernel's registered cost model."""
        k = self._kernels.get(name)
        if k is None or k.cost_model is None:
            return 0.0, 0.0
        nbytes, flops = k.cost_model(shape)
        return max(float(nbytes), 0.0), max(float(flops), 0.0)

    def record(self, name: str, device_ms: float, **shape) -> None:
        """Fold one timed invocation into the ledger, metrics, the current
        query's accountant tracker, and the active trace."""
        if name not in self._kernels:
            return
        nbytes, flops = self._cost(name, shape)
        bucket = shape_bucket(shape.get("rows", 0))
        with self._lock:
            s = self._stats.setdefault((name, bucket), _KernelStats())
            s.calls += 1
            s.device_ms += device_ms
            s.bytes_moved += nbytes
            s.flops += flops
        footprint = self.hbm.transient(int(nbytes))
        reg = server_metrics()
        reg.timer("engine.kernel.deviceMs", kernel=name, shape=bucket).update_ms(device_ms)
        reg.meter("engine.kernel.invocations", kernel=name, shape=bucket).mark()
        if nbytes:
            reg.meter("engine.kernel.bytesMoved", kernel=name, shape=bucket).mark(int(nbytes))
        default_accountant.sample(device_ms=device_ms, hbm_bytes=footprint)
        trace_event(
            "kernel.execute",
            kernel=name,
            shape=bucket,
            deviceMs=round(device_ms, 3),
            bytesMoved=int(nbytes),
        )
        tr = active_trace()
        if tr is not None:
            tr.record_phase(ServerQueryPhase.DEVICE_EXECUTION, device_ms)

    def timed_sync(self, name: str, fn: Callable[[], object], **shape):
        """Run `fn` (a device dispatch whose result the caller is about to
        consume), fence with `block_until_ready`, and record the fenced wall
        as it is: the wait for the result, under the name `deviceMs`.
        Disabled registries and calls made under an outer jax trace pass
        straight through."""
        if not self._enabled:
            return fn()
        import jax

        t0 = time.perf_counter()
        out = fn()
        if _has_tracer(out):
            with self._lock:
                self._inlined[name] = self._inlined.get(name, 0) + 1
            building = getattr(self._building, "work", None)
            if building is not None:
                # no time to take under an outer trace, but the shape is
                # static: the program being built gets this call's work
                nbytes, flops = self._cost(name, shape)
                ent = building.setdefault(name, {"calls": 0, "bytes": 0.0, "flops": 0.0})
                ent["calls"] += 1
                ent["bytes"] += nbytes
                ent["flops"] += flops
            return out
        out = jax.block_until_ready(out)
        self.record(name, (time.perf_counter() - t0) * 1e3, **shape)
        return out

    # -- static work of named outer programs ----------------------------------

    @contextlib.contextmanager
    def building(self, program: str, rows: int):
        """Around the traced body of a named outer program (query/kernels.py
        `get_packed_kernel`): every registered kernel reached inside while jax
        traces it (`timed_sync` sees tracers) adds its static shape's cost to
        `program_work(program, rows)`. Runs once per trace, never per call."""
        self._building.work = work = {}
        try:
            yield
        finally:
            self._building.work = None
            with self._lock:
                self._program_work[(program, int(rows))] = work

    def program_work(self, program: str, rows: int) -> dict:
        """{kernel: {"calls", "bytes", "flops"}} of one launch of `program` at
        `rows` padded docs; empty before its first trace and for a program
        with no registered kernel inside."""
        with self._lock:
            return self._program_work.get((program, int(rows))) or {}

    # -- reporting ----------------------------------------------------------

    def publish_hbm_gauges(self) -> dict:
        """Set `engine.hbm.liveBytes` / `peakBytes` from a fresh snapshot.
        Called where they are read (`/metrics`, `/debug/roofline`), not per
        kernel record: `device.memory_stats()` is no hot-path call."""
        hbm = self.hbm_snapshot()
        reg = server_metrics()
        reg.gauge("engine.hbm.liveBytes").set(hbm["liveBytes"])
        reg.gauge("engine.hbm.peakBytes").set(hbm["peakBytes"])
        return hbm

    def hbm_snapshot(self) -> dict:
        dev = device_hbm_stats()
        if dev is not None:
            return {**dev, "source": "device"}
        # CPU backend only (device_hbm_stats raises for a silent accelerator)
        return {"liveBytes": self.hbm.live, "peakBytes": self.hbm.peak, "source": "estimator"}

    def stats_snapshot(self) -> dict[tuple[str, str], dict]:
        with self._lock:
            return {
                key: {
                    "calls": s.calls,
                    "deviceMs": s.device_ms,
                    "bytesMoved": s.bytes_moved,
                    "flops": s.flops,
                }
                for key, s in self._stats.items()
            }

    def total_device_ms(self) -> float:
        with self._lock:
            return sum(s.device_ms for s in self._stats.values())

    def roofline(self, top: int = 10) -> dict:
        """The `/debug/roofline` document: the device this process runs on,
        per-(kernel, shape-bucket) achieved GB/s vs. that device's HBM peak,
        arithmetic intensity, and the top offenders ranked by device-ms spent
        below the roof (gap alone would rank microscopic kernels first). A
        device with no known peak gets achieved numbers and no percentages."""
        import jax

        device = jax.local_devices()[0]
        entry = DEVICE_PEAKS.get(device.device_kind)
        peak, peak_source = (entry["hbmGBps"], entry["source"]) if entry else (None, None)
        rows = []
        for (name, bucket), s in sorted(self.stats_snapshot().items()):
            dev_s = s["deviceMs"] / 1e3
            achieved = (s["bytesMoved"] / dev_s / 1e9) if dev_s > 0 else 0.0
            pct = (100.0 * achieved / peak) if peak else None
            rows.append(
                {
                    "kernel": name,
                    "shape": bucket,
                    "calls": s["calls"],
                    "deviceMs": round(s["deviceMs"], 3),
                    "bytesMoved": int(s["bytesMoved"]),
                    "flops": int(s["flops"]),
                    "achievedGBps": round(achieved, 3),
                    "arithmeticIntensity": (
                        round(s["flops"] / s["bytesMoved"], 4) if s["bytesMoved"] else 0.0
                    ),
                    "pctOfPeak": None if pct is None else round(pct, 3),
                    "rooflineGap": round(peak / achieved, 1) if peak and achieved > 0 else None,
                    "lostMs": (
                        None
                        if pct is None
                        else round(s["deviceMs"] * max(1.0 - pct / 100.0, 0.0), 3)
                    ),
                }
            )
        offenders = sorted(
            (r for r in rows if r["rooflineGap"] is not None),
            key=lambda r: -r["lostMs"],
        )[: max(int(top), 0)]
        with self._lock:
            inlined = dict(self._inlined)
        return {
            "platform": device.platform,
            "deviceKind": device.device_kind,
            "hbmPeakGBps": peak,
            "hbmPeakSource": peak_source,
            "enabled": self._enabled,
            "kernels": rows,
            "offenders": offenders,
            "inlined": inlined,
            "hbm": self.publish_hbm_gauges(),
            "registered": self.kernel_names(),
        }

    # -- test hooks ---------------------------------------------------------

    def reset_stats(self) -> None:
        with self._lock:
            self._stats.clear()
            self._inlined.clear()
        self.hbm.reset()

    def reset(self) -> None:
        with self._lock:
            self._kernels.clear()
            self._stats.clear()
            self._inlined.clear()
            self._enabled = True
        self.hbm.reset()


#: process-wide registry every compiled root registers into at import time
KERNELS = KernelRegistry()


# -- lru_cache observability ------------------------------------------------


class CacheObserver:
    """Publishes an `functools.lru_cache`'s hit/miss/size/evict counters as
    `engine.kernelCache.*{cache=...}` metric families. lru_cache keeps
    monotonic totals; we emit deltas so the meters compose with every other
    meter on /metrics. Evictions are inferred: every miss inserts, so
    `misses - currsize` (once the cache has filled) counts entries pushed
    out."""

    def __init__(self, cached_fn, cache: str):
        self._fn = cached_fn
        self._label = cache
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def observe(self) -> None:
        """Fold the cache's counters into metrics (call after each lookup)."""
        info = self._fn.cache_info()
        reg = server_metrics()
        with self._lock:
            d_hits = info.hits - self._hits
            d_misses = info.misses - self._misses
            evictions = max(info.misses - info.currsize, 0)
            d_evict = evictions - self._evictions
            self._hits, self._misses = info.hits, info.misses
            self._evictions = evictions
        if d_hits > 0:
            reg.meter("engine.kernelCache.hits", cache=self._label).mark(d_hits)
        if d_misses > 0:
            reg.meter("engine.kernelCache.misses", cache=self._label).mark(d_misses)
        if d_evict > 0:
            reg.meter("engine.kernelCache.evictions", cache=self._label).mark(d_evict)
        reg.gauge("engine.kernelCache.size", cache=self._label).set(info.currsize)
