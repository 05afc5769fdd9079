"""Retained telemetry: the server-side sampling collector (ISSUE 11).

`/v1/metrics` is a point-in-time InmemSink snapshot; a flatness
verdict had to be computed AFTER a run from windows a harness
assembled itself; and the device economics the north star turns on
(pad waste, compile counts, dispatch seconds) lived only in
process-local structs.
This collector closes all three gaps in-process: a background sampler
snapshots governor gauges, counter totals (rates derived from slot
deltas at read time), stage percentile reservoirs, device-economics
stats, and RSS into bounded struct-of-arrays ring buffers — numpy
float64 columns, one write cursor, wrap-around overwrite — so
`/v1/operator/flatness` can run `flatness_verdict` (below) over the
LIVE ring and `nomad operator top` can render rates and trends from
history instead of a single scrape.

The same sampler keeps the process's CPU ledger (ISSUE 36): each row
carries `process.cpu_s` (time.process_time) and `thread_cpu.<role>_s`
for the threads named worker-* (workers), plan-applier /
plan-committer / raft-fsm (applier), http-api / ingest-gateway / the
per-connection handlers (http), and the process less the three
(other), cumulative CPU seconds read from OUTSIDE the threads — nothing
on any hot path. A thread that lives shorter than a sample adds its own
CPU to its role as it ends (thread_ended). An operator reads the series
as cores under `rates`. They are amounts over a sample, no part of any
span, so they stay off the stages hook (utils/stages.py: a tap draws
every report there as an interval).

Bounding: `telemetry_ring_slots` slots × MAX_SERIES series × 8 bytes
(defaults: 512 × 256 = 1 MiB hard ceiling); series past the cap are
dropped and counted, never grown. The collector only READS — gauge
closures, counter totals, reservoir percentiles — and every read is
host-side (the device stats it samples are plain dict snapshots), so
sampling can never sync the accelerator.

Kill switch: NOMAD_TPU_TELEMETRY=0 (or telemetry_sample_interval_s=0)
builds no collector at all — /v1/metrics degenerates to today's
snapshot-only behavior and the flatness/telemetry routes report
disabled.
"""

from __future__ import annotations

import math
import os
import sys
import threading
import time
from statistics import median
from typing import Callable, Dict, List, Optional

import numpy as np

from ..governor.drift import least_squares_slope
from ..governor.governor import rss_mb
from ..utils import metrics, stages
from ..utils.locks import make_lock

# hard series ceiling: a gauge-name churn storm (e.g. per-job counter
# keys) must not grow the ring without bound — excess series are
# dropped and counted in status()
MAX_SERIES = 256

DEFAULT_SLOTS = 512
DEFAULT_INTERVAL_S = 1.0


def enabled() -> bool:
    """The NOMAD_TPU_TELEMETRY kill switch (parallel to
    NOMAD_TPU_TRACE): default on."""
    return os.environ.get("NOMAD_TPU_TELEMETRY", "1") not in ("0", "off")


# -- the process's CPU by thread role ------------------------------------
# The ledger is the process's, as time.process_time() and
# threading.enumerate() are: whichever server's collector samples reads
# the same clocks. Roles go by the names the threads already have.
CPU_ROLES = ("workers", "applier", "http")      # and "other": the rest
_APPLIER_THREADS = frozenset({"plan-applier", "plan-committer",
                              "raft-fsm"})
_HTTP_THREADS = frozenset({"http-api", "ingest-gateway"})
# CPU seconds of threads that ended since the process began, by role
# (thread_ended); with the live threads' clocks, a role's total
_ended_l = make_lock()
_ended_cpu: Dict[str, float] = {role: 0.0 for role in CPU_ROLES}


def thread_role(name: str) -> Optional[str]:
    """The CPU role of a thread by its name; None for `other`."""
    if name.startswith("worker-"):      # lanes and finishers too
        return "workers"
    if name in _APPLIER_THREADS:
        return "applier"
    # ThreadingHTTPServer names a connection's thread after its target
    if name in _HTTP_THREADS or "process_request_thread" in name:
        return "http"
    return None


def thread_ended(role: str) -> None:
    """The calling thread is about to end: its CPU goes to its role's
    tally and the sampler stops reading its clock. For threads that
    live shorter than a sample (a lane, a connection's handler), from
    the `finally` of what they run."""
    with _ended_l:
        cpu = stages.cpu_now()
        if cpu is not None:
            threading.current_thread()._cpu_tallied = True
            _ended_cpu[role] += cpu


def _thread_cpu_s(t: threading.Thread) -> Optional[float]:
    """Another thread's CPU clock, by its kernel id: the id glibc's
    pthread_getcpuclockid computes (CPUCLOCK_SCHED | per-thread), asked
    of the kernel itself — for a thread that is gone that is EINVAL,
    where pthread_getcpuclockid(t.ident) would read the dead thread's
    freed descriptor."""
    tid = t.native_id
    if tid is None:
        return None
    try:
        return time.clock_gettime((~tid << 3) | 6)
    except OSError:
        return None


def thread_cpu_by_role() -> Optional[Dict[str, float]]:
    """Cumulative CPU seconds of the three named roles: the ended
    threads' tally plus every live thread's clock. None where another
    thread's clock cannot be read (not Linux)."""
    if not sys.platform.startswith("linux") or \
            not hasattr(time, "clock_gettime"):
        return None
    with _ended_l:      # a thread cannot tally itself mid-sweep
        out = dict(_ended_cpu)
        for t in threading.enumerate():
            role = thread_role(t.name)
            if role is None or getattr(t, "_cpu_tallied", False):
                continue
            cpu = _thread_cpu_s(t)
            if cpu is not None:
                out[role] += cpu
    return out


def default_device_fn() -> Dict[str, float]:
    """The `device.*` metrics family (ISSUE 11): pad-waste ratio and
    per-arm dispatch/compile accounting from the kernel hot path,
    kernel-cache entries, and HBM-in-use where the backend reports it.
    Lazy imports: the collector must be constructible before (or
    without) the ops layer touching jax."""
    out: Dict[str, float] = {}
    try:
        from ..ops.select import (device_hbm_bytes, device_stats_snapshot,
                                  kernel_cache_entries)
        snap = device_stats_snapshot()
        out["device.pad_waste_ratio"] = snap["pad_waste_ratio"]
        out["device.pad_rows_shipped"] = snap["pad_rows_shipped"]
        out["device.packs"] = snap["packs"]
        for arm, s in snap["dispatch_s"].items():
            out[f"device.dispatch_s.{arm}"] = s
        for arm, c in snap["compiles"].items():
            out[f"device.compiles.{arm}"] = c
        for arm, d in snap["dispatches"].items():
            out[f"device.dispatches.{arm}"] = d
        out["device.kernel_cache_entries"] = kernel_cache_entries()
        out["device.hbm_bytes_in_use"] = device_hbm_bytes()
        # mesh-sharded residency economics: present only when a mesh
        # dispatcher exists, so a single-chip run's series stay lean
        from ..ops.select import mesh_stats_snapshot
        ms = mesh_stats_snapshot()
        if ms:
            out["device.mesh_devices"] = ms["devices"]
            out["device.mesh_resident_bytes_per_device"] = \
                ms["resident_bytes_per_device"]
            out["device.mesh_reshard_uploads"] = ms["reshard_uploads"]
            out["device.mesh_reshard_bytes"] = ms["reshard_bytes"]
            out["device.mesh_delta_scatters"] = ms["delta_scatters"]
            out["device.mesh_resident_hits"] = ms["resident_hits"]
            out["device.mesh_stale_misses"] = ms["stale_misses"]
    except Exception:       # pragma: no cover — defensive
        pass
    return out


# acceptance thresholds (ISSUE r6): a run passes when p99 in the
# last window-half stays within this ratio of the first half and RSS
# grows no faster than this slope
MAX_P99_DRIFT_RATIO = 1.5
MAX_RSS_SLOPE_MB_PER_HOUR = 100.0


def _slope_per_hour(ts_min: List[float], values: List[float]) -> float:
    """Least-squares slope in units/hour over (minutes, value) points —
    robust to one noisy endpoint, unlike last-minus-first."""
    return least_squares_slope(list(zip(ts_min, values))) * 60.0


def flatness_verdict(windows: List[Dict],
                     max_p99_ratio: float = MAX_P99_DRIFT_RATIO,
                     max_rss_slope: float = MAX_RSS_SLOPE_MB_PER_HOUR,
                     warmup_windows: int = 1) -> Dict:
    """The machine-checkable steady-state verdict over per-window
    samples. p99 drift is median-of-last-half over median-of-first-half
    (single-window spikes don't flip the verdict); RSS slope is the
    least-squares fit across the measured windows.

    The first `warmup_windows` are excluded when enough windows remain
    (>=3 measured): the run's BOUNDED structures (identity memos,
    changelog ring, harness history, JIT caches) legitimately fill to
    their plateau during the first window, and a steady-state verdict
    judges the plateau, not the fill — the r6 6-min run measured
    +29 MB in window 1-2 and then three windows of RSS flat to 0.1 MB.
    The exclusion is recorded in the verdict."""
    out: Dict = {"max_p99_drift_ratio": max_p99_ratio,
                 "max_rss_slope_mb_per_hour": max_rss_slope}
    if len(windows) - warmup_windows >= 3:
        windows = windows[warmup_windows:]
        out["warmup_windows_excluded"] = warmup_windows
    else:
        out["warmup_windows_excluded"] = 0
    if len(windows) < 2:
        out.update({"pass": False, "reason": "fewer than 2 windows"})
        return out
    p99 = [w["p99_ms"] for w in windows]
    half = max(1, len(p99) // 2)
    # median of each half: real drift raises every late window (and
    # the median with it); one noisy-neighbor window must not flip a
    # steady-state verdict the other five windows contradict
    first = median(p99[:half])
    last = median(p99[len(p99) - half:])
    ratio = (last / first) if first > 0 else 1.0
    rss_slope = _slope_per_hour([w["t_min"] for w in windows],
                                [w["rss_mb"] for w in windows])
    out["p99_drift_ratio"] = round(ratio, 3)
    out["p99_first_half_ms"] = round(first, 1)
    out["p99_last_half_ms"] = round(last, 1)
    out["rss_slope_mb_per_hour"] = round(rss_slope, 1)
    out["pass"] = bool(ratio <= max_p99_ratio
                       and rss_slope <= max_rss_slope)
    if not out["pass"]:
        reasons = []
        if ratio > max_p99_ratio:
            reasons.append(f"p99 drift {ratio:.2f}x > {max_p99_ratio}x")
        if rss_slope > max_rss_slope:
            reasons.append(f"rss slope {rss_slope:.0f} MB/h > "
                           f"{max_rss_slope:.0f} MB/h")
        out["reason"] = "; ".join(reasons)
    return out


class TelemetryCollector:
    """Struct-of-arrays history ring. One instance per server (or per
    bench); `sample_once()` is the deterministic entry the thread loop
    and the tests share, exactly like Governor.sample_once."""

    # cumulative series (counters, dispatch seconds/counts): rates
    # derive from slot deltas at READ time, so the ring stores raw
    # totals and a wrap never corrupts a rate. (stage_count.* is NOT
    # here: it is reservoir occupancy, capped at STAGE_RESERVOIR, not
    # a monotone total.)
    RATE_PREFIXES = ("counter.", "device.dispatch_s.",
                     "device.compiles.", "device.dispatches.",
                     "device.packs", "device.mesh_reshard_uploads",
                     "device.mesh_reshard_bytes",
                     "device.mesh_delta_scatters",
                     "device.mesh_resident_hits",
                     # CPU seconds: the rate is cores
                     "process.cpu_s", "thread_cpu.")

    def __init__(self, interval_s: float = DEFAULT_INTERVAL_S,
                 slots: int = DEFAULT_SLOTS,
                 gauges_fn: Optional[Callable[[], Dict[str, float]]] = None,
                 latency_fn: Optional[Callable[[float], float]] = None,
                 stage_fn: Optional[Callable[[], Dict[str, dict]]] = None,
                 device_fn: Optional[Callable[[], Dict[str, float]]]
                 = default_device_fn,
                 extra_fn: Optional[Callable[[], Dict[str, float]]] = None):
        self.interval_s = max(float(interval_s), 0.05)
        self.slots = max(int(slots), 8)
        self.gauges_fn = gauges_fn
        self.latency_fn = latency_fn
        self.stage_fn = stage_fn
        self.device_fn = device_fn
        self.extra_fn = extra_fn
        self._l = make_lock()
        self._t = np.full(self.slots, np.nan, dtype=np.float64)
        self._series: Dict[str, np.ndarray] = {}
        self._n = 0                     # total samples ever written
        self._dropped_series = 0
        self._started_at = time.time()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="telemetry")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:       # pragma: no cover — defensive
                import logging
                logging.getLogger("nomad_tpu.telemetry").exception(
                    "telemetry sample failed")

    # -- the sampling step ---------------------------------------------
    @staticmethod
    def _cpu_row() -> Dict[str, float]:
        """The CPU ledger's series: the process's clock and, where
        another thread's can be read, the four roles'."""
        row = {"process.cpu_s": time.process_time()}
        roles = thread_cpu_by_role()
        if roles is not None:
            for role, total in roles.items():
                row[f"thread_cpu.{role}_s"] = total
            row["thread_cpu.other_s"] = max(
                row["process.cpu_s"] - sum(roles.values()), 0.0)
        return row

    def _collect_row(self) -> Dict[str, float]:
        row: Dict[str, float] = {"process.rss_mb": rss_mb()}
        row.update(self._cpu_row())
        if self.gauges_fn is not None:
            try:
                row.update(self.gauges_fn())
            except Exception:       # pragma: no cover — defensive
                pass
        if self.latency_fn is not None:
            try:
                # FULL latency (host + queue wait): what an eval
                # experienced — the flatness verdict's p99 series
                row["latency.p50_ms"] = self.latency_fn(50)
                row["latency.p99_ms"] = self.latency_fn(99)
            except Exception:       # pragma: no cover — defensive
                pass
        # counter totals: raw cumulative sums; read-side slot deltas
        # become the rate series `operator top` renders
        for name, total in metrics.counter_totals().items():
            row[f"counter.{name}"] = total
        if self.stage_fn is not None:
            try:
                for stage, pct in self.stage_fn().items():
                    if stage.endswith(stages.CPU_SUFFIX):
                        # a span's CPU companion: the median alone,
                        # a column of its stage's row (`operator top`)
                        row[f"stage.{stage}.p50_ms"] = \
                            pct.get("p50_ms", 0.0)
                        continue
                    row[f"stage.{stage}.p50_ms"] = pct.get("p50_ms", 0.0)
                    row[f"stage.{stage}.p99_ms"] = pct.get("p99_ms", 0.0)
                    row[f"stage_count.{stage}"] = pct.get("count", 0)
            except Exception:       # pragma: no cover — defensive
                pass
        if self.device_fn is not None:
            try:
                row.update(self.device_fn())
            except Exception:       # pragma: no cover — defensive
                pass
        if self.extra_fn is not None:
            try:
                row.update(self.extra_fn())
            except Exception:       # pragma: no cover — defensive
                pass
        return row

    def sample_once(self, now: Optional[float] = None) -> int:
        """Collect one row into the ring; returns the sample ordinal.
        Series first seen mid-run begin at this slot (earlier slots
        hold NaN); series absent this sample record NaN so a
        wrapped-over stale value can never masquerade as fresh."""
        row = self._collect_row()
        now = time.time() if now is None else now
        with self._l:
            cur = self._n % self.slots
            self._t[cur] = now
            for arr in self._series.values():
                arr[cur] = np.nan
            for name, value in row.items():
                arr = self._series.get(name)
                if arr is None:
                    if len(self._series) >= MAX_SERIES:
                        self._dropped_series += 1
                        continue
                    arr = self._series[name] = np.full(
                        self.slots, np.nan, dtype=np.float64)
                try:
                    arr[cur] = float(value)
                except (TypeError, ValueError):
                    arr[cur] = np.nan
            self._n += 1
            return self._n

    # -- reads ---------------------------------------------------------
    def _order(self) -> np.ndarray:
        """Chronological slot indexes of the valid window."""
        if self._n <= self.slots:
            return np.arange(self._n)
        cur = self._n % self.slots
        return np.concatenate([np.arange(cur, self.slots),
                               np.arange(0, cur)])

    def history(self, last: Optional[int] = None) -> dict:
        """The ring, chronological, JSON-safe (NaN -> None). `last`
        limits to the most recent N samples."""
        with self._l:
            order = self._order()
            if last is not None and last > 0:
                order = order[-last:]
            t = self._t[order]
            series = {name: arr[order].tolist()
                      for name, arr in sorted(self._series.items())}
        def clean(vals):
            return [None if (isinstance(v, float) and math.isnan(v))
                    else v for v in vals]
        return {
            "interval_s": self.interval_s,
            "slots": self.slots,
            "samples": self._n,
            "series_count": len(series),
            "series_dropped": self._dropped_series,
            "t": t.tolist(),
            "series": {k: clean(v) for k, v in series.items()},
            "rates": {k: clean(self._rate(t, np.asarray(v, np.float64)))
                      for k, v in series.items()
                      if k.startswith(self.RATE_PREFIXES)},
        }

    @staticmethod
    def _rate(t: np.ndarray, totals: np.ndarray) -> List[float]:
        """Per-second rates from a cumulative series: delta over dt
        per slot pair (first slot NaN — no left neighbor). A counter
        reset (delta < 0, e.g. a series re-keyed) reads NaN, not a
        negative rate."""
        out = np.full(len(totals), np.nan)
        if len(totals) >= 2:
            dt = np.diff(t)
            dv = np.diff(totals)
            with np.errstate(invalid="ignore", divide="ignore"):
                r = np.where((dt > 0) & (dv >= 0), dv / np.maximum(
                    dt, 1e-9), np.nan)
            out[1:] = r
        return [float(v) for v in out]

    def windows(self) -> List[Dict]:
        """The window shape over the ring — the rows
        `flatness_verdict` consumes: per-slot t_min (from
        the first retained sample), p99_ms (full-latency reservoir),
        rss_mb, and the evals counted between slots."""
        with self._l:
            order = self._order()
            t = self._t[order]
            p99 = self._series.get("latency.p99_ms")
            rss = self._series.get("process.rss_mb")
            ev = self._series.get("counter.nomad.worker.eval_processed")
            p99 = p99[order] if p99 is not None else None
            rss = rss[order] if rss is not None else None
            ev = ev[order] if ev is not None else None
        out: List[Dict] = []
        if len(t) == 0:
            return out
        t0 = t[0]
        for i in range(len(t)):
            w = {"t_min": round((t[i] - t0) / 60.0, 4)}
            w["p99_ms"] = (0.0 if p99 is None or math.isnan(p99[i])
                           else float(p99[i]))
            w["rss_mb"] = (0.0 if rss is None or math.isnan(rss[i])
                           else float(rss[i]))
            if ev is not None and i > 0 and not math.isnan(ev[i]) \
                    and not math.isnan(ev[i - 1]):
                w["evals"] = int(max(ev[i] - ev[i - 1], 0))
            else:
                w["evals"] = 0
            out.append(w)
        return out

    # the live verdict needs this much post-warmup history before a
    # pass/fail is meaningful: an RSS slope fit over a few seconds is
    # noise (the first e2e drive measured -10161 MB/h over 3 slots)
    MIN_VERDICT_SPAN_S = 120.0

    def flatness(self, **kw) -> dict:
        """Live verdict: `flatness_verdict` over the in-process ring
        (the chaos matrix runs the same math over its cells' windows).

        Its thresholds are calibrated for 60-second windows
        (warmup_windows=1 excludes a full minute of legitimate
        bounded-structure fill). The ring samples much faster, so the
        warmup exclusion is rescaled to cover the same ~60 seconds of
        wall clock, and until MIN_VERDICT_SPAN_S of post-warmup
        history exists the verdict reports pass=None ("insufficient
        history") instead of failing a healthy server on a
        noise-dominated slope fit."""
        windows = self.windows()
        kw.setdefault("warmup_windows",
                      max(1, math.ceil(60.0 / self.interval_s)))
        out = flatness_verdict(windows, **kw)
        out["windows_measured"] = len(windows)
        out["interval_s"] = self.interval_s
        warmup = kw["warmup_windows"]
        measured = windows[warmup:] if len(windows) - warmup >= 3 \
            else windows
        span_s = ((measured[-1]["t_min"] - measured[0]["t_min"]) * 60.0
                  if len(measured) >= 2 else 0.0)
        out["span_s"] = round(span_s, 1)
        if span_s < self.MIN_VERDICT_SPAN_S:
            out["pass"] = None
            out["reason"] = (
                f"insufficient history: {span_s:.0f}s of post-warmup "
                f"windows < {self.MIN_VERDICT_SPAN_S:.0f}s — verdict "
                f"needs a longer retained window")
        return out

    def status(self) -> dict:
        with self._l:
            nbytes = self._t.nbytes + sum(
                a.nbytes for a in self._series.values())
            return {
                "enabled": True,
                "running": self._thread is not None,
                "interval_s": self.interval_s,
                "slots": self.slots,
                "samples": self._n,
                "series_count": len(self._series),
                "series_dropped": self._dropped_series,
                "ring_bytes": int(nbytes),
                "started_at": self._started_at,
            }
