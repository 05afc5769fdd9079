"""Rolling-window drift detection over throughput / p99 / RSS.

The detector answers the question the round-5 soak raised: "the
process was fast an hour ago and is slow now — what grew?" Each
tracked metric keeps a bounded rolling window of (t, value) samples; a
least-squares slope plus a last-half/first-half ratio classify the
series as flat or drifting. When a performance series (p99 up,
throughput down, RSS up) drifts, the detector names the registered
structure gauge whose own normalized growth over the same window is
largest — the structure most likely responsible — in the emitted
event. Pure functions over explicit samples, so synthetic series test
it without a clock.
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional, Tuple
from ..utils.locks import make_lock

# direction a metric degrades in: p99/rss degrade upward, throughput
# degrades downward
DEGRADES_UP = "up"
DEGRADES_DOWN = "down"


def least_squares_slope(points: List[Tuple[float, float]]) -> float:
    """Slope of a least-squares fit over (t, value) points, in
    value-units per t-unit. Shared by the drift detector and the flatness
    verdict (telemetry/collector.py) so the regression math exists
    once."""
    n = len(points)
    if n < 2:
        return 0.0
    mt = sum(t for t, _ in points) / n
    mv = sum(v for _, v in points) / n
    num = sum((t - mt) * (v - mv) for t, v in points)
    den = sum((t - mt) ** 2 for t, _ in points)
    if den <= 0:
        return 0.0
    return num / den


class RollingSeries:
    """Bounded (t, value) window with slope and half-over-half ratio."""

    def __init__(self, maxlen: int = 60):
        self._q: deque = deque(maxlen=maxlen)
        self._l = make_lock()

    def add(self, t: float, value: float) -> None:
        with self._l:
            self._q.append((float(t), float(value)))

    def __len__(self) -> int:
        return len(self._q)

    def samples(self) -> List[Tuple[float, float]]:
        with self._l:
            return list(self._q)

    def last(self) -> Optional[float]:
        with self._l:
            return self._q[-1][1] if self._q else None

    def slope_per_hour(self) -> float:
        """Least-squares slope in value-units per hour (t is seconds)."""
        return least_squares_slope(self.samples()) * 3600.0

    def ratio(self) -> float:
        """Mean of the last half over mean of the first half (>=0).
        1.0 == flat; 2.0 == doubled across the window."""
        pts = [v for _, v in self.samples()]
        n = len(pts)
        if n < 4:
            return 1.0
        half = n // 2
        first = sum(pts[:half]) / half
        last = sum(pts[n - half:]) / half
        if first <= 0:
            # a zero first half means "no signal yet" (empty latency
            # reservoir, idle counter), not an infinite degradation
            return 1.0
        return last / first


class DriftDetector:
    """Tracks performance series and structure-size series; check()
    returns structured drift findings."""

    def __init__(self, window: int = 60, min_samples: int = 10,
                 ratio_max: float = 1.5):
        self.window = window
        self.min_samples = min_samples
        self.ratio_max = ratio_max          # degradation ratio threshold
        # name -> (series, degrade direction)
        self._perf: Dict[str, Tuple[RollingSeries, str]] = {}
        # name -> series of structure sizes (suspects)
        self._structs: Dict[str, RollingSeries] = {}
        self._l = make_lock()

    # -- feeding -------------------------------------------------------
    def observe_perf(self, name: str, t: float, value: float,
                     degrades: str = DEGRADES_UP) -> None:
        with self._l:
            entry = self._perf.get(name)
            if entry is None:
                entry = (RollingSeries(self.window), degrades)
                self._perf[name] = entry
        entry[0].add(t, value)

    def observe_struct(self, name: str, t: float, value: float) -> None:
        with self._l:
            s = self._structs.get(name)
            if s is None:
                s = RollingSeries(self.window)
                self._structs[name] = s
        s.add(t, value)

    # -- checking ------------------------------------------------------
    def _suspect(self) -> Optional[Tuple[str, float]]:
        """The structure with the largest half-over-half growth ratio
        (> 1.05, i.e. actually growing), or None."""
        best = None
        with self._l:
            structs = list(self._structs.items())
        for name, series in structs:
            if len(series) < 4:
                continue
            r = series.ratio()
            if r <= 1.05:
                continue
            if best is None or r > best[1]:
                best = (name, r)
        return best

    def check(self) -> List[dict]:
        """Drift findings for every degrading performance series."""
        findings: List[dict] = []
        with self._l:
            perf = list(self._perf.items())
        for name, (series, degrades) in perf:
            if len(series) < self.min_samples:
                continue
            r = series.ratio()
            drifting = (r >= self.ratio_max if degrades == DEGRADES_UP
                        else (r > 0 and 1.0 / r >= self.ratio_max))
            if not drifting:
                continue
            finding = {
                "kind": "drift",
                "metric": name,
                "ratio": round(r, 3),
                "slope_per_hour": round(series.slope_per_hour(), 3),
                "degrades": degrades,
            }
            suspect = self._suspect()
            if suspect is not None:
                finding["suspect_structure"] = suspect[0]
                finding["suspect_growth_ratio"] = round(suspect[1], 3)
            findings.append(finding)
        return findings
