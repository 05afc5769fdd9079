"""Opt-in runtime sanitizer for the placement and scatter-delta
kernels (`NOMAD_TPU_SANITIZE=1`).

The static passes prove call-site discipline; this module checks the
VALUES. Checkify-style guards run host-side at the kernel boundary —
where the arrays are still (or again) numpy — so the device never pays
for them and the checks hold even when the dispatch itself is async:

  check_finite    NaN/Inf screens on the columns a dispatch ships
                  (capacity/used/ask) and the scores it returns — a
                  NaN in `used` silently wins every argmax
  check_rows      out-of-bounds row guards on the scatter-delta and
                  overlay index vectors — `.at[rows]` DROPS
                  out-of-range rows on TPU instead of raising, which
                  is exactly the silent corruption mode

Always-on (the cost is a set lookup): a per-kernel distinct
trace-signature counter. Every dispatch arm reports its compile key
(kernel name, shape bucket, statics); a NEW signature means XLA traced
and compiled. The total is exported as the `nomad.lint.recompiles`
metric gauge and registered as the governor's `lint.recompiles` gauge,
so a recompile storm (the failure mode the jit-hygiene pass guards
statically) shows up in `/v1/operator/governor` as a climbing number
instead of a mystery p99.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

import numpy as np
from ..utils.locks import make_lock

ENV = "NOMAD_TPU_SANITIZE"


def enabled() -> bool:
    """Read live (not cached) so tests and operators can toggle the
    env var without a restart; one getenv per guarded kernel entry."""
    return os.environ.get(ENV, "") not in ("", "0", "off", "no")


class SanitizerError(RuntimeError):
    """A value-level invariant violation caught at a kernel boundary."""


def check_finite(tag: str, **arrays) -> None:
    """Raise when any float array carries NaN/Inf. Non-float and
    non-numpy values are skipped — device arrays are checked at the
    host boundaries where they have been pulled anyway."""
    for name, a in arrays.items():
        if a is None or not isinstance(a, np.ndarray):
            continue
        if a.dtype.kind != "f":
            continue
        if not np.isfinite(a).all():
            bad = int((~np.isfinite(a)).sum())
            raise SanitizerError(
                f"sanitizer[{tag}]: {name} carries {bad} non-finite "
                f"value(s) — a NaN/Inf here silently corrupts every "
                f"downstream argmax")


def check_rows(tag: str, rows, n: int) -> None:
    """Raise when a scatter/overlay row-index vector leaves [0, n).
    On TPU `.at[rows]` drops out-of-range rows silently, so this is
    the only place the bug is visible."""
    idx = np.asarray(rows)
    if idx.size == 0:
        return
    lo = int(idx.min())
    hi = int(idx.max())
    if lo < 0 or hi >= n:
        raise SanitizerError(
            f"sanitizer[{tag}]: row indices [{lo}, {hi}] fall outside "
            f"the table's [0, {n}) — the device scatter would drop "
            f"them silently")


class TraceCounter:
    """Compile events per kernel. `note()` is the dispatch-side hook;
    it returns True when the signature is new since the last
    invalidation (== a trace + compile happened). The exported total
    is a MONOTONE cumulative compile count, not len(seen): after the
    governor's `clear_kernel_caches` reclaim (which must call
    `invalidate()`), warm shapes re-trace and each one moves the gauge
    again — a cache-thrash storm stays visible instead of hiding
    behind already-seen keys."""

    def __init__(self):
        self._l = make_lock()
        self._seen: Dict[str, set] = {}
        self._total = 0

    def note(self, kernel: str, signature: Tuple) -> bool:
        from ..utils import metrics
        with self._l:
            sigs = self._seen.setdefault(kernel, set())
            if signature in sigs:
                return False
            sigs.add(signature)
            self._total += 1
            # publish under the lock: metrics has its own independent
            # lock (no ordering cycle), and publishing outside would
            # let two concurrent notes land out of order and make the
            # "monotone by construction" gauge transiently regress
            metrics.set_gauge("nomad.lint.recompiles", self._total)
        return True

    def count(self) -> int:
        """Cumulative compile events (monotone; the gauge value)."""
        with self._l:
            return self._total

    def per_kernel(self) -> Dict[str, int]:
        """Distinct signatures since the last invalidation."""
        with self._l:
            return {k: len(v) for k, v in sorted(self._seen.items())}

    def signatures(self) -> Dict[str, set]:
        """A copy of the signatures seen per kernel since the last
        invalidation (chip_smoke.py diffs two of these to tell a new
        count bucket from a new shape family)."""
        with self._l:
            return {k: set(v) for k, v in self._seen.items()}

    def invalidate(self) -> None:
        """The compiled caches were dropped: forget seen signatures so
        re-traces count as fresh compiles, keep the cumulative total."""
        with self._l:
            self._seen.clear()

    def reset(self) -> None:
        with self._l:
            self._seen.clear()
            self._total = 0


# process-wide: every kernel arm (workers, gateways, benches) reports
# into the same counter the governor gauge reads
traces = TraceCounter()
