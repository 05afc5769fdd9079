"""Node heartbeat TTL timers on ONE thread (nomad/heartbeat.go).

The reference arms a time.AfterFunc per node, which costs a goroutine
only when it fires. A threading.Timer per node is an OS thread per
node: a 10k-node fleet registered through Node.Register holds 10k
sleeping threads, and the process is killed for it on a 40 GiB host.
Here every node's deadline sits in one heap served by one thread; a
reset pushes a new entry and the stale one is skipped when it surfaces
(lazy deletion), so the heap holds at most the resets of one TTL.
"""

from __future__ import annotations

import heapq
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..utils.locks import make_condition

LOG = logging.getLogger("nomad_tpu.server")


class HeartbeatTimers:
    def __init__(self, on_expire: Callable[[str], None]):
        self._on_expire = on_expire
        self._cv = make_condition()
        self._deadline: Dict[str, float] = {}
        self._heap: List[Tuple[float, str]] = []
        self._thread: Optional[threading.Thread] = None
        self._stopped = False

    def reset(self, node_id: str, ttl_s: float) -> None:
        """(Re)arm node_id to expire ttl_s from now."""
        deadline = time.monotonic() + ttl_s
        with self._cv:
            if self._stopped:
                return
            self._deadline[node_id] = deadline
            heapq.heappush(self._heap, (deadline, node_id))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="heartbeat-ttl")
                self._thread.start()
            if self._heap[0][0] == deadline:
                self._cv.notify()       # new earliest deadline

    def clear(self) -> None:
        """Disarm every node (leadership lost)."""
        with self._cv:
            self._deadline.clear()
            self._heap.clear()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._deadline.clear()
            self._heap.clear()
            self._cv.notify()

    def armed(self) -> int:
        with self._cv:
            return len(self._deadline)

    def _run(self) -> None:
        while True:
            with self._cv:
                if self._stopped:
                    return
                now = time.monotonic()
                expired = []
                while self._heap and self._heap[0][0] <= now:
                    deadline, node_id = heapq.heappop(self._heap)
                    if self._deadline.get(node_id) == deadline:
                        del self._deadline[node_id]
                        expired.append(node_id)
                if not expired:
                    self._cv.wait(self._heap[0][0] - now
                                  if self._heap else None)
                    continue
            for node_id in expired:
                try:
                    self._on_expire(node_id)
                except Exception:
                    # one node's failed status write must not stop the
                    # TTL service for the rest of the fleet
                    LOG.exception("heartbeat expiry for node %s",
                                  node_id[:8])
