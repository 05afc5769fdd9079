"""The client side of a run: HTTP on the agent, and nothing else.

Registers go out as PUT /v1/jobs, each on a connection of its own;
completion comes from ONE /v1/event/stream subscription on the
Evaluation topic — nothing polls; allocations are read back with GET
/v1/job/<id>/allocations once the window has closed. Every time is
this process's perf_counter.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Callable, Dict, List, Optional

from .traffic import Request

TERMINAL = ("complete", "failed", "canceled")


class Http:
    """One keep-alive connection; not shared between threads."""

    def __init__(self, addr: str, timeout_s: float = 120.0):
        self.addr = addr
        self.timeout_s = timeout_s
        self.conn = http.client.HTTPConnection(addr, timeout=timeout_s)

    def request(self, method: str, path: str, body: Optional[bytes] = None):
        """(status, decoded JSON body); reconnects once if the server
        closed an idle connection. A request with a body goes out on a
        connection of its own: the agent's handler caches the first
        body it reads on a keep-alive connection and serves it again to
        every later request there (api/http.py _read_body_bytes; see
        PERF.md, Open questions), which is also what its own ApiClient,
        one connection per call, never meets."""
        if body is not None:
            self.conn.close()
        for attempt in (0, 1):
            try:
                self.conn.request(method, path, body=body, headers={
                    "Content-Type": "application/json"})
                resp = self.conn.getresponse()
                raw = resp.read()
                return resp.status, (json.loads(raw) if raw else None)
            except (http.client.RemoteDisconnected, BrokenPipeError,
                    ConnectionResetError):
                self.conn.close()
                self.conn = http.client.HTTPConnection(
                    self.addr, timeout=self.timeout_s)
                if attempt:
                    raise

    def close(self) -> None:
        self.conn.close()


class EvalWatch(threading.Thread):
    """The one event-stream subscription. Records, per job id, the time
    its eval reached a terminal status and the eval as the event
    carried it; `on_done` (if set) is called from this thread."""

    def __init__(self, addr: str,
                 on_done: Optional[Callable[[str, float], None]] = None):
        super().__init__(daemon=True, name="bench-eval-watch")
        self.addr = addr
        self.on_done = on_done
        self.done_at: Dict[str, float] = {}
        self.evals: Dict[str, dict] = {}
        self.events = 0
        self.error: Optional[str] = None
        self.ready = threading.Event()
        self._halt = False
        self._cv = threading.Condition()
        self._resp = None

    def run(self) -> None:
        try:
            conn = http.client.HTTPConnection(self.addr, timeout=30.0)
            conn.request("GET", "/v1/event/stream?topic=Evaluation")
            self._resp = resp = conn.getresponse()
            self.ready.set()
            while not self._halt:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line or line == b"{}":
                    continue
                now = time.perf_counter()
                for ev in json.loads(line).get("Events", []):
                    self.events += 1
                    p = ev.get("payload") or {}
                    if p.get("status") in TERMINAL and p.get("job_id"):
                        self._note(p["job_id"], now, p)
        except Exception as e:
            if not self._halt:
                self.error = f"{type(e).__name__}: {e}"
        finally:
            self.ready.set()

    def _note(self, job_id: str, now: float, ev: dict) -> None:
        with self._cv:
            first = job_id not in self.done_at
            if first:
                self.done_at[job_id] = now
            self.evals[job_id] = ev
            self._cv.notify_all()
        if first and self.on_done is not None:
            self.on_done(job_id, now)

    def wait_for(self, job_ids: List[str], deadline: float) -> List[str]:
        """Blocks until every job has a terminal eval or `deadline`
        (perf_counter) passes; returns the ids still missing."""
        with self._cv:
            while True:
                missing = [j for j in job_ids if j not in self.done_at]
                left = deadline - time.perf_counter()
                if not missing or left <= 0 or self.error:
                    return missing
                self._cv.wait(min(left, 1.0))

    def stop(self) -> None:
        self._halt = True
        try:
            if self._resp is not None:
                self._resp.close()
        except Exception:
            pass


class Sent:
    """What the client saw of one request."""
    __slots__ = ("req", "sent_at", "acked_at", "status", "eval_ids", "error")

    def __init__(self, req: Request):
        self.req = req
        self.sent_at = self.acked_at = None
        self.status = 0
        self.eval_ids: List[str] = []
        self.error = ""


def put_jobs(http: Http, sent: Sent) -> None:
    sent.sent_at = time.perf_counter()
    try:
        sent.status, body = http.request("PUT", "/v1/jobs", sent.req.body)
    except Exception as e:
        sent.status, body = 0, None
        sent.error = f"{type(e).__name__}: {e}"
    sent.acked_at = time.perf_counter()
    if sent.status == 200:
        rows = body if isinstance(body, list) else [body]
        bad = [r for r in rows if not r or "Error" in r or not r.get("EvalID")]
        if bad:
            sent.status, sent.error = 500, f"register refused: {bad[:2]}"
        else:
            sent.eval_ids = [r["EvalID"] for r in rows]
    elif not sent.error:
        sent.error = f"HTTP {sent.status}: {body}"


class OpenLoop:
    """Sends each request at its due time, whatever came of the ones
    before: `senders` threads share the schedule round-robin, so one
    slow PUT delays only every senders-th request behind it (and that
    delay is in `sent_at - due`, which the run reports)."""

    def __init__(self, addr: str, requests: List[Request], senders: int):
        self.addr = addr
        self.sent = [Sent(r) for r in requests]
        self.senders = max(1, int(senders))
        self.t0: Optional[float] = None
        self._threads: List[threading.Thread] = []

    def start(self, t0: float) -> None:
        """`t0` is the perf_counter time of due_s == 0."""
        self.t0 = t0
        for k in range(self.senders):
            t = threading.Thread(target=self._run, args=(k,), daemon=True,
                                 name=f"bench-sender-{k}")
            t.start()
            self._threads.append(t)

    def _run(self, k: int) -> None:
        http = Http(self.addr)
        try:
            for sent in self.sent[k::self.senders]:
                wait = self.t0 + sent.req.due_s - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                put_jobs(http, sent)
        finally:
            http.close()

    def join(self, timeout_s: float) -> None:
        end = time.perf_counter() + timeout_s
        for t in self._threads:
            t.join(max(0.0, end - time.perf_counter()))


class ClosedLoop(threading.Thread):
    """Keeps `in_flight` jobs outstanding: whenever room for one more
    bulk request opens (completions arrive from the event stream), the
    next one goes out. Stops when told or when the requests run out."""

    def __init__(self, addr: str, requests: List[Request], in_flight: int):
        super().__init__(daemon=True, name="bench-closed-loop")
        self.addr = addr
        self.sent = [Sent(r) for r in requests]
        self.in_flight = int(in_flight)
        self._cv = threading.Condition()
        self._outstanding = 0
        self._halt = False

    def job_done(self, _job_id: str, _now: float) -> None:
        with self._cv:
            self._outstanding -= 1
            self._cv.notify_all()

    def run(self) -> None:
        http = Http(self.addr)
        try:
            for sent in self.sent:
                n = len(sent.req.jobs)
                with self._cv:
                    while not self._halt and \
                            self._outstanding + n > self.in_flight:
                        self._cv.wait(1.0)
                    if self._halt:
                        return
                    self._outstanding += n
                put_jobs(http, sent)
                if sent.status != 200:
                    with self._cv:
                        self._outstanding -= n
        finally:
            http.close()

    def stop(self) -> None:
        with self._cv:
            self._halt = True
            self._cv.notify_all()
