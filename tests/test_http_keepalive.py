"""A response leaves the agent in ONE write (ISSUE 27): headers and
body as two segments on an unbuffered socket made every GET on a
kept-alive connection wait out the client's delayed ACK, 41-44 ms
whatever the path."""
import http.client
import json
import time

from nomad_tpu.api import HTTPApiServer
from nomad_tpu.server import Server, ServerConfig


def test_kept_alive_get_answers_in_under_10_ms():
    srv = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=3600.0))
    srv.start()
    api = HTTPApiServer(srv, port=0)
    api.start()
    conn = http.client.HTTPConnection("127.0.0.1", api.port, timeout=30.0)
    try:
        took = []
        for path in ["/v1/agent/self"] * 6 + ["/v1/jobs", "/v1/nodes"] * 3:
            t0 = time.perf_counter()
            conn.request("GET", path)
            resp = conn.getresponse()
            body = resp.read()
            took.append(time.perf_counter() - t0)
            assert resp.status == 200 and json.loads(body) is not None
            assert not resp.will_close       # one connection throughout
        # the first pays the connect; a loaded test host may stall one
        # or two: a delayed ACK would hold back EVERY one of them
        took = sorted(took[1:])
        assert took[len(took) // 2] < 0.010, took
    finally:
        conn.close()
        api.shutdown()
        srv.shutdown()
