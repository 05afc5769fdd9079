"""Bring-up guards (ISSUE 21): the compile cache can be placed from
outside and is otherwise at one fixed in-checkout path; no entry point
continues on the CPU after failing to get an accelerator it was asked
for; chip_smoke.py's scenario builder imports without JAX and its
plain-reference checkers reject bad placements; node TTL timers cost
one thread, not one per node; the accelerator fingerprint never opens
the device.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env_extra=None, env_drop=(), timeout=120):
    env = dict(os.environ)
    for k in env_drop:
        env.pop(k, None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


_CACHE_CODE = (
    "import jax\n"
    "from nomad_tpu.utils.platform import configure_compile_cache\n"
    "print(configure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_env_is_left_to_jax(tmp_path):
    want = str(tmp_path / "cc")
    out = _run(_CACHE_CODE, {"JAX_COMPILATION_CACHE_DIR": want})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]


def test_compile_cache_default_is_one_in_checkout_path():
    outs = [_run(_CACHE_CODE, env_drop=("JAX_COMPILATION_CACHE_DIR",))
            for _ in range(2)]
    for out in outs:
        assert out.returncode == 0, out.stderr[-2000:]
    paths = {tuple(out.stdout.split()) for out in outs}
    assert paths == {(os.path.join(REPO, ".jax_cache"),) * 2}


def test_agent_exits_nonzero_without_the_accelerator_it_was_asked_for():
    """JAX_PLATFORMS=tpu on a host with no TPU: the agent must end, not
    print a warning and serve from the CPU."""
    out = subprocess.run(
        [sys.executable, "-m", "nomad_tpu.cli", "agent", "-dev",
         "-http-port", "0", "-rpc-port", "0"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "tpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "failed to initialize" in out.stderr
    assert "agent started" not in out.stdout


def test_chip_smoke_refuses_to_run_without_an_accelerator():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""     # no result line


def test_chip_smoke_result_line_has_exactly_the_contract_keys(tmp_path):
    """The driver reads the LAST stdout line and refuses any key beyond
    ok / device{platform, kind, count}; the evidence is the line before
    it. Checked on the explicit small CPU rehearsal."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu", "--nodes", "200", "--batch-count", "600",
         "--services", "4", "--out", str(tmp_path / "out")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "TMPDIR": str(tmp_path)},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    report = json.loads(lines[-2])["report"]
    assert report["rehearsal"] is True and report["problems"] == []
    with open(tmp_path / "out" / "chip_smoke.json") as f:
        assert json.load(f)["device"] == result["device"]


# -- chip_smoke scenario + plain-reference checkers --------------------

@pytest.fixture(scope="module")
def smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _placed(smoke, fleet, job, nodes):
    return {job["id"]: [
        {"id": f"a{i}", "name": f"{job['id']}.{job['group']}[{i}]",
         "node_id": n["id"], "job_id": job["id"],
         "task_group": job["group"], "desired_status": "run",
         "client_status": "pending"} for i, n in enumerate(nodes)]}


def test_smoke_scenario_is_seeded_and_full_width(smoke):
    a, b = smoke.build_fleet(7, 64), smoke.build_fleet(7, 64)
    assert a == b and a != smoke.build_fleet(8, 64)
    assert [n["id"] for n in a] == sorted(n["id"] for n in a)
    assert {n["datacenter"] for n in a} == set(smoke.DCS)
    assert len({n["meta"]["rack"] for n in a}) == smoke.N_RACKS
    jobs = smoke.build_jobs("w1")
    assert jobs[0]["count"] == 10_000 and len(jobs) == 17
    assert (smoke.N_NODES, smoke.ALLOCS_PER_NODE) == (10_000, 40)


def test_smoke_checkers_accept_a_good_placement(smoke):
    fleet = smoke.build_fleet(3, 64)
    job = smoke.build_jobs("t", 600, 1)[1]
    job["count"] = 4
    ok = [n for n in fleet if not smoke.node_feasible(n, job)]
    by_dc = {dc: [n for n in ok if n["datacenter"] == dc]
             for dc in smoke.DCS}
    nodes = [by_dc["dc1"][0], by_dc["dc1"][1], by_dc["dc2"][0],
             by_dc["dc3"][0]]
    allocs = _placed(smoke, fleet, job, nodes)
    evals = {job["id"]: {"status": "complete"}}
    used = smoke.node_usage(fleet, 40, [job], allocs)
    assert smoke.check_committed([job], allocs, evals) == []
    assert smoke.check_capacity(fleet, used) == []
    assert smoke.check_feasible(fleet, [job], allocs) == []
    assert smoke.check_spread(fleet, [job], allocs) == []


def test_smoke_checkers_reject_overcommit_and_infeasible(smoke):
    fleet = smoke.build_fleet(3, 64)
    job = smoke.build_jobs("t", 600, 1)[1]
    ok = [n for n in fleet if not smoke.node_feasible(n, job)]
    bad_rack = next(n for n in fleet if n["meta"]["rack"] == "r12")
    # ten 500 MHz instances on one node holding a 2000 MHz backlog
    over = _placed(smoke, fleet, job, [ok[0]] * 10)
    used = smoke.node_usage(fleet, 40, [job], over)
    assert any("cpu" in p for p in smoke.check_capacity(fleet, used))
    # a node whose rack fails the regexp constraint
    infeasible = _placed(smoke, fleet, job, ok[:9] + [bad_rack])
    problems = smoke.check_feasible(fleet, [job], infeasible)
    assert len(problems) == 1 and "regexp" in problems[0]
    # a missing instance, a duplicated name, a failed eval
    short = _placed(smoke, fleet, job, ok[:9])
    assert smoke.check_committed([job], short,
                                 {job["id"]: {"status": "complete"}})
    dup = _placed(smoke, fleet, job, ok[:10])
    dup[job["id"]][1]["name"] = dup[job["id"]][0]["name"]
    assert smoke.check_committed([job], dup,
                                 {job["id"]: {"status": "complete"}})
    fine = _placed(smoke, fleet, job, ok[:10])
    assert smoke.check_committed(
        [job], fine, {job["id"]: {"status": "complete",
                                  "failed_tg_allocs": {"web": {}}}})
    # every instance in one datacenter breaks the 40% target
    dc1 = [n for n in ok if n["datacenter"] == "dc1"][:10]
    assert smoke.check_spread(fleet, [job], _placed(smoke, fleet, job, dc1))


def test_smoke_tie_classification(smoke):
    """Two nodes in the same state are a tie; a node the reference
    scores visibly lower is a real disagreement."""
    fleet = smoke.build_fleet(3, 64)
    job = smoke.build_jobs("t", 600, 1)[0]
    used = smoke.node_usage(fleet, 40, [], {})
    want = smoke.PlainScorer(fleet, job, used).greedy(3)
    swapped = [want[1], want[0], want[2]]
    row = smoke.classify_sequences(
        "x", swapped, want, lambda: smoke.PlainScorer(fleet, job, used))
    assert row["step_mismatches"] == 2 and row["tie"] is True
    assert row["multiset_mismatches"] == 0
    used[fleet[5]["id"]]["cpu"] += 1000     # fuller: binpack prefers it
    want = smoke.PlainScorer(fleet, job, used).greedy(1)
    assert want == [fleet[5]["name"]]
    row = smoke.classify_sequences(
        "x", [fleet[6]["name"]], want,
        lambda: smoke.PlainScorer(fleet, job, used))
    assert row["tie"] is False and row["plain_score_delta"] > 1e-3


def test_smoke_tells_a_retry_bucket_from_a_new_shape_family(smoke):
    base = (16384, ("cpu", False), ("k_steps", 16), ("s_live", 1))
    before = {"scan": {base}, "scatter_set": {((16384, 4), 16)}}
    retry = (16384, ("cpu", False), ("k_steps", 4), ("s_live", 1))
    rows = ((16384, 4), 64)
    after = {"scan": {base, retry}, "scatter_set": {((16384, 4), 16), rows}}
    assert smoke.new_shape_families(before, after) == []
    flipped = (16384, ("cpu", True), ("k_steps", 16), ("s_live", 1))
    after["scan"].add(flipped)
    after["kway"] = {(16384, ("max_steps", 128), ("w", 128))}
    got = smoke.new_shape_families(before, after)
    assert len(got) == 2 and any("('cpu', True)" in g for g in got)


# -- program repairs the chip run forced -------------------------------

def test_heartbeat_timers_use_one_thread_for_the_fleet(monkeypatch):
    from nomad_tpu.server.heartbeat import HeartbeatTimers
    expired = []
    hb = HeartbeatTimers(expired.append)
    # the threads THIS thread starts (arming a timer runs here), not the
    # process's count: in a worker that ran other files first, their
    # daemon threads still start and end meanwhile (active_count read
    # 20 for 21 and 17 for 16 in two runs)
    me, started, start = threading.current_thread(), [], threading.Thread.start

    def counting_start(t):
        if threading.current_thread() is me:
            started.append(t)
        return start(t)

    monkeypatch.setattr(threading.Thread, "start", counting_start)
    try:
        for i in range(10_000):
            hb.reset(f"far-{i}", 3600.0)
        hb.reset("kept", 0.15)
        hb.reset("gone", 0.15)
        assert started == [hb._thread] and hb._thread.is_alive()
        assert hb.armed() == 10_002
        time.sleep(0.05)
        hb.reset("kept", 3600.0)        # a heartbeat postpones expiry
        deadline = time.monotonic() + 5.0
        while not expired and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)
        assert expired == ["gone"]
        hb.clear()
        assert hb.armed() == 0
    finally:
        hb.stop()


def test_accelerator_fingerprint_does_not_open_the_device():
    """The device plugin runs as a child of an agent that may hold the
    chip: fingerprint and stats must not initialize a JAX backend."""
    code = (
        "from nomad_tpu.plugins.device_client import "
        "AcceleratorDevicePlugin\n"
        "p = AcceleratorDevicePlugin()\n"
        "print(len(p.fingerprint()), len(p.stats()))\n"
        "import sys\n"
        "jax = sys.modules.get('jax')\n"
        "if jax is not None:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "print('untouched')\n")
    out = _run(code)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "untouched"


def test_absorbed_device_op_failure_is_counted_not_a_stale_miss():
    import numpy as np
    from nomad_tpu.ops import device_table as dt
    from nomad_tpu.ops.select import device_stats_snapshot

    class Table:
        n = 8
        capacity = np.ones((8, 4), np.float32)
        base_used = np.zeros((8, 4), np.float32)
        free_ports = np.ones(8, np.float32)
        device_version = 1

    mirror = dt.DeviceNodeTable()
    mirror.version = 1
    assert mirror.arrays_for(Table) is not None
    before = dict(dt.DEVICE_OP_FAILURES)
    stale0 = mirror.stats["stale_misses"]
    broken = Table()
    broken.base_used = None             # the scatter will raise
    try:
        mirror.note_delta(broken, [0, 1])
        assert dt.DEVICE_OP_FAILURES.get("device_table.scatter", 0) == \
            before.get("device_table.scatter", 0) + 1
        assert mirror.stats["stale_misses"] == stale0
        assert device_stats_snapshot()["device_op_failures"][
            "device_table.scatter"] >= 1
    finally:
        dt.DEVICE_OP_FAILURES.clear()
        dt.DEVICE_OP_FAILURES.update(before)
