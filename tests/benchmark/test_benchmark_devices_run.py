"""The toy GPU fleet through the harness: files that are all the tests'
own (tests/benchmark/gpu-toy.json, tests/benchmark/traffic/toy-gpu.json)
through `run.py --rehearse-cpu --nodes 640 --manifest <the tests' own>`,
on the program as it stands and with the plain reference in its place;
and the agent's side of the device keys - the node the harness loads and
reads back, and a job's asks over the wire. Counts only: nothing a CPU
run times is a device number."""
import copy
import json
import os
import re
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchrun_helper import GPU, ROOT, rehearse  # noqa: E402

from benchmark.lib import fleet as fleetlib         # noqa: E402
from benchmark.lib import traffic                   # noqa: E402

TWELVE = ["never_completed", "unplaced_evals", "lost_or_duplicated",
          "unread", "over_capacity", "infeasible", "port_conflicts",
          "spread_over_target", "stacked", "rank_gap", "device_conflicts",
          "harness_problems"]


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CFG = load("tests", "benchmark", "gpu-toy.json")
MIX = load("tests", "benchmark", "traffic", "toy-gpu.json")


@pytest.fixture(scope="module")
def program():
    return rehearse(GPU, "--trace", "0")


def test_the_program_places_device_jobs_and_is_judged_correct(program):
    line, err = program
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["compared"]) == TWELVE
    assert all(c["value"] == 0 for k, c in line["compared"].items()
               if k != "rank_gap")
    # each number compared closes standard error beside its limit
    tail = [ln for ln in err.strip().splitlines() if ln][-len(TWELVE):]
    assert [ln.split()[1] for ln in tail] == TWELVE


def test_the_probe_reads_a_device_node_back_as_made(program):
    _line, err = program
    m = re.search(r"devices on node-\d+: \[\('(Tesla [^']+)', (\d+)\)\], "
                  r"as made", err)
    assert m, err[-3000:]
    assert (m.group(1), int(m.group(2))) in {
        ("Tesla T4", 4), ("Tesla V100-SXM2-32GB", 8)}


def test_the_device_check_reads_allocations_on_device_nodes(program):
    _line, err = program
    m = re.search(r"device check: read (\d+) allocs in full on (\d+) "
                  r"device nodes", err)
    assert m, err[-3000:]
    assert int(m.group(1)) >= int(m.group(2)) == MIX["device_check_nodes"]


@pytest.mark.parametrize("control,number", [
    ("none", None), ("devblind", "infeasible"),
    ("devtwice", "device_conflicts")])
def test_controls_in_the_programs_place(control, number):
    line, err = rehearse(GPU, "--control", control)
    assert list(line["compared"]) == TWELVE
    broke = [k for k, c in line["compared"].items()
             if c["value"] > c["limit"]]
    assert broke == ([number] if number else []), err[-3000:]


# -- the agent's side -----------------------------------------------------

@pytest.fixture(scope="module")
def agent():
    """The toy's 640 nodes loaded into an agent that schedules nothing,
    behind its HTTP API."""
    from nomad_tpu.server import Server, ServerConfig
    from benchmark.lib import agent as agentlib
    from benchmark.lib import client
    cfg = copy.deepcopy(CFG)
    cfg["server"]["num_schedulers"] = 0
    fleet = fleetlib.build_fleet(cfg, 9, 640)
    a = agentlib.Agent(cfg, lambda _msg: None)
    addr = a.boot()
    try:
        a.load(fleet)
        http = client.Http(addr)
        yield types.SimpleNamespace(agent=a, fleet=fleet, http=http,
                                    Server=Server, ServerConfig=ServerConfig)
        http.close()
    finally:
        a.close()


def test_a_device_node_is_loaded_as_the_program_holds_it(agent):
    node = next(n for n in agent.fleet if n["class"] == "c2x-v100")
    held = agent.agent.srv.store.node_by_id(node["id"])
    (g,) = held.node_resources.devices
    assert (g.vendor, g.type, g.name) == ("nvidia", "gpu",
                                          "Tesla V100-SXM2-32GB")
    assert [i.id for i in g.instances] == node["devices"][0]["ids"]
    assert all(i.healthy for i in g.instances)
    assert g.attributes == node["devices"][0]["attributes"]
    plain = next(n for n in agent.fleet if "devices" not in n)
    assert agent.agent.srv.store.node_by_id(plain["id"]) \
        .node_resources.devices == []


def _runner(fleet, seed=9):
    import benchmark.run as run
    me = types.SimpleNamespace(fleet=fleet, seed=seed)
    return lambda http: run.Run._probe_devices(me, http)


def test_the_probe_agrees_and_refuses_a_node_whose_groups_differ(agent):
    _runner(agent.fleet)(agent.http)            # as made: no error
    for change in ("ids", "attributes", "model", "group"):
        other = copy.deepcopy(agent.fleet)
        for n in other:
            for g in n.get("devices", []):
                if change == "ids":
                    g["ids"][-1] = "00000000-0000-4000-8000-000000000000"
                elif change == "attributes":
                    g["attributes"]["memory"] = "1 MiB"
                elif change == "model":
                    g["model"] = "Tesla P100"
            if change == "group" and n.get("devices"):
                n["devices"].append(dict(n["devices"][0], ids=[]))
        with pytest.raises(RuntimeError, match="devices on node-"):
            _runner(other)(agent.http)


def test_a_device_job_goes_over_the_wire_and_reads_back(agent):
    job = traffic.closed_loop(MIX, 9, 3.0, ["dc1", "dc2", "dc3", "dc4"]
                              )[0].jobs[0]
    status, body = agent.http.request("PUT", "/v1/jobs", traffic.payload(
        [job]))
    assert status == 200 and body.get("EvalID"), body
    status, got = agent.http.request("GET", f"/v1/job/{job['id']}")
    assert status == 200
    asks = got["task_groups"][0]["tasks"][0]["resources"]["devices"]
    assert [(a["name"], a["count"]) for a in asks] == \
        [(a["name"], a["count"]) for a in job["devices"]]
    for sent, read in zip(job["devices"], asks):
        assert [(c["ltarget"], c["operand"], c["rtarget"])
                for c in read["constraints"]] == sent["constraints"]
        assert [(x["ltarget"], x["operand"], x["rtarget"], x["weight"])
                for x in read["affinities"]] == sent["affinities"]
    # and the program holds its own typed ask
    from nomad_tpu.models import RequestedDevice
    stored = agent.agent.srv.store.job_by_id("default", job["id"])
    (req,) = stored.task_groups[0].tasks[0].resources.devices
    assert isinstance(req, RequestedDevice)
    assert req.constraints[0].rtarget == "16 GiB"
