"""The WAL record's encoder (ISSUE 26): `utils/codec.to_wire` works from
a per-type plan instead of reflecting on every object, and within one
`encode_payload` call an object reached twice is walked once. Held
here to the walk it replaced, which stays below as the oracle: the
frames of a live server are byte for byte what that walk and the same
`packb` call give, a restart from them restores the live store, every
model class encodes as before, and the sharing stays inside one call.

Since ISSUE 35 a plan entry (`plan_results`, and each member of a
`plan_group_results`) writes its three lists of allocations as one
record of constants, a table of shared objects and rows
(`utils/codec.rows_to_wire`). For those two kinds the oracle is what
the old walk's frame DECODES to, field by field; every other kind
keeps its frame byte for byte. A WAL of old frames, and one holding
both forms, restore the live store.
Counts and bytes only; nothing here reads a clock."""

import dataclasses
import enum
import importlib
import json
import os
import pkgutil
import struct
import time
import types
import typing

import msgpack
import pytest

import nomad_tpu.models
from nomad_tpu import mock
from nomad_tpu.models import Allocation, Evaluation
from nomad_tpu.models.alloc import (AllocDeploymentStatus, AllocMetric,
                                    DesiredTransition, NodeScoreMeta,
                                    RescheduleEvent, RescheduleTracker,
                                    TaskEvent, TaskState)
from nomad_tpu.models.deployment import Deployment, DeploymentStatusUpdate
from nomad_tpu.models.resources import (AllocatedDeviceResource,
                                        AllocatedResources,
                                        AllocatedSharedResources,
                                        AllocatedTaskResources)
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server import persistence
from nomad_tpu.server.persistence import (PLAN_ENTRIES, decode_payload,
                                          encode_payload)
from nomad_tpu.utils import stages
from nomad_tpu.utils.codec import (ShareMemo, rows_from_wire, rows_to_wire,
                                   to_wire)


# -- the oracle: the walk this PR replaced, word for word ---------------

def old_to_wire(obj):
    if isinstance(obj, bytes):
        import base64
        return {"__b64__": base64.b64encode(obj).decode("ascii")}
    if obj is None or isinstance(obj, (str, int, float, bool)):
        return obj
    if isinstance(obj, enum.Enum):
        return obj.value
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {}
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            out[f.name] = old_to_wire(v)
        return out
    if isinstance(obj, dict):
        return {k: old_to_wire(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [old_to_wire(v) for v in obj]
    return obj


def old_encode_payload(msg_type, payload):
    if msg_type == "plan_group_results":
        return {"groups": [old_encode_payload("plan_results", g)
                           for g in payload.get("groups", [])]}
    if msg_type == "ingest_batch":
        return {"entries": [old_encode_payload(e.get("kind", ""), e)
                            for e in payload.get("entries", [])]}
    out = {}
    for k, v in payload.items():
        out[k] = old_to_wire(v)
    return out


def _containers(tree):
    """id of every dict and list of a wire tree, once per occurrence."""
    out = []
    stack = [tree]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            out.append(id(x))
            stack.extend(x.values())
        elif isinstance(x, list):
            out.append(id(x))
            stack.extend(x)
    return out


def _canon(d) -> str:
    return json.dumps(d, sort_keys=True, default=str)


def _rows(table):
    """A dumped table without the order its rows came out in."""
    return sorted(map(_canon, table)) if isinstance(table, list) \
        else _canon(table)


ALLOC_LISTS = ("allocs_stopped", "allocs_placed", "allocs_preempted")
SHARED_FIELDS = ("job", "allocated_resources", "metrics")


def _plans(msg_type, payload):
    """The plan_results payloads of a plan entry, decoded or live."""
    return payload["groups"] if msg_type == "plan_group_results" \
        else [payload]


def _distinct(msg_type, payload):
    """Per member, list and shared field of a plan entry: how many
    distinct objects (None apart) the list's allocations hold there."""
    return [{(key, field): len({id(getattr(a, field))
                                for a in plan.get(key) or []
                                if getattr(a, field) is not None})
             for key in ALLOC_LISTS for field in SHARED_FIELDS}
            for plan in _plans(msg_type, payload)]


def _assert_decodes_alike(msg_type, got_tree, want_tree, where):
    """What two wire trees of one plan entry decode to is equal, field
    by field of every allocation and value by value of the rest."""
    got = _plans(msg_type, decode_payload(msg_type, got_tree))
    want = _plans(msg_type, decode_payload(msg_type, want_tree))
    assert len(got) == len(want), where
    for g, w in zip(got, want):
        assert list(g) == list(w), where
        for key in w:
            if key not in ALLOC_LISTS:
                assert g[key] == w[key], (where, key)
                continue
            assert len(g[key]) == len(w[key]), (where, key)
            for x, y in zip(g[key], w[key]):
                assert type(x) is type(y) is Allocation
                for f in dataclasses.fields(Allocation):
                    assert getattr(x, f.name) == getattr(y, f.name), \
                        (where, key, y.id, f.name)
        assert to_wire(g) == to_wire(w), where


def _old_frame(index, msg_type, ts, oracle):
    """The frame the old walk and the same packb call give."""
    return msgpack.packb({"i": index, "t": msg_type, "ts": ts, "p": oracle},
                         use_bin_type=True)


def _write_log(path, frames):
    with open(path, "wb") as f:
        for frame in frames:
            f.write(struct.pack("<I", len(frame)))
            f.write(frame)


# -- one live server, every kind of entry -------------------------------

N_NODES = 1100
FILL = 1000
CASES = ["plan_results_1000", "plan_group_results", "ingest_batch",
         "job_register", "alloc_client_update", "plan_results_rich"]


def _small_job(job_id, count=2, kind="service"):
    job = mock.batch_job() if kind == "batch" else mock.job()
    job.id = job_id
    job.name = job_id
    tg = job.task_groups[0]
    tg.count = count
    for t in tg.tasks:
        t.resources.networks = []
        t.resources.cpu = 20
        t.resources.memory_mb = 32
    tg.networks = []
    return job


def _pending_eval(job):
    return Evaluation(namespace="default", job_id=job.id, type=job.type,
                      priority=50, triggered_by="job-register",
                      status="pending")


def _flyweight_allocs(job, nodes, n, prefix):
    """n placements built the way the scheduler builds a batch: one
    AllocatedResources and one AllocMetric under all of them."""
    tg = job.task_groups[0]
    res = AllocatedResources(
        tasks={tg.tasks[0].name: AllocatedTaskResources()},
        shared=AllocatedSharedResources(disk_mb=10))
    metric = AllocMetric(
        nodes_evaluated=len(nodes), nodes_available={"dc1": len(nodes)},
        score_meta_data=[NodeScoreMeta(node_id=nodes[0].id,
                                       scores={"binpack": 0.5},
                                       norm_score=0.5)])
    out = []
    for i in range(n):
        node = nodes[i % len(nodes)]
        out.append(Allocation(
            id=f"{prefix}-{i:04d}", eval_id=f"{prefix}-eval",
            name=f"{job.id}.{tg.name}[{i}]", node_id=node.id,
            node_name=node.name, job_id=job.id, task_group=tg.name,
            allocated_resources=res, metrics=metric))
    return out


@pytest.fixture(scope="module")
def wal(tmp_path_factory):
    """Drives a served 1,000-placement batch eval and one hand-made
    entry of each other kind through a live Server with a data dir,
    recording beside every WAL append the oracle's tree of the same
    payload at that moment; then restarts from the directory."""
    data_dir = str(tmp_path_factory.mktemp("walenc"))
    records = []    # (index, msg_type, oracle tree, (objects, shared, rows))
    aliasing = {}   # index of a plan entry -> _distinct() of its payload
    real_append = persistence.RaftLog.append

    def recording_append(self, index, msg_type, payload, sync=False):
        oracle = old_encode_payload(msg_type, payload)
        if msg_type in PLAN_ENTRIES:
            aliasing[index] = _distinct(msg_type, payload)
        counts = real_append(self, index, msg_type, payload, sync)
        records.append((index, msg_type, oracle, counts))
        return counts

    cfg = dict(heartbeat_ttl_s=3600.0, data_dir=data_dir,
               snapshot_every=10**6)
    persistence.RaftLog.append = recording_append
    reports = []
    labels = {}
    srv = Server(ServerConfig(num_schedulers=2, **cfg))
    srv.start()
    prev = (stages._trace_hook, stages._trace_on)

    def hook(stage, seconds, attrs):
        if stage == "wal_encode":
            reports.append(dict(attrs))
        if prev[0] is not None:
            prev[0](stage, seconds, attrs)

    stages.set_trace_hook(hook)     # after the Server: it re-arms its own
    try:
        nodes = []
        for i in range(N_NODES):
            node = mock.node()
            node.name = f"walenc-n{i}"
            node.compute_class()
            srv.register_node(node)
            nodes.append(node)
        # the real scheduler path: one 1,000-count batch job
        fill = _small_job("walenc-fill", FILL, "batch")
        srv.register_job(fill)
        deadline = time.time() + 120
        while time.time() < deadline and len(
                srv.store.allocs_by_job("default", fill.id)) < FILL:
            time.sleep(0.02)
        placed = srv.store.allocs_by_job("default", fill.id)
        assert len(placed) == FILL
        # let the eval's own status write land, then hold the workers:
        # the hand-made entries below enqueue evals nobody should run
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                t == "eval_update" for _i, t, _o, _c in records):
            time.sleep(0.02)
        for w in srv.workers:
            w.set_pause(True)
        time.sleep(0.3)

        job = _small_job("walenc-svc", 4)
        labels["job_register"] = srv.raft_apply(
            "job_register", dict(job=job, evals=[_pending_eval(job)]))

        state = TaskState(state="running", started_at=12.5, events=[
            TaskEvent(type="Started", time=3, details={"k": "v"})])
        ups = []
        for a in placed[:3]:
            up = a.copy()
            up.client_status = "running"
            up.task_states = {"worker": state}      # one state, thrice
            ups.append(up)
        labels["alloc_client_update"] = srv.raft_apply(
            "alloc_client_update", dict(allocs=ups, evals=[]))

        g1 = _flyweight_allocs(job, nodes, 3, "walenc-g1")
        g2 = _flyweight_allocs(job, nodes[5:], 2, "walenc-g2")
        g2[0].metrics = g1[0].metrics       # shared ACROSS two members
        stop = placed[10].copy()
        stop.desired_status = "stop"
        stop.desired_description = "alloc not needed"
        labels["plan_group_results"] = srv.raft_apply(
            "plan_group_results", dict(groups=[
                dict(allocs_stopped=[], allocs_placed=g1,
                     allocs_preempted=[], evals=[]),
                dict(allocs_stopped=[stop], allocs_placed=g2,
                     allocs_preempted=[], deployment=None,
                     deployment_updates=[], evals=[])]))

        jobs = [_small_job(f"walenc-ing-{k}") for k in range(2)]
        done = placed[20].copy()
        done.client_status = "complete"
        labels["ingest_batch"] = srv.raft_apply("ingest_batch", dict(entries=[
            dict(kind="job_register", job=jobs[0],
                 evals=[_pending_eval(jobs[0])]),
            dict(kind="job_register", job=jobs[1],
                 evals=[_pending_eval(jobs[1])]),
            dict(kind="alloc_client_update", allocs=[done], evals=[]),
            dict(kind="alloc_desired_transition", alloc_ids=[placed[21].id],
                 transition=DesiredTransition(migrate=True), evals=[])]))

        # stops, preemptions, a deployment, canaries, ports, devices
        rich_job = _small_job("walenc-rich", 2)
        srv.raft_apply("job_register", dict(job=rich_job, evals=[]))
        dep = Deployment.from_job(rich_job)
        stops = []
        for a in placed[30:33]:
            s = a.copy()
            s.desired_status = "stop"
            s.desired_description = "alloc is being updated"
            stops.append(s)
        canaries = []
        for i in range(2):
            c = mock.alloc()        # ports: reserved + dynamic
            c.id = f"walenc-canary-{i}"
            c.job = rich_job        # one Job under both
            c.job_id = rich_job.id
            c.node_id = nodes[40 + i].id
            c.name = f"{rich_job.id}.web[{i}]"
            c.deployment_id = dep.id
            c.deployment_status = AllocDeploymentStatus(canary=True)
            c.allocated_resources.tasks["web"].devices = [
                AllocatedDeviceResource(vendor="nvidia", type="gpu",
                                        name="1080ti",
                                        device_ids=[f"gpu-{i}"])]
            c.preempted_allocations = [placed[50 + i].id]
            c.reschedule_tracker = RescheduleTracker(events=[
                RescheduleEvent(reschedule_time=5.0,
                                prev_alloc_id=placed[30].id,
                                prev_node_id=placed[30].node_id,
                                delay_s=30.0)])
            c.metrics = AllocMetric(nodes_evaluated=7,
                                    class_filtered={"c1": 2},
                                    quota_exhausted=["q"])
            canaries.append(c)
        preempted = []
        for i, a in enumerate(placed[50:52]):
            p = a.copy()
            p.desired_status = "evict"
            p.desired_description = "Preempted by alloc"
            p.preempted_by_allocation = canaries[i].id
            preempted.append(p)
        labels["plan_results_rich"] = srv.raft_apply("plan_results", dict(
            allocs_stopped=stops, allocs_placed=canaries,
            allocs_preempted=preempted, deployment=dep,
            deployment_updates=[DeploymentStatusUpdate(
                deployment_id=dep.id, status="running",
                status_description="deployment is running")],
            evals=[_pending_eval(rich_job)]))
    finally:
        srv.shutdown()
        stages.set_trace_hook(*prev)
        persistence.RaftLog.append = real_append
    live = srv.store.dump()
    stats = dict(srv.persistence.stats)

    labels["plan_results_1000"] = next(
        i for i, t, o, _c in records
        if t == "plan_results" and len(o["allocs_placed"]) == FILL)
    frames = {}
    with open(os.path.join(data_dir, "raft.log"), "rb") as f:
        while True:
            header = f.read(4)
            if len(header) < 4:
                break
            frame = f.read(struct.unpack("<I", header)[0])
            frames[msgpack.unpackb(frame, raw=False)["i"]] = frame
    assert not os.path.exists(os.path.join(data_dir, "state.snap"))

    again = Server(ServerConfig(num_schedulers=0, **cfg))
    try:
        replayed = again.store.dump()
    finally:
        again.shutdown()
    return {"records": {i: (t, o, c) for i, t, o, c in records},
            "frames": frames, "labels": labels, "live": live,
            "replayed": replayed, "stats": stats, "reports": reports,
            "aliasing": aliasing, "cfg": cfg}


# -- (a) the frame: byte for byte, but a plan's, which decodes alike -----

@pytest.mark.parametrize("case", CASES + ["every_entry"])
def test_frame_equals_the_old_walk_and_packb(wal, case):
    if case == "every_entry":
        indexes = sorted(wal["records"])
        assert len(indexes) > N_NODES and indexes == sorted(wal["frames"])
        kinds = {wal["records"][i][0] for i in indexes}
        assert {"node_register", "eval_update"} <= kinds
    else:
        indexes = [wal["labels"][case]]
    for index in indexes:
        msg_type, oracle, _counts = wal["records"][index]
        frame = wal["frames"][index]
        entry = msgpack.unpackb(frame, raw=False)
        if msg_type in PLAN_ENTRIES:
            assert (entry["i"], entry["t"]) == (index, msg_type)
            _assert_decodes_alike(msg_type, entry["p"], oracle,
                                  (case, index))
        else:
            assert frame == _old_frame(index, msg_type, entry["ts"],
                                       oracle), (case, index, msg_type)


# -- (b) a restart from those frames restores the live store ------------

def _touched(tree, out=None):
    """ids of the allocations, jobs, evals and deployments an entry's
    wire tree names."""
    out = out if out is not None else set()
    if isinstance(tree, dict):
        for key in ("id", "deployment_id"):
            if isinstance(tree.get(key), str) and tree[key]:
                out.add(tree[key])
        for v in tree.values():
            _touched(v, out)
    elif isinstance(tree, list):
        for v in tree:
            if isinstance(v, str):
                out.add(v)
            else:
                _touched(v, out)
    return out


@pytest.mark.parametrize("case", CASES + ["whole_store"])
def test_replay_of_the_new_frames_restores_the_live_store(wal, case):
    live, replayed = wal["live"]["tables"], wal["replayed"]["tables"]
    if case == "whole_store":
        assert wal["replayed"]["indexes"] == wal["live"]["indexes"]
        assert sorted(live) == sorted(replayed)
        for table in live:
            assert _rows(replayed[table]) == _rows(live[table]), table
        return
    index = wal["labels"][case]
    msg_type, oracle, _counts = wal["records"][index]
    # what a restart decodes from the new frame is what the oracle's
    # tree decodes to
    entry = msgpack.unpackb(wal["frames"][index], raw=False)
    assert (entry["p"] == oracle) == (msg_type not in PLAN_ENTRIES)
    assert to_wire(decode_payload(msg_type, entry["p"])) == \
        to_wire(decode_payload(msg_type, oracle))
    ids = _touched(oracle)
    seen = 0
    for table in ("allocs", "jobs", "evals", "deployments"):
        rows = {r["id"]: r for r in live[table] if r["id"] in ids}
        back = {r["id"]: r for r in replayed[table] if r["id"] in ids}
        assert sorted(back) == sorted(rows), (case, table)
        for key, row in rows.items():
            assert _canon(back[key]) == _canon(row), (case, table, key)
        seen += len(rows)
    assert seen >= {"plan_results_1000": FILL, "plan_group_results": 6,
                    "ingest_batch": 6, "job_register": 2,
                    "alloc_client_update": 3,
                    "plan_results_rich": 9}[case]


# -- (b') a WAL of old frames, and one of both forms, restore it too -----

@pytest.mark.parametrize("forms", ["old_form", "both_forms"])
def test_a_wal_written_before_the_rows_restores_the_live_store(
        wal, tmp_path, forms):
    """The oracle's frames are what the parent commit wrote (test (a) of
    ISSUE 26 held them to it byte for byte): a directory of them, or of
    them and the new ones interleaved, replays into the live store."""
    frames, old, new = [], 0, 0
    for index in sorted(wal["frames"]):
        msg_type, oracle, _counts = wal["records"][index]
        frame = wal["frames"][index]
        if msg_type in PLAN_ENTRIES and (forms == "old_form" or old <= new):
            ts = msgpack.unpackb(frame, raw=False)["ts"]
            frame = _old_frame(index, msg_type, ts, oracle)
            assert frame != wal["frames"][index]
            old += 1
        elif msg_type in PLAN_ENTRIES:
            new += 1
        frames.append(frame)
    assert old >= 2 and (new >= 1) == (forms == "both_forms")
    _write_log(str(tmp_path / "raft.log"), frames)
    again = Server(ServerConfig(num_schedulers=0, **dict(
        wal["cfg"], data_dir=str(tmp_path))))
    try:
        replayed = again.store.dump()
    finally:
        again.shutdown()
    live = wal["live"]
    assert replayed["indexes"] == live["indexes"]
    assert sorted(replayed["tables"]) == sorted(live["tables"])
    for table in live["tables"]:
        assert _rows(replayed["tables"][table]) == \
            _rows(live["tables"][table]), table


@pytest.mark.parametrize("form", ["new_form", "old_form"])
def test_a_torn_final_frame_is_dropped(wal, tmp_path, form):
    index = wal["labels"]["plan_results_1000"]
    msg_type, oracle, _counts = wal["records"][index]
    last = wal["frames"][index]
    if form == "old_form":
        last = _old_frame(index, msg_type, 0.0, oracle)
    earlier = [i for i in sorted(wal["frames"]) if i < index]
    before = [wal["frames"][i] for i in earlier]
    path = str(tmp_path / "raft.log")
    _write_log(path, before + [last])
    whole = persistence.RaftLog(path).replay()
    assert [e[0] for e in whole] == earlier + [index]
    assert len(whole[-1][2]["allocs_placed"]) == FILL
    size = os.path.getsize(path)
    for cut in (1, len(last) // 2, len(last) + 3):
        with open(path, "r+b") as f:
            f.truncate(size - cut)
        log = persistence.RaftLog(path)
        assert [e[0] for e in log.replay()] == earlier
        assert log._good_offset == size - len(last) - 4
        _write_log(path, before + [last])


# -- (b") the decoded plan aliases where the payload did ------------------

@pytest.mark.parametrize("case", ["plan_results_1000", "plan_group_results",
                                  "plan_results_rich"])
def test_decoded_allocations_share_what_the_payload_shared(wal, case):
    index = wal["labels"][case]
    msg_type, oracle, _counts = wal["records"][index]
    entry = msgpack.unpackb(wal["frames"][index], raw=False)
    was = wal["aliasing"][index]
    assert _distinct(msg_type, decode_payload(msg_type, entry["p"])) == was
    if case == "plan_results_1000":
        # ONE Job and ONE AllocatedResources under the 1,000, a few
        # metrics; the old frame decoded to 1,000 of each
        assert was[0]["allocs_placed", "job"] == 1
        assert was[0]["allocs_placed", "allocated_resources"] == 1
        assert 1 <= was[0]["allocs_placed", "metrics"] < 20
        old = _distinct(msg_type, decode_payload(msg_type, oracle))
        assert old[0]["allocs_placed", "job"] == FILL
    elif case == "plan_group_results":
        # the second member's first placement holds the first's metric
        assert [w["allocs_placed", "metrics"] for w in was] == [1, 2]
    else:
        # two canaries: one Job, a metric and a resource row each
        assert was[0]["allocs_placed", "job"] == 1
        assert was[0]["allocs_placed", "metrics"] == 2
        assert was[0]["allocs_placed", "allocated_resources"] == 2


def _varied(n):
    """n allocations no two of which agree in any field."""
    out = []
    for i in range(n):
        a = mock.alloc()
        a.namespace, a.eval_id, a.name = f"ns{i}", f"e{i}", f"j.web[{i}]"
        a.node_id, a.node_name = f"node-{i}", f"n{i}"
        a.job_id, a.task_group = f"j{i}", f"g{i}"
        a.job = _small_job(f"varied-{i}")
        a.metrics = AllocMetric(nodes_evaluated=i)
        a.desired_status = ("run", "stop", "evict")[i]
        a.desired_description = f"d{i}"
        a.desired_transition = DesiredTransition(migrate=bool(i % 2),
                                                 reschedule=i == 2)
        a.client_status = ("pending", "running", "failed")[i]
        a.client_description = f"c{i}"
        a.task_states = {f"t{i}": TaskState(state="running")}
        a.deployment_id = f"dep{i}"
        a.deployment_status = AllocDeploymentStatus(canary=bool(i % 2),
                                                    timestamp=float(i))
        a.reschedule_tracker = RescheduleTracker(events=[
            RescheduleEvent(reschedule_time=float(i))])
        a.follow_up_eval_id, a.previous_allocation = f"f{i}", f"p{i}"
        a.next_allocation, a.preempted_allocations = f"x{i}", [f"v{i}"]
        a.preempted_by_allocation = f"by{i}"
        a.create_index, a.modify_index = 10 + i, 20 + i
        a.alloc_modify_index, a.create_time = 30 + i, 40 + i
        a.modify_time = 50 + i
        out.append(a)
    return out


@pytest.mark.parametrize("shape", ["empty", "one", "every_field_varies",
                                   "atoms_alike_objects_not",
                                   "none_among_objects"])
def test_a_list_round_trips_through_the_record(shape):
    if shape == "empty":
        allocs = []
    elif shape == "one":
        allocs = [mock.alloc()]
    elif shape == "every_field_varies":
        allocs = _varied(3)
    elif shape == "atoms_alike_objects_not":
        # equal but distinct objects stay distinct; equal atoms of
        # another type stay apart (1 == True)
        allocs = [mock.alloc() for _ in range(3)]
        for a, v in zip(allocs, (1, True, 1)):
            a.create_index = v
    else:
        allocs = [mock.alloc() for _ in range(4)]
        allocs[0].metrics = allocs[2].metrics = AllocMetric(
            nodes_evaluated=3)
        allocs[1].metrics = allocs[3].metrics = None
        allocs[2].job = None
    record = msgpack.unpackb(msgpack.packb(rows_to_wire(allocs),
                                           use_bin_type=True), raw=False)
    assert record["rows"] == len(allocs)
    fields = {f.name for f in dataclasses.fields(Allocation)}
    written = [k for part in ("consts", "cols", "refs")
               for k in record[part]]
    assert sorted(written) == (sorted(fields) if allocs else [])
    back = rows_from_wire(Allocation, record)
    assert back == allocs
    assert to_wire(back) == old_to_wire(allocs)
    # decoded as the list form decodes: by the field's type
    assert to_wire(back) == to_wire(decode_payload("alloc_client_update", {
        "allocs": old_to_wire(allocs)})["allocs"])
    plan = dict(allocs_stopped=[], allocs_placed=allocs,
                allocs_preempted=[])
    assert _distinct("plan_results", dict(plan, allocs_placed=back)) == \
        _distinct("plan_results", plan)
    if shape == "one":
        assert not record["cols"] and not record["refs"]
    elif shape == "every_field_varies":
        assert not record["consts"]
    elif shape == "atoms_alike_objects_not":
        assert [type(v) for v in record["cols"]["create_index"]] == \
            [int, bool, int]
        assert len(set(record["refs"]["desired_transition"])) == 3
    elif shape == "none_among_objects":
        assert record["refs"]["metrics"][1] is None
        assert record["refs"]["metrics"][0] == record["refs"]["metrics"][2]


def test_a_list_of_anything_else_keeps_the_list_form():
    """The record is for a plan's lists of Allocations: stubs in one, or
    the same list under another kind of entry, are walked as before."""
    allocs = [mock.alloc(), mock.alloc()]
    enc = encode_payload("plan_results", dict(
        allocs_placed=[to_wire(allocs[0])], allocs_stopped=allocs))
    assert isinstance(enc["allocs_placed"], list)
    assert enc["allocs_stopped"]["rows"] == 2
    for kind in ("alloc_client_update", "noop"):
        payload = dict(allocs=allocs, allocs_placed=allocs)
        assert encode_payload(kind, payload) == \
            old_encode_payload(kind, payload)


def test_the_served_plan_is_rows_and_under_300_bytes_a_placement(wal):
    index = wal["labels"]["plan_results_1000"]
    _t, oracle, (_objects, _shared, rows) = wal["records"][index]
    assert rows == FILL
    frame = wal["frames"][index]
    assert len(frame) < 300 * FILL
    assert len(frame) * 8 < len(_old_frame(index, "plan_results", 0.0,
                                           oracle))
    placed = msgpack.unpackb(frame, raw=False)["p"]["allocs_placed"]
    assert placed["rows"] == FILL
    # what differs between two placements of one plan, and no more
    assert {"id", "name", "node_id", "node_name"} <= set(placed["cols"])
    assert "job" in placed["consts"] and len(placed["consts"]) >= 20
    report = next(r for r in wal["reports"] if r["rows"] == FILL)
    assert report["bytes"] == len(frame)
    assert report["consts"] >= 20 and report["table"] < 50


# -- (c) to_wire is what it was, for every model class ------------------

def _model_classes():
    out = []
    for info in pkgutil.iter_modules(nomad_tpu.models.__path__):
        mod = importlib.import_module(f"nomad_tpu.models.{info.name}")
        for name, cls in vars(mod).items():
            if isinstance(cls, type) and dataclasses.is_dataclass(cls) \
                    and cls.__module__ == mod.__name__:
                out.append(cls)
    return sorted(out, key=lambda c: (c.__module__, c.__name__))


MODEL_CLASSES = _model_classes()


def _sample(hint, depth):
    """A value of the hinted type with every field filled, dataclasses
    nested `depth` deep (below that, their defaults)."""
    origin = typing.get_origin(hint)
    if origin in (typing.Union, types.UnionType):
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        return _sample(args[0], depth) if args else None
    if hint is typing.Any:
        return {"any": [1, "two", None, 3.5, {"deep": (4, 5)}]}
    if hint is str:
        return "s"
    if hint is bool:
        return True
    if hint is int:
        return 7
    if hint is float:
        return 1.5
    if hint is bytes:
        return b"\x00\xffraw"
    if isinstance(hint, type) and issubclass(hint, enum.Enum):
        return list(hint)[0]
    if isinstance(hint, type) and dataclasses.is_dataclass(hint):
        return _instance(hint, depth - 1)
    args = typing.get_args(hint)
    if origin in (list, set, frozenset, tuple) or hint in (list, tuple):
        elem = _sample(args[0], depth) if args else "e"
        if origin in (set, frozenset):
            return (origin or set)([elem]) if isinstance(
                elem, (str, int, float)) else (origin or set)()
        seq = [elem, _sample(args[0], depth) if args else 2]
        return tuple(seq) if origin is tuple or hint is tuple else seq
    if origin is dict or hint is dict:
        return {"k": _sample(args[1], depth) if len(args) == 2 else 1}
    return None


def _instance(cls, depth=3):
    if depth <= 0:
        return cls()
    hints = typing.get_type_hints(cls)
    obj = cls()
    for f in dataclasses.fields(cls):
        setattr(obj, f.name, _sample(hints.get(f.name, typing.Any), depth))
    return obj


@pytest.mark.parametrize("cls", MODEL_CLASSES,
                         ids=lambda c: f"{c.__module__.split('.')[-1]}."
                                       f"{c.__name__}")
def test_to_wire_is_unchanged_for_every_model_class(cls):
    for obj in (cls(), _instance(cls)):
        want = old_to_wire(obj)
        assert to_wire(obj) == want
        assert msgpack.packb(to_wire(obj), use_bin_type=True) == \
            msgpack.packb(want, use_bin_type=True)      # key order too
        assert to_wire(obj, ShareMemo()) == want


class _Color(enum.Enum):
    RED = "red"


class _Level(int, enum.Enum):
    HIGH = 3


class _Word(str):
    pass


class _Bag(dict):
    pass


@pytest.mark.parametrize("value", [
    None, True, 3, 2.5, "s", b"\x00\x01", bytearray(b"ab"), _Color.RED,
    _Level.HIGH, _Word("w"), _Bag(a=_Color.RED), (1, (2, [3])),
    {"s": {1, }}, frozenset(["x"]), Allocation, object, 1 + 2j,
    [mock.alloc(), {"n": mock.node()}], {"t": (mock.job(), b"raw")},
], ids=lambda v: type(v).__name__)
def test_to_wire_is_unchanged_for_the_odd_values(value):
    want = old_to_wire(value)
    got = to_wire(value)
    assert got == want and type(got) is type(want)
    assert to_wire(value, ShareMemo()) == want


# -- (d) the sharing stays inside one encode_payload call ----------------

def _shared_plan():
    job = _small_job("walenc-share")
    nodes = [mock.node() for _ in range(3)]
    return dict(allocs_stopped=[], allocs_preempted=[],
                allocs_placed=_flyweight_allocs(job, nodes, 4, "share"))


def _holds_what_it_indexes():
    """Values that exist only while they are walked: were the memo to
    key a dead object's id, the next one made at that address would
    come back as the first one's wire form."""
    class Fresh(dict):
        def items(self):
            return ((k, NodeScoreMeta(node_id=k)) for k in self.keys())
    fresh = Fresh.fromkeys(f"n{i}" for i in range(50))
    enc = encode_payload("noop", {"fresh": fresh})
    assert [v["node_id"] for v in enc["fresh"].values()] == list(fresh)


def _within_one_call():
    plan = _shared_plan()
    placed = encode_payload("plan_results", plan)["allocs_placed"]
    assert placed["rows"] == 4
    # one AllocatedResources and one AllocMetric under the four: each
    # written once for the list
    assert {"allocated_resources", "metrics"} <= set(placed["consts"])
    ingest = encode_payload("alloc_client_update", dict(
        allocs=plan["allocs_placed"], evals=[]))
    a, b = ingest["allocs"][0], ingest["allocs"][1]
    assert a is not b
    assert a["allocated_resources"] is b["allocated_resources"]
    assert a["metrics"] is b["metrics"]


def _never_across_calls():
    plan = _shared_plan()
    first = encode_payload("plan_results", plan)
    second = encode_payload("plan_results", plan)
    assert first == second
    assert not set(_containers(first)) & set(_containers(second))
    group = dict(groups=[plan, plan])
    assert not set(_containers(encode_payload("plan_group_results",
                                              group))) \
        & set(_containers(first))


def _across_the_members_of_one_entry():
    plan = _shared_plan()
    enc = encode_payload("plan_group_results", dict(groups=[plan, plan]))
    first, second = (g["allocs_placed"] for g in enc["groups"])
    assert first is not second and first["consts"] is not second["consts"]
    assert first["consts"]["metrics"] is second["consts"]["metrics"]
    ingest = encode_payload("ingest_batch", dict(entries=[
        dict(kind="alloc_client_update", evals=[],
             allocs=plan["allocs_placed"][:1]),
        dict(kind="alloc_client_update", evals=[],
             allocs=plan["allocs_placed"][:2])]))
    assert ingest["entries"][0]["allocs"][0] is \
        ingest["entries"][1]["allocs"][0]


def _to_wire_callers_get_trees_of_their_own():
    allocs = _shared_plan()["allocs_placed"]
    assert allocs[0].metrics is allocs[1].metrics
    tree = to_wire({"allocs": allocs, "again": allocs})
    ids = _containers(tree)
    assert len(ids) == len(set(ids))    # no dict or list reached twice
    tree["allocs"][0]["metrics"]["nodes_evaluated"] = -1
    assert tree["allocs"][1]["metrics"]["nodes_evaluated"] == 3
    assert tree["again"][0]["metrics"]["nodes_evaluated"] == 3
    assert allocs[0].metrics.nodes_evaluated == 3


@pytest.mark.parametrize("check", [
    _within_one_call, _never_across_calls,
    _across_the_members_of_one_entry,
    _to_wire_callers_get_trees_of_their_own, _holds_what_it_indexes],
    ids=lambda f: f.__name__.lstrip("_"))
def test_sharing_is_confined_to_one_encode_payload_call(check):
    check()


# -- (e) the counts ------------------------------------------------------

@pytest.mark.parametrize("case", [
    "plan_results_1000", "plan_results_rich", "eval_update",
    "node_register", "stats_totals", "stage_reports"])
def test_encoder_counts(wal, case):
    records = wal["records"]
    if case == "plan_results_1000":
        _t, oracle, (objects, shared, rows) = records[wal["labels"][case]]
        # the allocations are rows; what is walked is what they hang
        # off: the job's tree, the resources, the metrics
        assert rows == FILL and 0 < objects < FILL
        # the walk it replaced reached at least this many
        assert _count_dataclass_dicts(oracle) == 3 * FILL
    elif case == "plan_results_rich":
        _t, _o, (objects, shared, rows) = records[wal["labels"][case]]
        assert rows == 7 and objects > 10
    elif case in ("eval_update", "node_register"):
        counts = [c for t, _o, c in records.values() if t == case]
        assert counts and all(c[1:] == (0, 0) for c in counts)
        assert all(c[0] >= 1 for c in counts)
    elif case == "stats_totals":
        for k, key in enumerate(("wal_objects", "wal_shared", "wal_rows")):
            assert wal["stats"][key] == sum(
                c[k] for _t, _o, c in records.values()), key
        assert wal["stats"]["wal_rows"] >= FILL + 13
    else:
        plan_entries = sorted(i for i, (t, _o, _c) in records.items()
                              if t in PLAN_ENTRIES)
        assert len(wal["reports"]) == len(plan_entries) >= 3
        for index, attrs in zip(plan_entries, wal["reports"]):
            _t, _o, (objects, shared, rows) = records[index]
            assert sorted(attrs) == ["bytes", "consts", "objects", "rows",
                                     "shared", "table"]
            assert (attrs["objects"], attrs["shared"], attrs["rows"],
                    attrs["bytes"]) == (objects, shared, rows,
                                        len(wal["frames"][index]))
            assert attrs["consts"] + attrs["table"] > 0


def _count_dataclass_dicts(tree):
    """Wire dicts of the oracle's tree that stand for an Allocation, an
    AllocatedResources or an AllocMetric: a lower bound on the
    instances the reflective walk visited."""
    marks = ("allocated_resources", "shared", "nodes_evaluated")
    n = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            n += any(m in x for m in marks)
            stack.extend(x.values())
        elif isinstance(x, list):
            stack.extend(x)
    return n
