"""Multi-server consensus: election, replication, write forwarding,
failover, snapshot reseed (reference: nomad/server.go setupRaft,
leader.go, fsm.go Snapshot/Restore; raft-lite semantics documented in
server/raft.py).
"""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.rpc import RpcServer
from nomad_tpu.server import Server, ServerConfig


def _wait_for(pred, timeout=15.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _make_cluster(n=3, num_schedulers=1):
    servers = []
    rpcs = []
    for _ in range(n):
        s = Server(ServerConfig(num_schedulers=num_schedulers,
                                heartbeat_ttl_s=30.0))
        r = RpcServer(s, port=0)
        servers.append(s)
        rpcs.append(r)
    addrs = [r.addr for r in rpcs]
    for s, r in zip(servers, rpcs):
        s.attach_raft(r, addrs)
        r.start()
        s.start()
    return servers, rpcs, addrs


def _leaders(servers):
    return [s for s in servers if s.raft.is_leader()]


@pytest.fixture
def cluster():
    servers, rpcs, addrs = _make_cluster()
    yield servers, rpcs, addrs
    for s, r in zip(servers, rpcs):
        try:
            r.shutdown()
            s.shutdown()
        except Exception:
            pass


@pytest.mark.slow
def test_single_leader_elected(cluster):
    servers, _rpcs, _addrs = cluster
    assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10), \
        [s.raft.role for s in servers]
    leader = _leaders(servers)[0]
    # followers agree on the leader address
    assert _wait_for(lambda: all(
        s.raft.leader_addr == leader.raft.self_addr for s in servers))


@pytest.mark.slow
def test_replication_and_follower_forwarding(cluster):
    servers, _rpcs, _addrs = cluster
    assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10)
    leader = _leaders(servers)[0]
    followers = [s for s in servers if s is not leader]

    # write through the leader: replicates everywhere
    node = mock.node()
    leader.register_node(node)
    assert _wait_for(lambda: all(
        s.store.node_by_id(node.id) is not None for s in servers)), \
        "node did not replicate"

    # write through a FOLLOWER: forwarded to the leader, then replicated
    job = mock.batch_job()
    job.task_groups[0].count = 1
    followers[0].register_job(job)
    assert _wait_for(lambda: all(
        s.store.job_by_id("default", job.id) is not None
        for s in servers)), "forwarded write did not replicate"

    # the leader scheduled it (broker enabled only there)
    assert _wait_for(lambda: len(
        leader.store.allocs_by_job("default", job.id)) == 1)
    assert _wait_for(lambda: all(len(
        s.store.allocs_by_job("default", job.id)) == 1 for s in servers)), \
        "allocs did not replicate"


@pytest.mark.slow
def test_failover_elects_new_leader_and_serves_writes(cluster):
    servers, rpcs, _addrs = cluster
    assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10)
    leader = _leaders(servers)[0]
    li = servers.index(leader)

    # seed state pre-failover
    node = mock.node()
    leader.register_node(node)
    assert _wait_for(lambda: all(
        s.store.node_by_id(node.id) is not None for s in servers))

    rpcs[li].shutdown()
    leader.shutdown()
    rest = [s for s in servers if s is not leader]
    assert _wait_for(lambda: len(_leaders(rest)) == 1, timeout=10), \
        [s.raft.role for s in rest]
    new_leader = _leaders(rest)[0]
    assert new_leader is not leader

    # pre-failover state survived and new writes land
    assert new_leader.store.node_by_id(node.id) is not None
    job = mock.batch_job()
    new_leader.register_job(job)
    assert _wait_for(lambda: all(
        s.store.job_by_id("default", job.id) is not None for s in rest))


@pytest.mark.slow
def test_acked_write_survives_immediate_leader_kill(cluster):
    """Quorum commit: raft_apply acks only after a majority holds the
    entry, so a write acked just before the leader dies MUST survive
    failover (Raft §5.4; the round-2 primary/backup semantics lost
    exactly this tail)."""
    servers, rpcs, _addrs = cluster
    assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10)
    leader = _leaders(servers)[0]
    li = servers.index(leader)

    node = mock.node()
    leader.register_node(node)          # returns only after quorum ack
    rpcs[li].shutdown()                 # kill immediately after the ack
    leader.shutdown()

    rest = [s for s in servers if s is not leader]
    assert _wait_for(lambda: len(_leaders(rest)) == 1, timeout=10), \
        [s.raft.role for s in rest]
    new_leader = _leaders(rest)[0]
    assert new_leader.store.node_by_id(node.id) is not None, \
        "acked write lost on failover"


@pytest.mark.slow
def test_dead_peer_does_not_destabilize_leader(cluster):
    """Per-peer replication threads: one unreachable peer must not
    starve heartbeats to the healthy follower (which would trigger
    continual elections). Writes keep committing on the 2/3 quorum."""
    servers, rpcs, _addrs = cluster
    assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10)
    leader = _leaders(servers)[0]
    followers = [s for s in servers if s is not leader]
    dead = followers[0]
    di = servers.index(dead)
    rpcs[di].shutdown()
    dead.shutdown()

    term_before = leader.raft.term
    # writes must still ack via leader + surviving follower
    for i in range(3):
        node = mock.node()
        node.name = f"alive-{i}"
        leader.register_node(node)
        time.sleep(0.3)
    assert leader.raft.is_leader(), "leader lost leadership"
    assert leader.raft.term == term_before, \
        "election churn while a peer was down"
    assert len([n for n in followers[1].store.nodes()
                if n.name.startswith("alive-")]) == 3


def test_deposed_leader_refuses_append_and_term_pins_waits():
    """append_entry on a non-leader must raise (a deposed leader
    appending with the new term would make the real leader's entry at
    that index look already-present on a follower), and wait_for_applied
    pinned to a term must fail once the term moves — the entry may have
    been erased by a truncation in between."""
    from nomad_tpu.server.raft import FOLLOWER, LEADER, RaftNode

    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    node = RaftNode(s, "127.0.0.1:1", ["127.0.0.1:1", "127.0.0.1:2"])
    node.role = FOLLOWER
    with pytest.raises(RuntimeError, match="not the leader"):
        node.append_entry("noop", {})
    assert node.log == []

    node.role = LEADER
    node.term = 3
    index, term = node.append_entry("noop", {})
    assert term == 3
    assert index == node.base_index + 1
    node.term = 4                       # deposed + re-elected elsewhere
    with pytest.raises(RuntimeError, match="term moved"):
        node.wait_for_applied(index, term=3, timeout_s=0.5)
    s.shutdown()


def test_uncommitted_entries_are_not_applied():
    """Apply-at-commit: a leader that cannot reach a quorum appends to
    its log but must NOT run the FSM — a blocking query against its
    store can never observe the unacked write (r3 verdict item 6; the
    reference applies at commit via hashicorp/raft)."""
    from nomad_tpu.server.raft import LEADER, RaftNode

    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    # two unreachable peers: no quorum is possible
    node = RaftNode(s, "127.0.0.1:1",
                    ["127.0.0.1:1", "127.0.0.1:2", "127.0.0.1:3"])
    s.raft = node
    node.role = LEADER
    node.term = 2
    n = mock.node()
    before = len(s.store.nodes())
    with pytest.raises(RuntimeError, match="no quorum"):
        # the raft_apply path: append + wait for commit (times out)
        _apply_with_timeout(s, "node_register", dict(node=n))
    # the unacked write is invisible to reads on the partitioned leader
    assert len(s.store.nodes()) == before
    assert s.store.node_by_id(n.id) is None
    # ...but it IS in the log, awaiting commit or truncation
    assert any(e[2] == "node_register" for e in node.log)
    s.shutdown()


def _apply_with_timeout(server, msg_type, payload, timeout_s=0.5):
    index, waiter = server.raft_apply_async(msg_type, payload)
    server.raft.wait_for_applied(index, timeout_s=timeout_s)


def test_install_snapshot_pins_applied_index_above_table_indexes():
    """The r3 advisor's high finding: a reseeded follower whose
    snapshot base sits above store.latest_index() (no-op entries touch
    no table) must adopt the BASE as its applied index, or it would
    reissue already-used log indexes after winning an election."""
    from nomad_tpu.server.raft import RaftNode

    donor = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    donor.establish_leadership()
    donor.register_node(mock.node())
    snap = donor.store.snapshot().dump()
    table_max = donor.store.latest_index()

    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    node = RaftNode(s, "127.0.0.1:1", ["127.0.0.1:1", "127.0.0.1:2"])
    s.raft = node
    # the leader's applied index ran past the last table write because
    # of election no-ops
    base = table_max + 7
    node._handle_install_snapshot(
        {"term": 5, "leader": "127.0.0.1:2", "snapshot": snap,
         "base_index": base, "base_term": 5})
    assert s._raft_index == base
    assert node.base_index == base
    assert node.commit_index == base
    donor.shutdown()
    s.shutdown()


@pytest.mark.slow
def test_snapshot_reseed_of_fresh_follower():
    """A server joining with empty state catches up via snapshot
    install when the leader's log has been compacted past its needs."""
    servers, rpcs, addrs = _make_cluster(n=3)
    try:
        assert _wait_for(lambda: len(_leaders(servers)) == 1, timeout=10)
        leader = _leaders(servers)[0]
        for i in range(5):
            node = mock.node()
            node.name = f"n{i}"
            leader.register_node(node)
        # compact the leader's log to force snapshot path for laggards
        leader.raft.compact(keep=0)
        # wipe a follower's raft progress by simulating a fresh joiner:
        follower = [s for s in servers if s is not leader][0]
        follower.raft.needs_snapshot = True
        assert _wait_for(
            lambda: len(list(follower.store.nodes())) >= 5, timeout=10), \
            len(list(follower.store.nodes()))
    finally:
        for s, r in zip(servers, rpcs):
            try:
                r.shutdown()
                s.shutdown()
            except Exception:
                pass


# -- a plan's rows cross the wire (ISSUE 35) --------------------------------
# The leader encodes a plan entry's allocation lists as one record of
# constants, a table and rows (utils/codec.rows_to_wire); whoever is handed
# the entry, over AppendEntries or Raft.Forward, decodes what it encoded.

_STORE_SET = ("create_index", "modify_index", "alloc_modify_index",
              "create_time", "modify_time")


def _rows_plan(tag):
    """A node, a job and a plan of five placements that hang off ONE
    job, ONE resource row and ONE metric, with a stop beside them."""
    from nomad_tpu.models import Allocation
    from nomad_tpu.models.alloc import AllocMetric
    node = mock.node()
    job = mock.job()
    job.id = job.name = f"rows-{tag}"
    res = mock.alloc().allocated_resources
    metric = AllocMetric(nodes_evaluated=1, nodes_available={"dc1": 1})
    placed = [Allocation(
        id=f"rows-{tag}-{i}", eval_id=f"rows-{tag}-eval",
        name=f"{job.id}.web[{i}]", node_id=node.id, node_name=node.name,
        job_id=job.id, job=job, task_group="web",
        allocated_resources=res, metrics=metric) for i in range(5)]
    stop = Allocation(id=f"rows-{tag}-old", node_id=node.id, job_id=job.id,
                      task_group="web", desired_status="stop",
                      desired_description="alloc not needed")
    return node, job, dict(allocs_stopped=[stop], allocs_placed=placed,
                           allocs_preempted=[], deployment=None,
                           deployment_updates=[], evals=[])


def _assert_the_plan_landed(store, plan):
    import dataclasses
    got = [store.alloc_by_id(a.id) for a in plan["allocs_placed"]]
    assert all(g is not None for g in got)
    for g, want in zip(got, plan["allocs_placed"]):
        for f in dataclasses.fields(want):
            if f.name not in _STORE_SET:
                assert getattr(g, f.name) == getattr(want, f.name), f.name
    # one Job, one resource row, one metric under the five, as sent
    for field in ("job", "allocated_resources", "metrics"):
        assert len({id(getattr(g, field)) for g in got}) == 1, field
    assert store.alloc_by_id(
        plan["allocs_stopped"][0].id).desired_status == "stop"


@pytest.fixture
def lone_leader():
    """One server that is its own quorum, on a real RPC port."""
    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    r = RpcServer(s, port=0)
    s.attach_raft(r, [r.addr])
    r.start()
    s.start()
    assert _wait_for(s.raft.is_leader, timeout=10)
    yield s, r
    r.shutdown()
    s.shutdown()


def _follower_of(rpc_addr):
    from nomad_tpu.server.raft import RaftNode
    s = Server(ServerConfig(num_schedulers=0, heartbeat_ttl_s=30.0))
    s.raft = RaftNode(s, "127.0.0.1:2", [rpc_addr, "127.0.0.1:2"])
    s.raft.leader_addr = rpc_addr
    return s


def test_a_follower_decodes_the_plan_rows_the_leader_appended(lone_leader):
    from nomad_tpu.rpc.codec import _default_backend
    from nomad_tpu.server.persistence import decode_payload
    leader, rpc = lone_leader
    node, job, plan = _rows_plan("append")
    leader.raft_apply("node_register", dict(node=node))
    leader.raft_apply("job_register", dict(job=job, evals=[]))
    index = leader.raft_apply("plan_results", plan)
    _assert_the_plan_landed(leader.store, plan)

    entries = [e for e in leader.raft.log if e[0] <= index]
    enc = entries[-1][3]
    assert entries[-1][2] == "plan_results"
    assert enc["allocs_placed"]["rows"] == 5        # the new form
    assert "job" in enc["allocs_placed"]["consts"]
    dumps, loads = _default_backend()               # AppendEntries' wire
    sent = loads(dumps({"entries": entries}))["entries"]
    back = decode_payload("plan_results", sent[-1][3])
    assert back == plan
    assert len({id(a.job) for a in back["allocs_placed"]}) == 1

    follower = _follower_of(rpc.addr)
    try:
        res = follower.raft._handle_append_entries({
            "term": leader.raft.term, "leader": rpc.addr,
            "prev_index": leader.raft.base_index,
            "prev_term": leader.raft.base_term,
            "entries": sent, "leader_commit": index})
        assert res["success"]
        for idx, _term, msg_type, payload in list(follower.raft.log):
            follower.apply_replicated(idx, msg_type, payload)
        assert follower._raft_index == index
        _assert_the_plan_landed(follower.store, plan)
        ours = {a.id: a for a in follower.store.allocs_by_job(
            "default", job.id)}
        theirs = {a.id: a for a in leader.store.allocs_by_job(
            "default", job.id)}
        assert ours == theirs and len(ours) == 6     # the stop too
    finally:
        follower.shutdown()


def test_a_forwarded_plan_arrives_as_it_was_sent(lone_leader):
    leader, rpc = lone_leader
    node, job, plan = _rows_plan("forward")
    leader.raft_apply("node_register", dict(node=node))
    leader.raft_apply("job_register", dict(job=job, evals=[]))
    follower = _follower_of(rpc.addr)
    try:
        index = follower.raft.forward_apply("plan_results", plan)
        assert _wait_for(lambda: leader._raft_index >= index)
        _assert_the_plan_landed(leader.store, plan)
        assert follower.store.alloc_by_id(plan["allocs_placed"][0].id) \
            is None
    finally:
        follower.raft.stop()
        follower.shutdown()
