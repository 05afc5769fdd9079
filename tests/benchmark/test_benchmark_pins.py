"""What keeps the accepted cells still (PR 32): every opening of the
harness to a preempting deployment is keyed on something `prod-10k`,
`svc-10k` and the three mixes of PR 31 do not have, and without that key
the requests, the backlog, the roofline's floor, the judge's numbers and
the loader's calls are the parent's. Each value here was taken on the
parent commit (4cc2fba) before the change. Counts and digests: nothing a
CPU run times."""
import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import fleet as fleetlib         # noqa: E402
from benchmark.lib import kernelcost as kc          # noqa: E402
from benchmark.lib import reference as ref          # noqa: E402
from benchmark.lib import traffic                   # noqa: E402

DCS = ["dc1", "dc2", "dc3", "dc4"]
PORTS = (20000, 32000)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def digest_of_every_body(mix_name, seed=7, seconds=51.0):
    """(requests, sha256 over their bodies): warm-up, rehearsal and
    window of one run, in the order the generator makes them."""
    mix = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        mix_name + ".json"))
    reqs = [r for rnd in traffic.warmup_requests(mix, seed, DCS)
            for r in rnd]
    if mix["loop"] == "open":
        reqs += traffic.open_loop(mix, seed, seconds, DCS,
                                  mix["rate_per_s"])
    else:
        reqs += traffic.closed_loop(mix, seed, seconds, DCS)
    h = hashlib.sha256()
    for r in reqs:
        h.update(r.body)
    return len(reqs), h.hexdigest()


# batch-fill's list at a ceiling of 32 jobs a second; that it begins with
# the list of 12 is test_benchmark_traffic's to show
@pytest.mark.parametrize("mix,n,sha", [
    ("batch-fill", 1007,
     "641a35bf2c25684359f047449b4c29d0cb70b0be3b9a0712c6983a3adf1c2fee"),
    ("service-fill", 953,
     "6d05de98d88825f19a7087a4bc9fdbc08731781d03c71717345991e460a3d17b"),
    ("service-stream", 346,
     "026ecc179cf4c81b07ed06305a67603b36cd0eca728e10307f0f82e2c63c6ee7"),
    ("service-evict", 1874,
     "67385d0df5865d215660d9ced80841d9715494b38d078245083d3af5b2b96eac"),
])
def test_request_bodies_of_seed_7_are_pinned(mix, n, sha):
    assert digest_of_every_body(mix) == (n, sha)


def sha_of(obj):
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()


# The device keys (a machine class's `devices`, a mix's `device_deck`)
# change nothing where they are absent: the accepted
# configurations' fleets at seed 7, and what the judge says of the plain
# scheduler's answers to six jobs of each cell's mix on 320 of their
# nodes, whole and with two controls, hash as on the parent commit
# (97624d2) before the keys went in.
@pytest.mark.parametrize("config", ["prod-10k", "svc-10k", "preempt-10k"])
def test_fleet_of_seed_7_is_pinned(config):
    fleet = fleetlib.build_fleet(load("benchmark", "configs",
                                      config + ".json"), 7)
    assert len(fleet) == 10000 and not any("devices" in n for n in fleet)
    assert sha_of(fleet) == ("44daa35bfbc37ca73d791e035d628404"
                             "e7658de97142426c975aece51efbee24")


@pytest.mark.parametrize("config,mix,broken,n,sha", [
    ("prod-10k", "batch-fill", None, 10,
     "50b75d4e2d993c3880d0f923a435bfd8b2aea4700c3e4b750073e589aae34533"),
    ("prod-10k", "batch-fill", "capacity", 10,
     "50b75d4e2d993c3880d0f923a435bfd8b2aea4700c3e4b750073e589aae34533"),
    ("prod-10k", "batch-fill", "firstfit", 10,
     "2c86237df89a703864784e94ac05cac7d09d9672675618e3701aee6b0f36c7f7"),
    ("svc-10k", "service-fill", None, 10,
     "7fa9ceb9e552a5952aedd6e5676075dddfe8279790ad940077c302a97295114e"),
    ("svc-10k", "service-fill", "capacity", 10,
     "7fa9ceb9e552a5952aedd6e5676075dddfe8279790ad940077c302a97295114e"),
    ("svc-10k", "service-fill", "firstfit", 10,
     "1bd8a3f667fbf6bacaab12ea5f8ab5fa8c2a3f674e7a9899224bf0d8b8234fc7"),
    ("preempt-10k", "service-evict", None, 14,
     "45b2bec6746df0493bd63b80fdb74ab5488176ef122feff4c71c2f0584aa9a0a"),
    ("preempt-10k", "service-evict", "capacity", 14,
     "7c3909c957b919addd3dc42a605c3e72aac460d6bf744d8b3d5a3ef278914d52"),
    ("preempt-10k", "service-evict", "firstfit", 14,
     "45b2bec6746df0493bd63b80fdb74ab5488176ef122feff4c71c2f0584aa9a0a"),
])
def test_a_fixed_verdict_of_seed_7_is_pinned(config, mix, broken, n, sha):
    cfg = load("benchmark", "configs", config + ".json")
    fleet = fleetlib.build_fleet(cfg, 7, 320)
    tiers = fleetlib.residents(cfg, fleet) if "resident_tiers" in cfg \
        else None
    backlog = tiers["usage"] if tiers else fleetlib.backlog_usage(cfg, fleet)
    tpl = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        mix + ".json"))
    jobs = [traffic.plain_job(tpl, f"pin-{i}", c, DCS)
            for i, c in enumerate([3, 1, 5, 2, 8, 4])]
    plain = ref.PlainScheduler(
        fleet, backlog, PORTS, broken=broken, residents=tiers,
        scheduler_configuration=cfg.get("scheduler_configuration"))
    for job in jobs:
        plain.submit(job)
    full = [plain.full[a["id"]] for stubs in plain.allocs.values()
            for a in stubs if a["id"] in plain.full]
    compared, found = ref.judge(
        fleet, backlog, jobs, plain.evals, plain.allocs, full, [], PORTS,
        cfg["server"]["num_schedulers"], cfg["server"].get("decorrelation"),
        ref.ResidentState(tiers, plain.resident_allocs()) if tiers else None)
    assert "device_conflicts" not in compared and len(compared) == n
    assert sha_of([compared, found, plain.allocs, full]) == sha


@pytest.mark.parametrize("config", ["prod-10k", "svc-10k"])
def test_backlog_usage_is_the_parents(config):
    cfg = load("benchmark", "configs", config + ".json")
    assert "resident_tiers" not in cfg
    assert "scheduler_configuration" not in cfg
    fleet = fleetlib.build_fleet(cfg, 7)
    usage = fleetlib.backlog_usage(cfg, fleet)
    assert len(usage) == 10000
    assert usage[fleet[0]["id"]] == {"cpu": 2000, "memory_mb": 2560,
                                     "disk_mb": 400, "mbits": 0}
    assert hashlib.sha256(json.dumps(usage, sort_keys=True).encode()
                          ).hexdigest() == ("023a3c235340befc56a473d69a7ea699"
                                            "3e81f75bfe84531df615339ff9cf66c6")


@pytest.mark.parametrize("kw,want", [
    (dict(count=1000), 679_764),
    (dict(count=10, spreads=1, affinities=1, ports=2), 868_452)],
    ids=["batch-fill", "service-fill"])
def test_floor_bytes_of_both_cells_shapes_are_the_parents(kw, want):
    assert kc.select_floor_bytes(10000, 4, **kw) == want
    assert kc.select_floor_bytes(10000, 4, preempt_candidates=0, **kw) == want


def test_floor_bytes_with_candidates_by_hand():
    # 312,000 candidates (about what the toy's 10,000 nodes hold): each
    # one's four resources f32, its priority and node row i32; a score
    # per padded node f32
    extra = 312_000 * (4 * 4 + 2 * 4) + 16384 * 4
    assert kc.select_floor_bytes(10000, 4, 3, preempt_candidates=312_000) \
        == kc.select_floor_bytes(10000, 4, 3) + extra == 8_225_324


def test_judge_of_an_untiered_config_compares_the_parents_ten():
    cfg = load("benchmark", "configs", "prod-10k.json")
    fleet = fleetlib.build_fleet(cfg, 7, 320)
    backlog = fleetlib.backlog_usage(cfg, fleet)
    mix = load("benchmark", "traffic", "batch-fill.json")
    jobs = [traffic.plain_job(mix, f"pin-{i}", 12, DCS) for i in range(3)]
    plain = ref.PlainScheduler(fleet, backlog, PORTS)
    for job in jobs:
        plain.submit(job)
    compared, found = ref.judge(fleet, backlog, jobs, plain.evals,
                                plain.allocs, [], [], PORTS, 2)
    assert list(compared) == [
        "never_completed", "unplaced_evals", "lost_or_duplicated", "unread",
        "over_capacity", "infeasible", "port_conflicts",
        "spread_over_target", "stacked", "rank_gap"]
    assert list(found) == list(compared)
    assert ref.is_correct(compared)
    assert not set(ref.TIER_LIMITS) & set(ref.LIMITS)


def test_a_plain_job_without_a_template_priority_goes_out_at_50():
    mix = load("benchmark", "traffic", "batch-fill.json")
    assert "priority" not in mix["job"]
    job = traffic.plain_job(mix, "pin", 3, DCS)
    assert job["priority"] == 50
    assert traffic.wire_job(job)["priority"] == 50
    evict = load("tests", "benchmark", "traffic", "toy-evict.json")
    job = traffic.plain_job(evict, "pin", 3, DCS)
    assert job["priority"] == traffic.wire_job(job)["priority"] == 70


class _Calls:
    def __init__(self, fn):
        self.fn, self.n = fn, 0

    def __call__(self, *a, **kw):
        self.n += 1
        return self.fn(*a, **kw)


@pytest.mark.parametrize("config,calls", [
    (("benchmark", "configs", "prod-10k.json"), 1),
    (("tests", "benchmark", "preempt-toy.json"), 0)],
    ids=["untiered", "tiered"])
def test_agent_load_takes_the_programs_loader_only_without_tiers(
        monkeypatch, config, calls):
    from nomad_tpu.bench import ladder
    from nomad_tpu.server import Server, ServerConfig
    from benchmark.lib import agent as agentlib
    cfg = load(*config)
    fleet = fleetlib.build_fleet(cfg, 7, 64)
    counted = _Calls(ladder.seed_c2m_allocs)
    monkeypatch.setattr(ladder, "seed_c2m_allocs", counted)
    agent = agentlib.Agent(cfg, lambda _msg: None)
    agent.srv = Server(ServerConfig(num_schedulers=0))
    try:
        loaded = agent.load(fleet, None if calls
                            else fleetlib.residents(cfg, fleet))
        assert counted.n == calls
        assert loaded["nodes"] == 64 and loaded["rows_in_id_order"]
        allocs = list(agent.srv.store.allocs())
        if calls:
            assert len(allocs) == 64 * cfg["resident_allocs_per_node"]
            assert {a.job_id for a in allocs} == {"c2m-seed"}
        else:
            plain = fleetlib.residents(cfg, fleet)
            assert {a.id for a in allocs} == set(plain["allocs"])
            for a in allocs:
                # every resident carries its job, the store's own object:
                # a candidate for eviction, ignored by an eval of its job
                stored = agent.srv.store.job_by_id("default", a.job_id)
                assert a.job is stored
                assert a.job.job_modify_index == stored.job_modify_index > 0
                assert a.job.priority == plain["jobs"][a.job_id]["priority"]
    finally:
        agent.srv.shutdown()
        agent.close()


@pytest.mark.parametrize("mix,under", [
    ("batch-fill", "benchmark"), ("service-fill", "benchmark"),
    ("service-stream", "benchmark"), ("toy-evict", "tests/benchmark")])
def test_a_mix_is_found_under_the_first_of_the_manifests_paths_that_has_it(
        mix, under):
    # the accepted cells' mixes are the files they were (benchmark/ comes
    # first in `paths`); the tests' toy mix lies with the tests
    import benchmark.run as run
    paths = load("BENCHMARK.json")["paths"]
    assert paths[0] == "benchmark"
    assert run.find_mix(paths, mix) == os.path.join(
        ROOT, under, "traffic", mix + ".json")


def test_a_mix_no_path_has_is_refused():
    import benchmark.run as run
    with pytest.raises(SystemExit, match="no traffic/nowhere.json"):
        run.find_mix(load("BENCHMARK.json")["paths"], "nowhere")
