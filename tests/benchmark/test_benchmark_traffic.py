"""The traffic generator: what a seed may and may not change."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import traffic  # noqa: E402

DCS = ["dc1", "dc2", "dc3", "dc4"]


def mix(name):
    return traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                         name + ".json"))


def window_requests(seed, seconds=45.0, rate=6.0):
    reqs = traffic.open_loop(mix("service-stream"), seed, seconds, DCS, rate)
    return [r for r in reqs if r.due_s >= 0.0]


@pytest.mark.parametrize("seed", [1, 2, 77, 2**31 + 5])
def test_deck_gives_every_seed_the_same_multiset(seed):
    base = collections.Counter(r.jobs[0]["count"]
                               for r in window_requests(0))
    got = collections.Counter(r.jobs[0]["count"]
                              for r in window_requests(seed))
    assert got == base
    assert sum(got.values()) == 270


def test_deck_order_differs_between_seeds():
    a = [r.jobs[0]["count"] for r in window_requests(1)]
    b = [r.jobs[0]["count"] for r in window_requests(2)]
    assert a != b


@pytest.mark.parametrize("n", [20, 40, 270, 7, 33])
def test_deck_counts_whole_decks_and_fixed_partial(n):
    deck = mix("service-stream")["deck"]
    got = traffic.deck_counts(deck, n)
    assert len(got) == n
    whole = collections.Counter(deck)
    for card, k in collections.Counter(got).items():
        assert k >= (n // len(deck)) * whole[card]
    assert got == traffic.deck_counts(deck, n)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_gaps_are_one_multiset_in_another_order(seed):
    def gaps(s):
        due = [r.due_s for r in window_requests(s)]
        return [round(b - a, 9) for a, b in zip(due, due[1:])]
    base, got = gaps(0), gaps(seed)
    assert got != base
    # all but the gap the last arrival would open are the same numbers
    assert len(set(sorted(got)) ^ set(sorted(base))) <= 2


def test_arrivals_fill_the_window_in_order():
    due = [r.due_s for r in window_requests(9, seconds=30.0, rate=10.0)]
    assert len(due) == 300 and due == sorted(due)
    assert due[0] == 0.0 and due[-1] < 30.0


def test_exponential_gaps_are_exponential():
    g = traffic.exponential_gaps(1000, 100.0)
    assert abs(sum(g) - 100.0) < 1e-9
    mean = sum(g) / len(g)
    var = sum((x - mean) ** 2 for x in g) / len(g)
    assert 0.85 < var / mean ** 2 < 1.05      # cv^2 of an exponential is 1


def test_rehearsal_comes_before_the_window():
    reqs = traffic.open_loop(mix("service-stream"), 5, 10.0, DCS, 6.0)
    early = [r for r in reqs if r.due_s < 0]
    assert len(early) == round(6.0 * mix("service-stream")["rehearse_s"])
    assert min(r.due_s for r in early) >= -mix("service-stream")["rehearse_s"]


def test_job_ids_are_unique_and_seeded():
    ids = [j["id"] for r in traffic.open_loop(
        mix("service-stream"), 11, 10.0, DCS, 6.0) for j in r.jobs]
    ids += [j["id"] for rnd in traffic.warmup_requests(
        mix("service-stream"), 11, DCS) for r in rnd for j in r.jobs]
    assert len(ids) == len(set(ids))
    assert all("-11-" in i for i in ids)


def test_same_seed_same_bytes():
    a = traffic.open_loop(mix("service-stream"), 21, 5.0, DCS, 6.0)
    b = traffic.open_loop(mix("service-stream"), 21, 5.0, DCS, 6.0)
    assert [r.body for r in a] == [r.body for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]


def test_service_wire_job_carries_the_shape():
    req = window_requests(1)[0]
    wire = json.loads(req.body)["Job"]
    tg = wire["task_groups"][0]
    assert wire["type"] == "service" and wire["datacenters"] == DCS
    assert tg["count"] == req.jobs[0]["count"]
    assert {c["operand"] for c in tg["constraints"]} == {"=", "regexp"}
    assert tg["affinities"][0]["weight"] == 50
    assert tg["spreads"][0]["spread_target"][0] == {"value": "dc1",
                                                     "percent": 40}
    res = tg["tasks"][0]["resources"]
    assert (res["cpu"], res["memory_mb"]) == (250, 256)
    assert len(res["networks"][0]["dynamic_ports"]) == 2


def test_batch_wire_job_has_no_network():
    reqs = traffic.closed_loop(mix("batch-fill"), 1, 10.0, DCS)
    body = json.loads(reqs[0].body)
    assert isinstance(body, list) and len(body) == mix("batch-fill")["bulk"]
    tg = body[0]["Job"]["task_groups"][0]
    assert tg["count"] == 1000 and tg["constraints"] == []
    res = tg["tasks"][0]["resources"]
    assert (res["cpu"], res["memory_mb"], res["networks"]) == (20, 32, [])


def test_closed_loop_has_jobs_for_the_fastest_program_allowed():
    m = mix("batch-fill")
    reqs = traffic.closed_loop(m, 1, 45.0, DCS)
    assert len(reqs) * m["bulk"] >= 45.0 * m["max_jobs_per_s"]


def test_batch_request_ceiling_grew_and_kept_the_list_s_head():
    """Four times what the program completes, so that a faster program
    does not run out of requests before the close; the deck is one size
    and the ids count up, so a list built at 12 jobs a second is the
    head of it."""
    m = mix("batch-fill")
    assert m["max_jobs_per_s"] == 32.0 and "not a rate" in m["max_jobs_note"]
    new = traffic.closed_loop(m, 7, 51.0, DCS)
    old = traffic.closed_loop(dict(m, max_jobs_per_s=12.0), 7, 51.0, DCS)

    def jobs(reqs):
        return [j for r in reqs for j in r.jobs]
    assert len(jobs(old)) == 752 and len(jobs(new)) >= 1900
    assert jobs(new)[:752] == jobs(old)
    assert [r.body for r in new[:len(old)]] == [r.body for r in old]


@pytest.mark.parametrize("name,largest", [("service-stream", 50),
                                          ("batch-fill", 1000)])
def test_warmup_covers_every_bucket_of_the_deck(name, largest):
    m = mix(name)
    rounds = traffic.warmup_requests(m, 1, DCS)
    solo = [r[0].jobs[0]["count"] for r in rounds if len(r[0].jobs) == 1]
    burst = [[j["count"] for j in r[0].jobs] for r in rounds
             if len(r[0].jobs) > 1]

    def bucket(k):
        b = 1
        while b < k:
            b *= 2
        return b
    assert {bucket(c) for c in m["deck"]} <= {bucket(c) for c in solo}
    assert max(solo) == largest
    assert {len(b) for b in burst} >= {2, 4}


def test_cell_overrides_win_over_the_mix(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"name": "m", "rate_per_s": 1.0}))
    assert traffic.load_mix(str(p), {"rate_per_s": 2.5})["rate_per_s"] == 2.5


def test_stream_mix_is_the_issue_s_deck_at_the_swept_half_knee():
    m = mix("service-stream")
    assert sorted(m["deck"]) == [1] * 4 + [2] * 4 + [3] * 3 + [5] * 3 \
        + [10] * 3 + [20] * 2 + [50]
    assert m["rate_per_s"] == 6.0


def test_toy_rehearsal_shrinks_every_job_with_the_fleet():
    m = mix("batch-fill")
    traffic.scale_counts(m, 640 / 10000)
    assert m["deck"] == [64] and m["warmup"]["solo"] == [64, 45, 19, 3]
    assert m["max_jobs_per_s"] == 32.0 / 0.064
    assert all(c >= 1 for b in m["warmup"]["bursts"] for c in b)
    m = mix("service-stream")
    traffic.scale_counts(m, 0.01)
    assert set(m["deck"]) == {1}        # never below one instance
