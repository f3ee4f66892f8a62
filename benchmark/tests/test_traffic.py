"""Each traffic mix is a pure function of (--seed, client); bodies do not
repeat; a write is followed by its own read-back; nobody reads a row that
another client writes."""

import json
import os

import pytest

from lib import byname

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))
MIXES.append("cold_tail")   # not a file yet: ISSUE 25's tall-frame mix, queued in PERF.md 7
N_ROWS, N_COLS = 512, 64 << 20
traffic = byname.load("generators", "pairs_rw")


def mix(name):
    if name == "cold_tail":
        return {"generator": "pairs_rw", "loop": "closed", "clients": 8, "read_calls": 32,
                "ops": ["Intersect", "Union", "Difference", "Xor"], "hot_rows": 192,
                "hot_share": 0.95, "zipf_s": 1.0, "write_share": 0.0}
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def take(p, seed, client, n=300, phase=0):
    s = byname.load("generators", p["generator"]).Stream(p, "f", N_ROWS, N_COLS, seed, client, phase)
    return [s.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_pure_function_of_seed_and_client(name):
    p = mix(name)
    a, b = take(p, 2**31 + 5, 3), take(p, 2**31 + 5, 3)
    assert [r.body for r in a] == [r.body for r in b]
    assert [r.body for r in a] != [r.body for r in take(p, 2**31 + 5, 4)]
    assert [r.body for r in a] != [r.body for r in take(p, 2**31 + 6, 3)]
    assert [r.body for r in a] != [r.body for r in take(p, 2**31 + 5, 3, phase=1)]


@pytest.mark.parametrize("name", MIXES)
def test_bodies_do_not_repeat_and_rows_differ(name):
    p = mix(name)
    reqs = take(p, 11, 0, n=2000)
    reads = [r for r in reqs if r.kind != "write"]
    assert len({r.body for r in reads}) == len(reads)
    assert all(a != b for r in reads for _op, a, b in r.calls)
    assert all(len(r.calls) == p["read_calls"] for r in reqs if r.kind == "read")


@pytest.mark.parametrize("name", MIXES)
def test_hot_share(name):
    p = mix(name)
    if p["hot_rows"] == "all":
        pytest.skip("no cold rows in this mix")
    hot = set(traffic.ranking(11, N_ROWS)[: p["hot_rows"]].tolist())
    draws = [r for q in take(p, 11, 1, n=500) for _op, a, b in q.calls for r in (a, b)]
    share = sum(r in hot for r in draws) / len(draws)
    assert abs(share - p["hot_share"]) < 0.02
    cold = [[r not in hot for _op, a, b in q.calls for r in (a, b)] for q in take(p, 11, 1, n=500)]
    per_body = [sum(c) for c in cold]
    assert max(per_body) >= 8 and min(per_body) == 0    # drawn one by one, not spread evenly


@pytest.mark.parametrize("name", [m for m in MIXES if mix(m).get("write_share", 0) > 0])
def test_write_pairs_and_ownership(name):
    p = mix(name)
    rank = traffic.ranking(11, N_ROWS)
    owned = [set(rank[traffic.owned_ranks(p, c)].tolist()) for c in range(p["clients"])]
    assert sum(len(o) for o in owned) == len(set().union(*owned))  # disjoint
    n_writes = 0
    for c in range(p["clients"]):
        others = set().union(*(o for i, o in enumerate(owned) if i != c))
        reqs = take(p, 11, c, n=1500)
        for prev, cur in zip(reqs, reqs[1:]):
            if prev.kind == "write":
                n_writes += 1
                assert prev.row in owned[c] and 0 <= prev.col < N_COLS
                assert cur.kind == "readback" and len(cur.calls) == p["readback_calls"]
                assert all(a == prev.row for _op, a, _b in cur.calls)
            else:
                assert cur.kind != "readback"
        read_rows = {r for q in reqs for _op, a, b in q.calls for r in (a, b)}
        assert not read_rows & others
    share = n_writes / (1500 * p["clients"] - n_writes)   # read-backs are not draws
    assert abs(share - p["write_share"]) < 0.015


@pytest.mark.parametrize("name", MIXES)
def test_fill_names_every_hot_row_and_every_write_burst_and_stress_is_the_mix_all_writes(name):
    p = mix(name)
    a = traffic.fill_requests(p, "f", N_ROWS, N_COLS, 2**31 + 5)
    assert [r.body for r in a] == [r.body for r in traffic.fill_requests(p, "f", N_ROWS, N_COLS, 2**31 + 5)]
    rank = traffic.ranking(2**31 + 5, N_ROWS)
    hot = set((rank if p["hot_rows"] == "all" else rank[: p["hot_rows"]]).tolist())
    reads = [r for r in a if r.kind == "read"]
    assert all(len(r.calls) <= p["read_calls"] for r in reads)
    assert hot == {x for r in reads for _op, r1, r2 in r.calls for x in (r1, r2)}
    assert all(r1 != r2 for r in a for _op, r1, r2 in r.calls)
    bursts, run = [], []
    for r in a[len(reads):]:                 # the write ladder follows the reads
        if r.kind == "write":
            run.append(r)
        else:
            assert r.kind == "readback" and [w.row for w in run] == [r1 for _op, r1, _r2 in r.calls]
            assert len({w.row for w in run}) == len(run)
            bursts.append((len(run), len({w.col >> 20 for w in run})))
            run = []
    n = p["clients"] if p.get("write_share", 0) > 0 else 0
    assert bursts == [(k, s) for k in range(1, n + 1) for s in range(1, k + 1)] and not run
    stress = traffic.stress_mixes(p)
    if p.get("write_share", 0) > 0:
        kinds = [r.kind for r in take(stress[0], 11, 0, n=40)]
        assert kinds == ["write", "readback"] * 20
        assert {k: v for k, v in stress[0].items() if k != "write_share"} == \
               {k: v for k, v in p.items() if k != "write_share"}
    else:
        assert stress == []


@pytest.mark.parametrize("name", MIXES)
def test_expected_and_n_calls(name):
    class Ref:
        def set_bit(self, row, col):
            return (row, col)

        def count(self, op, a, b):
            return (op, a, b)

    for req in take(mix(name), 11, 2, n=200):
        want = traffic.expected(Ref(), req)
        assert len(want) == traffic.n_calls(req)
        assert want == ([(req.row, req.col)] if req.kind == "write" else req.calls)
