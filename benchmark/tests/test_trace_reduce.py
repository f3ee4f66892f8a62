"""The reduction from trace to numbers: interval arithmetic on made-up
intervals, then the recorded trace of the chip (tests/data/small.xplane.pb:
one jitted popcount program run three times with 20 ms sleeps between)."""

import os

import numpy as np
import pytest

from lib import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_merges_nested_and_overlapping():
    s = np.array([0.0, 2.0, 3.0, 10.0, 11.0, 30.0])
    e = np.array([5.0, 4.0, 8.0, 12.0, 11.5, 31.0])
    ms, me = tr.union_ns(s, e)
    assert ms.tolist() == [0.0, 10.0, 30.0] and me.tolist() == [8.0, 12.0, 31.0]
    assert (me - ms).sum() == 11.0
    order = np.random.default_rng(0).permutation(len(s))
    ms2, me2 = tr.union_ns(s[order], e[order])
    assert ms2.tolist() == ms.tolist() and me2.tolist() == me.tolist()
    assert tr.union_ns(np.array([]), np.array([]))[0].size == 0


def test_spans_arithmetic():
    from lib import spans
    tree = [{"name": "POST /index/i/query", "ms": 10.0, "tags": {"lane": "fused"}, "children": [
        {"name": "parse", "ms": 1.0},
        {"name": "fused", "ms": 8.0, "children": [
            {"name": "device", "ms": 3.0, "tags": {"lane": "gather"},
             "children": [{"name": "device", "ms": 2.0}]},
            {"name": "call.Count", "ms": 1.0, "children": [{"name": "device", "ms": 0.5}]}]}]}]
    assert spans.root_ms(tree) == 10.0 and spans.root_ms(tree[0]) == 10.0
    assert spans.root_ms(None) == 0.0 and spans.root_ms(tree + tree) == 20.0
    seen = set()
    spans.lanes(tree, seen)
    assert "call.Count" in seen and "POST:lane=fused" in seen


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_recorded_trace_numbers():
    r = tr.reduce_trace(RECORDED)
    assert r["devices"] == 1
    # Three runs of one program (13.4, 13.5 and 13.3 us on the device, read
    # off the trace by hand), each followed by a 20 ms sleep: the window is
    # the three sleeps between the profiler's start and stop calls (which
    # took 46 and 249 ms and are no part of it), the idle gaps are the sleeps.
    assert r["busy_s"] == pytest.approx(40.2e-6, rel=0.01)
    assert r["window_s"] == pytest.approx(0.066, abs=0.003)
    assert [g[0] for g in r["idle_gaps"][:3]].count("python: $time sleep") >= 2
    assert all(0.019 < g[1] < 0.023 for g in r["idle_gaps"][:3])
    assert r["device_ops"] and len(r["device_ops"]) <= 10
    assert [n.split("(")[0] for n, _s in r["device_programs"]] == ["jit__lambda"]
    assert r["device_programs"][0][1] == pytest.approx(r["busy_s"], rel=0.01)
