"""Each per-layer reader on a made-up context: the arithmetic it states,
and nothing (never 0) where there is nothing to read."""

import pytest

from lib.records import Record, Request


def reader(run_mod, name):
    return lambda ctx: run_mod.read_metric(name, ctx)


def rec(kind, t_send, t_recv, calls=(), spans=None, results=(1,)):
    return Record(0, Request(kind, "", list(calls)), t_send, t_recv,
                       list(results) if results is not None else None, spans=spans)


def tree(root_ms, **children):
    return [{"name": "POST /index/i/query", "ms": root_ms,
             "children": [{"name": k, "ms": v} for k, v in children.items()]}]


def ctx_of(records, trace=None, traced=(0.0, 1.0), platform="tpu"):
    return {"records": records, "trace": trace, "traced": traced, "config": {"slices": 64},
            "device": {"platform": platform, "kind": "TPU v5 lite", "count": 1}, "write_pairs": []}


def test_idle_share(run_mod):
    read = reader(run_mod, "device_idle_share")
    assert read(ctx_of([], {"busy_s": 0.25, "window_s": 1.0})) == 75.0
    assert read(ctx_of([], {"busy_s": 0.0, "window_s": 0.0})) is None


def test_exec_host_ms_is_the_mean_root_span_of_reads(run_mod):
    recs = [rec("read", 0, 1, spans=tree(10.0, parse=1.0, fused=8.0)),
            rec("readback", 0, 1, spans=tree(4.0, device=1.0)),
            rec("write", 0, 1, spans=tree(100.0)),              # writes are not reads
            rec("read", 0, 1)]                                  # an untraced request
    assert reader(run_mod, "exec_host_ms")(ctx_of(recs)) == pytest.approx(7.0)
    assert reader(run_mod, "exec_host_ms")(ctx_of([rec("read", 0, 1)])) is None


def test_readback_ms_is_what_the_repair_adds_to_a_read(run_mod):
    recs = [rec("read", 0.0, 0.010), rec("read", 0.0, 0.012), rec("read", 0.0, 0.020),
            rec("readback", 1.0, 1.050), rec("readback", 1.0, 1.070), rec("readback", 2.0, 9.0, results=None),
            rec("write", 0.0, 5.0)]
    assert reader(run_mod, "readback_ms")(ctx_of(recs)) == pytest.approx(60.0 - 12.0)
    assert reader(run_mod, "readback_ms")(ctx_of([rec("read", 0.0, 0.010)])) is None
    assert reader(run_mod, "readback_ms")(ctx_of([rec("readback", 0.0, 0.010)])) is None


def test_write_ack(run_mod):
    recs = [rec("write", 0.0, 0.002), rec("write", 1.0, 1.004), rec("write", 2.0, 2.009),
            rec("write", 3.0, 9.0, results=None), rec("read", 0.0, 5.0)]
    assert reader(run_mod, "write_ack_ms")(ctx_of(recs)) == pytest.approx(4.0)
    assert reader(run_mod, "write_ack_ms")(ctx_of([rec("read", 0.0, 5.0)])) is None
