"""The fast reference (pair intersections counted per column) equals the
plain one (sorted sets of columns, chip_smoke.py's), writes inside and
outside the loaded columns included; and each control is a reference that
the comparison calls wrong."""

import numpy as np

from lib import byname, compare
from lib.oracle import OPS, GramOracle, SetOracle
from lib.records import Record

datagen = byname.load("datagens", "windows")
traffic = byname.load("generators", "pairs_rw")
FRAME = {"name": "f", "rows": 48, "pool": 256, "bits_per_row_slice": {"base": 20, "step": 7, "mod": 16}}


def test_gram_oracle_equals_set_oracle():
    rows, cols = datagen.make_frame(2**31 + 9, 3, FRAME)
    rows, cols = np.concatenate((rows, rows[:50])), np.concatenate((cols, cols[:50]))  # bits given twice
    a, b = SetOracle(rows, cols), GramOracle(rows, cols, FRAME["rows"])
    rng = np.random.default_rng(5)
    for i in range(400):
        if i % 5 == 0:
            r = int(rng.integers(0, FRAME["rows"]))
            c = int(rng.choice(cols)) if i % 10 == 0 else int(rng.integers(0, 3 << 20))
            assert a.set_bit(r, c) == b.set_bit(r, c)
            assert a.set_bit(r, c) is False and b.set_bit(r, c) is False
        r1, r2 = (int(x) for x in rng.choice(FRAME["rows"], size=2, replace=False))
        for op in OPS:
            assert a.count(op, r1, r2) == b.count(op, r1, r2), (op, r1, r2)


def test_datagen_is_seeded_and_sorted():
    r1, c1 = datagen.make_frame(123456789012 % (2**32), 2, FRAME)
    r2, c2 = datagen.make_frame(123456789012 % (2**32), 2, FRAME)
    assert np.array_equal(r1, r2) and np.array_equal(c1, c2)
    assert len(r1) == 2 * int(datagen.bits_of_rows(FRAME).sum())
    key = (c1 >> 20) * (1 << 40) + r1 * (1 << 20) + (c1 & ((1 << 20) - 1))
    assert (np.diff(key.astype(np.int64)) > 0).all()       # sorted by (slice, row, column), no bit twice
    assert not np.array_equal(c1, datagen.make_frame(1, 2, FRAME)[1])


def _records(p, rows, cols, n=150):
    """What a perfect server would have been sent and would have served."""
    out, ref = [], GramOracle(rows, cols, FRAME["rows"])
    for c in range(p["clients"]):
        s = traffic.Stream(p, "f", FRAME["rows"], 3 << 20, 3, c)
        out.append([Record(c, s.next(), 0.0, 0.0, None) for _ in range(n)])
    for recs, want in zip(out, compare.expected_answers(traffic, ref, [], out)):
        for rec, w in zip(recs, want):
            rec.results = w
    return out


P = {"clients": 2, "read_calls": 6, "ops": list(OPS), "hot_rows": "all", "hot_share": 1.0,
     "zipf_s": 1.0, "write_share": 0.1, "readback_calls": 4, "owned_rows_per_client": 3,
     "owned_rank_start": 4}


def test_sound_answers_pass_and_each_control_fails():
    rows, cols = datagen.make_frame(3, 3, FRAME)
    recs = _records(P, rows, cols)
    served = [[r.results for r in rs] for rs in recs]
    want = compare.expected_answers(traffic, GramOracle(rows, cols, FRAME["rows"]), [], recs)
    v = compare.judge(recs, served, want)
    assert v["wrong_answers"] == 0 and v["missing_answers"] == 0 and v["answers_checked"] > 500
    for control in ("drop_slice", "stale_read"):
        got = compare.control_answers(control, traffic, rows, cols, FRAME["rows"], 1 << 20, [], recs)
        v = compare.judge(recs, got, want)
        assert v["wrong_answers"] > 0, control
    assert v["stale_readbacks"] > 0   # stale_read is caught in the read-backs


def test_an_answer_that_never_came_is_missing_not_wrong():
    rows, cols = datagen.make_frame(3, 3, FRAME)
    recs = _records(P, rows, cols, n=20)
    served = [[r.results for r in rs] for rs in recs]
    served[0][0] = None
    served[1][1] = served[1][1][:-1] if len(served[1][1]) > 1 else ["x"]
    want = compare.expected_answers(traffic, GramOracle(rows, cols, FRAME["rows"]), [], recs)
    v = compare.judge(recs, served, want)
    assert v["wrong_answers"] == 0 and v["missing_answers"] == len(want[0][0]) + len(want[1][1])
