"""The comparison that decides ``correct``: every answer the window was
given, against the plain reference, once the window has closed.

The reference replays each client's requests in that client's own order
(a client's writes touch only rows it owns, and no other client reads
them), so every answer has one right value.  Exact: every limit is 0.
What a request's right results are is its generator's to say
(``expected(oracle, request)``), from the oracle's sets.

A *control* is the reference put in the program's place with one stated
guarantee broken; ``control_answers`` computes what it would have served
for the same requests, and the same comparison has to call it wrong.
"""

from __future__ import annotations

from .oracle import GramOracle, StaleOracle

CHECKS = ("wrong_answers", "missing_answers", "stale_readbacks")


def expected_answers(gen, oracle, history: list, records_by_client: list) -> list:
    """For each client, for each record, the list of right results.
    ``history``: the record sets of the warm-up phases, whose writes the
    reference applies first."""
    for phase in history:
        expected_answers(gen, oracle, [], phase)
    return [[gen.expected(oracle, rec.req) for rec in recs] for recs in records_by_client]


def control_answers(control: str, gen, rows, cols, n_rows: int, slice_width: int,
                    history: list, records_by_client: list) -> list:
    """What a reference with one guarantee broken would have served.

    ``drop_slice``: answers are no longer exact - the highest slice's bits
    are left out of every count.  ``stale_read``: a write is acknowledged
    and never applied - reads after it do not reflect it."""
    if control == "drop_slice":
        keep = cols < (int(cols.max()) // slice_width) * slice_width
        return expected_answers(gen, GramOracle(rows[keep], cols[keep], n_rows), history, records_by_client)
    if control == "stale_read":
        return expected_answers(gen, StaleOracle(rows, cols, n_rows), history, records_by_client)
    raise ValueError(f"unknown control {control!r}")


def _norm(results, exp: list):
    """Served results in the reference's terms (an int for a count, a bool
    for a SetBit); None where the shape is not an answer at all."""
    if not isinstance(results, list) or len(results) != len(exp):
        return None
    return results if all(type(g) is type(e) for g, e in zip(results, exp)) else None


def judge(records_by_client: list, served: list, want: list) -> dict:
    """Counts of calls: checked, wrong, missing (no answer ever came), and
    wrong ones that sit in a read-back (a stale read after a write)."""
    n = {"answers_checked": 0, "wrong_answers": 0, "missing_answers": 0,
         "stale_readbacks": 0}
    first_wrong = []
    for recs, got_c, want_c in zip(records_by_client, served, want):
        for rec, got, exp in zip(recs, got_c, want_c):
            got = _norm(got, exp)
            if got is None:
                n["missing_answers"] += len(exp)
                rec.ok = False
                continue
            bad = sum(g != e for g, e in zip(got, exp))
            n["answers_checked"] += len(exp)
            n["wrong_answers"] += bad
            if rec.req.kind == "readback":
                n["stale_readbacks"] += bad
            rec.ok = bad == 0
            if bad and len(first_wrong) < 3:
                first_wrong.append({"client": rec.client, "kind": rec.req.kind,
                                    "body": rec.req.body[:160], "served": got[:8],
                                    "reference": exp[:8]})
    n["first_wrong"] = first_wrong
    return n
