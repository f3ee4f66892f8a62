"""Generated differential fuzz over every kernel/strategy lane.

CPU form of the asm-vs-Go idiom (roaring/assembly_test.go): the Pallas
kernels run in interpret mode here; ``python chip_smoke.py`` (kernels
phase) runs the SAME generated cases against the real Mosaic lowering on
a chip.
"""

import pytest

from pilosa_tpu.ops import diffcheck


@pytest.mark.parametrize("seed", [11, 12])
def test_all_lanes_vs_numpy(seed):
    failures = diffcheck.run_lanes(seed=seed, cases_per_lane=12, interpret=True)
    assert not failures, "\n".join(failures)


def test_lane_coverage_is_complete():
    """Every strategy lane reachable from ops/dispatch.py + engine.py has
    a generated-case lane in the harness (VERDICT r3 item 3): pair ops x
    {fused, tiled, resident, slice-major gather, row-major gather, gram
    identities, dispatch 3D/4D/gram}, multi-fold x layouts, TopN scorer,
    count1, Gram builder tiers."""
    lanes = diffcheck.lane_names()
    for op in ("and", "or", "xor", "andnot"):
        for fam in ("count2", "resident", "gather", "rmgather",
                    "gram_pairs", "dispatch", "dispatch4", "dispatch_gram"):
            assert f"{fam}:{op}" in lanes
    for mop in ("and", "or", "andnot"):
        for k in (2, 4):
            assert f"multi:{mop}:k{k}" in lanes
            assert f"rmmulti:{mop}:k{k}" in lanes
        for ka, kb in ((1, 1), (2, 1), (2, 2)):
            assert f"xframe:{mop}:k{ka}{kb}" in lanes
        assert f"dispatch_xframe:{mop}" in lanes
    assert {"count1", "topn", "gram_oneshot", "gram_scan", "gram_chunked"} <= lanes
    # 2 seeds x 12 cases = 24 generated cases per lane family >= 20.
