"""Differential fuzz harness: every kernel/strategy lane vs numpy.

The reference's single most important test idiom is asm-vs-Go
equivalence over randomized inputs (roaring/assembly_test.go:45-141).
This module is that idiom generalized to the full lane surface of this
build: for each strategy lane (fused count, resident, slice-major
gather, row-major gather, multi-fold both layouts, the cross-frame fold
over two matrices, TopN scorer, Gram one-shot/scan/word-chunked, dispatch
3D/4D parity) it generates N
random (shape, op, density, layout) cases and requires EXACT agreement
with a pure-numpy ground truth.

Two consumers run the same cases:
- the pytest suite (tests/test_differential_kernels.py), CPU backend,
  Pallas kernels in interpret mode;
- ``chip_smoke.py`` (kernels phase) on a real chip, the actual Mosaic
  lowering.
"""

from __future__ import annotations

import numpy as np

# Fixed shape buckets bound jit recompiles (each distinct shape traces
# once; values/ops/densities vary freely inside a bucket).
# Words must satisfy ops.pallas_kernels._tileable (divisible by 8*128).
SHAPES = [  # (n_slices, n_rows, words)
    (1, 8, 1024),
    (2, 16, 2048),
    (3, 48, 1024),
    (2, 64, 3072),
]
B = 16  # queries per case
KS = (2, 4)  # multi-fold operand buckets
XKS = ((1, 1), (2, 1), (2, 2))  # cross-frame fold: operands of the first matrix, of the second
PAIR_OPS = ("and", "or", "xor", "andnot")
MULTI_OPS = ("and", "or", "andnot")


def _random_words(rng: np.random.Generator, shape, density_k: int) -> np.ndarray:
    """uint32 words with controlled bit density: AND of k draws ~ 2^-k
    density, OR of k draws ~ 1 - 2^-k; k=0 -> all zeros, k=-1 -> all ones.
    Extreme densities are where popcount accumulators and fold-identity
    padding break."""
    if density_k == 0:
        return np.zeros(shape, dtype=np.uint32)
    if density_k == -1:
        return np.full(shape, 0xFFFFFFFF, dtype=np.uint32)
    out = rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)
    for _ in range(abs(density_k) - 1):
        nxt = rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)
        out = (out & nxt) if density_k > 0 else (out | nxt)
    return out


_DENSITIES = (1, 3, -3, 0, -1)  # ~0.5, ~0.125, ~0.875, zeros, ones


def gen_case(rng: np.random.Generator, shape):
    """One random case for a shape bucket."""
    s, r, w = shape
    dk = int(rng.choice(_DENSITIES))
    rm = _random_words(rng, (s, r, w), dk)
    pairs = rng.integers(0, r, size=(B, 2), dtype=np.int32)
    idx = {k: rng.integers(0, r, size=(B, k), dtype=np.int32) for k in KS}
    src = _random_words(rng, (s, w), 1)
    return rm, pairs, idx, src


# ---- numpy ground truths ---------------------------------------------------

def _np_pop(x: np.ndarray) -> np.ndarray:
    from pilosa_tpu.ops.bitwise import np_popcount

    return np_popcount(x)


def _np_pair(op: str, a: np.ndarray, b: np.ndarray) -> int:
    from pilosa_tpu.ops import bitwise as bw

    fn = {
        "and": bw.np_count_and,
        "or": bw.np_count_or,
        "xor": bw.np_count_xor,
        "andnot": bw.np_count_andnot,
    }[op]
    return int(fn(a, b))


def np_pair_counts(op: str, rm: np.ndarray, pairs: np.ndarray) -> list[int]:
    return [
        sum(_np_pair(op, rm[s, int(p0)], rm[s, int(p1)]) for s in range(rm.shape[0]))
        for p0, p1 in pairs
    ]


def np_multi_counts(op: str, rm: np.ndarray, idx: np.ndarray) -> list[int]:
    from pilosa_tpu.ops.bitwise import np_gather_count_multi

    return [int(v) for v in np_gather_count_multi(op, rm, idx)]


def np_xframe_counts(op: str, rm_a, idx_a, rm_b, idx_b) -> list[int]:
    from pilosa_tpu.ops.bitwise import np_gather_count_xframe

    return [int(v) for v in np_gather_count_xframe(op, rm_a, idx_a, rm_b, idx_b)]


def np_topn_counts(rm: np.ndarray, src: np.ndarray) -> list[int]:
    return [
        int(_np_pop(rm[:, ri, :] & src).sum()) for ri in range(rm.shape[1])
    ]


def np_gram(rm: np.ndarray) -> np.ndarray:
    r = rm.shape[1]
    out = np.zeros((r, r), dtype=np.int64)
    for i in range(r):
        for j in range(r):
            out[i, j] = sum(
                _np_pop(rm[s, i] & rm[s, j]).sum() for s in range(rm.shape[0])
            )
    return out


# ---- lane runners ----------------------------------------------------------

def run_lanes(seed: int, cases_per_lane: int, interpret: bool) -> list[str]:
    """Run every lane over generated cases; returns failure descriptions
    (empty = all lanes agree with numpy everywhere)."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitwise as bw
    from pilosa_tpu.ops import dispatch
    from pilosa_tpu.ops import pallas_kernels as pk

    failures: list[str] = []
    rng = np.random.default_rng(seed)
    # The cross-frame cases draw from a stream of their own: the other
    # lanes' cases stay what they were.
    xrng = np.random.default_rng([seed, 0x7F])

    def check(lane: str, case_i: int, got, want) -> None:
        got = np.asarray(got).astype(np.int64).reshape(-1).tolist()
        want = list(want) if isinstance(want, (list, tuple)) else [want]
        if got[: len(want)] != want:
            failures.append(
                f"{lane}[case {case_i}]: got {got[:len(want)][:6]}... want {want[:6]}..."
            )

    for ci in range(cases_per_lane):
        shape = SHAPES[ci % len(SHAPES)]
        s, r, w = shape
        rm, pairs, idx, src = gen_case(rng, shape)
        rm4 = jnp.asarray(rm.reshape(s, r, w // 128, 128))
        rmj = jnp.asarray(rm)
        rmt = np.ascontiguousarray(rm.transpose(1, 0, 2))
        rmt4 = jnp.asarray(rmt.reshape(r, s, w // 128, 128))
        # Decorrelated from the shape cycle (len(SHAPES)=4 would alias a
        # same-period op cycle: each op pinned to one shape forever) —
        # rng draws give every (op, k, shape) combination coverage across
        # cases while shapes still cycle deterministically for jit reuse.
        op = PAIR_OPS[int(rng.integers(len(PAIR_OPS)))]
        mop = MULTI_OPS[int(rng.integers(len(MULTI_OPS)))]
        k = KS[int(rng.integers(len(KS)))]

        # L0 whole-array counts (popcntSliceAsm / popcnt*SliceAsm
        # analogs).  These kernels return (8, 128) PARTIAL TILES per row
        # (scalar outputs can't lower on TPU); callers reduce — mirror
        # that contract here.
        a2, b2 = rm[0], rm[(s - 1) % s]
        check("count1", ci,
              np.asarray(pk.fused_count1(jnp.asarray(a2), interpret=interpret)).sum(),
              int(_np_pop(a2).sum()))
        check(f"count2:{op}", ci,
              np.asarray(pk.fused_count2(
                  op, jnp.asarray(a2), jnp.asarray(b2), interpret=interpret)).sum(),
              _np_pair(op, a2, b2))
        # tiled (4D) form of the same pair
        check(f"count2_tiled:{op}", ci,
              np.asarray(pk.fused_count2(
                  op, jnp.asarray(a2.reshape(r, w // 128, 128)),
                  jnp.asarray(b2.reshape(r, w // 128, 128)),
                  interpret=interpret, tiled=True)).sum(),
              _np_pair(op, a2, b2))

        want_pairs = np_pair_counts(op, rm, pairs)
        jp = jnp.asarray(pairs)
        # resident lane (stream-all-rows strategy)
        check(f"resident:{op}", ci,
              pk.fused_resident_count2(op, rm4, jp, interpret=interpret), want_pairs)
        # slice-major gather lane
        check(f"gather:{op}", ci,
              pk.fused_gather_count2(op, rm4, jp, interpret=interpret), want_pairs)
        # row-major gather lane (one contiguous descriptor per operand row)
        check(f"rmgather:{op}", ci,
              pk.fused_gather_count2_rowmajor(op, rmt4, jp, interpret=interpret),
              want_pairs)
        # multi-fold lanes, both layouts
        want_multi = np_multi_counts(mop, rm, idx[k])
        ji = jnp.asarray(idx[k])
        check(f"multi:{mop}:k{k}", ci,
              pk.fused_gather_count_multi(mop, rm4, ji, interpret=interpret), want_multi)
        check(f"rmmulti:{mop}:k{k}", ci,
              pk.fused_gather_count_multi_rowmajor(mop, rmt4, ji, interpret=interpret),
              want_multi)
        # cross-frame fold: a second matrix of other rows over the same
        # slices, operands of both, padded batch (repeats of the first query)
        rb = 8 + 8 * int(xrng.integers(0, 3))
        rm_b = _random_words(xrng, (s, rb, w), int(xrng.choice(_DENSITIES)))
        ka, kb = XKS[int(xrng.integers(len(XKS)))]
        ia = xrng.integers(0, r, size=(B, ka), dtype=np.int32)
        ib = xrng.integers(0, rb, size=(B, kb), dtype=np.int32)
        ia[B // 2:], ib[B // 2:] = ia[0], ib[0]
        want_x = np_xframe_counts(mop, rm, ia, rm_b, ib)
        rm_b4 = jnp.asarray(rm_b.reshape(s, rb, w // 128, 128))
        check(f"xframe:{mop}:k{ka}{kb}", ci,
              pk.fused_gather_count_xframe(mop, rm4, jnp.asarray(ia), rm_b4, jnp.asarray(ib),
                                           interpret=interpret), want_x)
        check(f"dispatch_xframe:{mop}", ci,
              dispatch.gather_count_xframe(mop, rm4, jnp.asarray(ia), rm_b4, jnp.asarray(ib)),
              want_x)
        # TopN candidate scorer
        check("topn", ci,
              pk.fused_topn_counts(rm4, jnp.asarray(src), interpret=interpret),
              np_topn_counts(rm, src))

        # Gram lanes: one-shot, forced scan (per slice), forced word-chunk
        want_gram = np_gram(rm)
        got_one = np.asarray(bw.pair_gram(rmj)).astype(np.int64)
        orig_oneshot, orig_step = bw.GRAM_ONESHOT_BYTES, bw.GRAM_STEP_BYTES
        try:
            bw.GRAM_ONESHOT_BYTES = 1
            got_scan = np.asarray(bw.pair_gram(rm4)).astype(np.int64)
            bw.GRAM_STEP_BYTES = r * (w // 4) * 32
            got_chunk = np.asarray(bw.pair_gram(rm4)).astype(np.int64)
        finally:
            bw.GRAM_ONESHOT_BYTES, bw.GRAM_STEP_BYTES = orig_oneshot, orig_step
        for lane, got_g in (("gram_oneshot", got_one), ("gram_scan", got_scan),
                            ("gram_chunked", got_chunk)):
            if not np.array_equal(got_g, want_gram):
                failures.append(f"{lane}[case {ci}]: gram mismatch")
        # Gram count identities answer every pair op
        check(f"gram_pairs:{op}", ci,
              np.asarray(bw.gram_pair_counts(op, want_gram, pairs)), want_pairs)

        # dispatch-level parity: 3D vs 4D vs numpy, current backend's
        # chosen lane (Pallas on TPU, jnp on CPU CI)
        check(f"dispatch:{op}", ci,
              dispatch.gather_count(op, rmj, jp, allow_gram=False), want_pairs)
        check(f"dispatch4:{op}", ci,
              dispatch.gather_count(op, rm4, jp, allow_gram=False), want_pairs)
        check(f"dispatch_gram:{op}", ci,
              dispatch.gather_count(op, rmj, jp, allow_gram=True), want_pairs)
        check(f"dispatch_multi:{mop}", ci,
              dispatch.gather_count_multi(mop, rm4, ji), want_multi)

    return failures


def lane_names() -> set[str]:
    """The lane identifiers run_lanes covers (for coverage assertions)."""
    lanes = {"count1", "topn", "gram_oneshot", "gram_scan", "gram_chunked"}
    for op in PAIR_OPS:
        lanes |= {f"count2:{op}", f"count2_tiled:{op}", f"resident:{op}",
                  f"gather:{op}", f"rmgather:{op}", f"gram_pairs:{op}",
                  f"dispatch:{op}", f"dispatch4:{op}", f"dispatch_gram:{op}"}
    for mop in MULTI_OPS:
        for k in KS:
            lanes |= {f"multi:{mop}:k{k}", f"rmmulti:{mop}:k{k}"}
        lanes.add(f"dispatch_multi:{mop}")
        lanes |= {f"xframe:{mop}:k{ka}{kb}" for ka, kb in XKS}
        lanes.add(f"dispatch_xframe:{mop}")
    return lanes
