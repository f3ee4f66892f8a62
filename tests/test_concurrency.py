"""Concurrency stress tests — the -race analog (SURVEY §5: the reference
relies on go test -race + mutex-per-object; here threaded stress over the
same object graph must never corrupt state or raise).
"""

import os
import threading

import numpy as np
import pytest

from pilosa_tpu.core.frame import FrameOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.pilosa import SLICE_WIDTH


def test_concurrent_writers_readers_snapshots(tmp_path):
    """4 writer threads + 2 reader threads + a snapshotter against one
    frame: no exceptions, and the final bitmap equals the model."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("f", FrameOptions())
    fr = idx.frame("f")
    e = Executor(h, engine="numpy")

    n_per_thread = 300
    rngs = [np.random.default_rng(seed) for seed in range(4)]
    written: list[set[tuple[int, int]]] = [set() for _ in range(4)]
    errors: list[BaseException] = []
    stop = threading.Event()

    def writer(k):
        try:
            rng = rngs[k]
            for _ in range(n_per_thread):
                r = int(rng.integers(0, 8))
                c = int(rng.integers(0, 2 * SLICE_WIDTH))
                fr.set_bit("standard", r, c)
                written[k].add((r, c))
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    def reader():
        try:
            while not stop.is_set():
                e.execute("i", 'Count(Bitmap(rowID=1, frame="f"))')
                e.execute(
                    "i",
                    'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
                    ' Count(Union(Bitmap(rowID=2, frame="f"), Bitmap(rowID=3, frame="f")))',
                )
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    def snapshotter():
        try:
            while not stop.is_set():
                view = fr.view("standard")      # None until the first write has made it
                for frag in list(view.fragments.values()) if view is not None else ():
                    frag.snapshot()
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    aux = [threading.Thread(target=reader) for _ in range(2)] + [
        threading.Thread(target=snapshotter)
    ]
    for t in threads + aux:
        t.start()
    for t in threads:
        t.join(timeout=120)
    stop.set()
    for t in aux:
        t.join(timeout=30)

    assert not errors, errors
    model: dict[int, set[int]] = {}
    for s in written:
        for r, c in s:
            model.setdefault(r, set()).add(c)
    for r, cols in model.items():
        (bm,) = e.execute("i", f'Count(Bitmap(rowID={r}, frame="f"))')
        assert bm == len(cols), f"row {r}: {bm} != {len(cols)}"
    # Durability: state survives close + reopen (WAL/snapshot interplay
    # under concurrent snapshots must not lose acked writes).
    h.close()
    h2 = Holder(str(tmp_path / "data"))
    h2.open()
    e2 = Executor(h2, engine="numpy")
    for r, cols in model.items():
        (n,) = e2.execute("i", f'Count(Bitmap(rowID={r}, frame="f"))')
        assert n == len(cols), f"after reopen, row {r}: {n} != {len(cols)}"
    h2.close()


def test_concurrent_schema_and_writes(tmp_path):
    """Schema mutations racing writes on other frames must not interfere."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame("stable", FrameOptions())
    fr = idx.frame("stable")
    errors: list[BaseException] = []

    def churn():
        try:
            for k in range(30):
                name = f"tmp{k % 3}"
                try:
                    idx.create_frame(name, FrameOptions())
                except Exception:
                    pass
                try:
                    idx.delete_frame(name)
                except Exception:
                    pass
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    def write():
        try:
            for c in range(500):
                fr.set_bit("standard", 0, c)
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)

    ts = [threading.Thread(target=churn) for _ in range(2)] + [
        threading.Thread(target=write) for _ in range(2)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errors, errors
    assert fr.view("standard").fragment(0).row_count(0) == 500
    h.close()


@pytest.mark.skipif(
    not os.environ.get("PILOSA_TPU_SOAK"),
    reason="heavy soak; run with PILOSA_TPU_SOAK=1",
)
def test_soak_two_engines_with_snapshots(tmp_path):
    """8 writers (16k mixed direct/PQL/time-quantum writes), numpy AND
    jax readers, a snapshot+flush loop — then exact per-row counts and
    durability across reopen."""
    from pilosa_tpu.executor import Executor

    h = Holder(str(tmp_path / "soak"))
    h.open()
    idx = h.create_index("i")
    idx.create_frame(
        "f", FrameOptions(inverse_enabled=True, time_quantum="YM", cache_type="ranked")
    )
    fr = idx.frame("f")
    e = Executor(h, engine="numpy")
    e2 = Executor(h, engine="jax")
    errors: list = []
    stop = threading.Event()
    written: list[set] = [set() for _ in range(8)]

    def writer(k):
        try:
            rng = np.random.default_rng(k)
            for j in range(2000):
                r = int(rng.integers(0, 16))
                c = int(rng.integers(0, 3 * SLICE_WIDTH))
                if j % 37 == 0:
                    e.execute(
                        "i",
                        f'SetBit(rowID={r}, frame="f", columnID={c}, '
                        f'timestamp="2017-0{1 + (j % 9)}-01T00:00")',
                    )
                else:
                    fr.set_bit("standard", r, c)
                written[k].add((r, c))
        except BaseException as x:  # pragma: no cover
            errors.append(("w", k, x))

    def reader(eng):
        try:
            while not stop.is_set():
                eng.execute(
                    "i",
                    'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
                    ' Count(Union(Bitmap(rowID=2, frame="f"), Bitmap(rowID=3, frame="f")))'
                    # 3-operand tree: the multi-fold lane shares the matrix.
                    ' Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))',
                )
                # Fused Range batch: multi-view matrix + cover memo under
                # concurrent timestamped writes (generation invalidation).
                eng.execute(
                    "i",
                    'Count(Range(rowID=0, frame="f", start="2017-01-01T00:00", end="2018-01-01T00:00"))'
                    ' Count(Range(rowID=1, frame="f", start="2017-03-01T00:00", end="2017-06-01T00:00"))',
                )
                eng.execute("i", 'TopN(frame="f", n=3)')
                # TopN(src): the engine-backed candidate scorer against the
                # shared row matrix while writers mutate it.
                eng.execute("i", 'TopN(Bitmap(rowID=4, frame="f"), frame="f", n=3)')
                eng.execute("i", 'Bitmap(columnID=5, frame="f")')
        except BaseException as x:  # pragma: no cover
            errors.append(("r", x))

    def flusher():
        try:
            while not stop.is_set():
                h.flush_caches()
                view = fr.view("standard")  # None until the first write
                if view is None:
                    continue
                for frag in list(view.fragments.values()):
                    frag.snapshot()
        except BaseException as x:  # pragma: no cover
            errors.append(("s", x))

    ws = [threading.Thread(target=writer, args=(k,)) for k in range(8)]
    aux = [threading.Thread(target=reader, args=(eng,)) for eng in (e, e2)] + [
        threading.Thread(target=flusher)
    ]
    for t in ws + aux:
        t.start()
    for t in ws:
        t.join(timeout=300)
    stop.set()
    for t in aux:
        t.join(timeout=60)
    assert not errors, errors[:3]
    model: dict[int, set] = {}
    for s in written:
        for r, c in s:
            model.setdefault(r, set()).add(c)
    for r, cols in model.items():
        assert e.execute("i", f'Count(Bitmap(rowID={r}, frame="f"))') == [len(cols)]
    h.close()
    h2 = Holder(str(tmp_path / "soak"))
    h2.open()
    e3 = Executor(h2, engine="numpy")
    for r, cols in model.items():
        assert e3.execute("i", f'Count(Bitmap(rowID={r}, frame="f"))') == [len(cols)]
    h2.close()


@pytest.mark.parametrize("write_queue", [False, True])
def test_gram_at_scale_reads_stable_under_write_churn(tmp_path, write_queue):
    """Round-4 Gram-at-scale lane under concurrent invalidation: reader
    threads issue fused pair-count batches over rows a writer thread
    NEVER touches, while the writer churns other rows of the same frame
    (every write kills the pool's cache box, forcing Gram rebuilds and
    lane re-decisions mid-stream).  The readers' counts must stay
    exactly constant throughout — a stale Gram, a torn box, or a lane
    race would surface as a changed count.  Runs both executor
    configurations: bare, and the server's serve-queue coalescing
    (merged cross-client batches racing the same invalidation)."""
    rng = np.random.default_rng(3)
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("c").create_frame("f", FrameOptions())
    fr = h.index("c").frame("f")
    n_read_rows, n_churn_rows = 48, 8
    rows = np.repeat(np.arange(n_read_rows, dtype=np.uint64), 12)
    for s in range(2):
        cols = rng.integers(0, SLICE_WIDTH, size=len(rows)).astype(np.uint64) + np.uint64(
            s * SLICE_WIDTH
        )
        fr.import_bits(rows, cols)

    ex = Executor(h, engine="jax", write_queue=write_queue)
    if not getattr(ex.engine, "wants_static_shapes", False):
        pytest.skip("jax engine unavailable")

    def build_q(seed):
        perm = np.random.default_rng(seed).permutation(n_read_rows)
        return " ".join(
            f'Count(Intersect(Bitmap(rowID={int(perm[2 * i])}, frame="f"), '
            f'Bitmap(rowID={int(perm[2 * i + 1])}, frame="f")))'
            for i in range(8)
        )

    qs = [build_q(i) for i in range(6)]
    # Ground truth once, pre-churn, via numpy (the churned rows are
    # disjoint, so these stay correct throughout).
    want = {q: Executor(h, engine="numpy").execute("c", q) for q in qs}
    for q in qs:  # warm: rows resident, Gram builds
        assert ex.execute("c", q) == want[q]

    stop = threading.Event()
    failures: list = []
    writes_done = [0]

    def reader(tid):
        try:
            k = tid
            while not stop.is_set():
                q = qs[k % len(qs)]
                got = ex.execute("c", q)
                if got != want[q]:
                    failures.append((q, got, want[q]))
                    return
                k += 1
        except BaseException as exc:  # raising IS a failure here
            failures.append(("reader raised", exc))

    def writer():
        try:
            wrng = np.random.default_rng(99)
            while not stop.is_set():
                row = n_read_rows + int(wrng.integers(n_churn_rows))
                col = int(wrng.integers(2 * SLICE_WIDTH))
                ex.execute("c", f'SetBit(rowID={row}, frame="f", columnID={col})')
                writes_done[0] += 1
        except BaseException as exc:
            failures.append(("writer raised", exc))

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(4)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    import time

    time.sleep(6.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "thread hung (deadlock?)"
    assert not failures, failures[:2]
    assert writes_done[0] > 0, "writer made no progress: churn never happened"
    h.close()


def test_blocks_walked_while_a_slice_is_written_are_exact(tmp_path):
    """A writer sets bits in one slice while a reader walks blocks over
    all slices (``Executor._walk_block``: the view's columns where a
    fragment's part is at its generation, its dict where not): the
    written slice's part of every block is the fragment's content at one
    of the generations it had between the walk's start and its end, the
    other slices' are what they always held, and the walks took both
    ways and rebuilt the written part (the counters).  Under the lock
    checker (conftest's gate): the order ``core.columns._mu`` ->
    ``core.fragment._mu`` and the lockset of ``ViewColumns._state``."""
    from pilosa_tpu.analysis import lockcheck
    from pilosa_tpu.stats import ExpvarStatsClient

    assert lockcheck.enabled()
    n_slices, rows, written = 4, list(range(12)), 1
    h = Holder(str(tmp_path / "data"))
    h.open()
    h.create_index("i").create_frame("f", FrameOptions())
    fr = h.index("i").frame("f")
    rng = np.random.default_rng(37)
    for r in rows:
        for s in range(n_slices):
            for c in rng.choice(4096, size=1 + r % 3, replace=False):
                fr.set_bit("standard", r, int(s * SLICE_WIDTH + c * 13))
    stats = ExpvarStatsClient()
    ex = Executor(h, engine="numpy", stats=stats)
    frag = h.fragment("i", "f", "standard", written)
    words = SLICE_WIDTH // 32

    def content():
        return np.stack([frag.row_dense(r) for r in rows])

    history = [content()]      # the written fragment's rows, one entry a generation
    errors: list[BaseException] = []
    done = threading.Event()

    def writer():
        try:
            wrng = np.random.default_rng(41)
            for i in range(120):
                with frag._mu:     # the write and its entry are one step for a reader
                    if frag.set_bit(int(wrng.integers(0, len(rows))), int(written * SLICE_WIDTH + wrng.integers(0, 1 << 16))):
                        history.append(content())
                if i % 10 == 9:
                    done.wait(0.03)      # quiet for a few walks: the part is rebuilt
        except BaseException as exc:  # pragma: no cover
            errors.append(exc)
        finally:
            done.set()

    still = {s: np.stack([h.fragment("i", "f", "standard", s).row_dense(r) for r in rows])
             for s in range(n_slices) if s != written}
    t = threading.Thread(target=writer)
    walks = 0
    t.start()
    try:
        while not done.is_set() or walks < 8:
            i0 = len(history)
            block = ex._densify_block("i", "f", "standard", list(range(n_slices)), rows)
            i1 = len(history)
            walks += 1
            for s, planes in still.items():
                assert (block[s] == planes).all()
            assert any((block[written] == history[i]).all() for i in range(i0 - 1, i1)), (i0, i1)
    finally:
        done.set()
        t.join()
    assert not errors and block.shape == (n_slices, len(rows), words)
    final = ex._densify_block("i", "f", "standard", list(range(n_slices)), rows)
    assert (final[written] == content()).all()
    got = stats.snapshot()
    assert got["walk.fragments_snapshot"] > 0 and got["walk.fragments_dict"] > 0
    assert got["walk.snapshot_builds"] > n_slices     # every part once, the written one again
    h.close()
