"""Worker process for tests/test_multihost.py: joins a 2-process gloo
mesh, builds a global slice stack from process-local shards, runs the
sharded kernels, and prints verifiable results.

Run: python tests/multihost_worker.py <coordinator> <num_procs> <pid>
"""

import json
import sys


def main() -> int:
    coordinator, num_procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    import numpy as np

    from pilosa_tpu.parallel.multihost import MultiHostSliceMesh, init_multihost

    init_multihost(coordinator, num_procs, pid, local_device_count=2)

    import jax

    from pilosa_tpu.ops import bitwise as bw
    from pilosa_tpu.parallel import sharded_count_and, sharded_union_reduce

    mesh = MultiHostSliceMesh()
    n_slices, W = 8, 256
    rng = np.random.default_rng(42)  # same seed everywhere: shared ground truth
    a_full = rng.integers(0, 1 << 32, size=(n_slices, W), dtype=np.uint32)
    b_full = rng.integers(0, 1 << 32, size=(n_slices, W), dtype=np.uint32)

    owned = mesh.owned_slices(n_slices)
    a = mesh.shard_stack_local({s: a_full[s] for s in owned}, n_slices, (W,))
    b = mesh.shard_stack_local({s: b_full[s] for s in owned}, n_slices, (W,))

    got_count = int(sharded_count_and(mesh, a, b))
    want_count = sum(bw.np_count_and(a_full[i], b_full[i]) for i in range(n_slices))

    union = mesh.fetch_global(sharded_union_reduce(mesh, [a, b]))
    union_ok = bool(np.array_equal(union, a_full | b_full))

    # Full product stack in SPMD lockstep: every process holds the same
    # Holder data and runs the SAME PQL through a MeshEngine whose slice
    # axis spans the GLOBAL device list — host work is replicated, device
    # work is sharded, counts psum across processes.  The multi-host
    # analog of the reference's coordinator+peers, with ICI/DCN
    # collectives instead of protobuf-over-TCP reduces.
    import tempfile

    from pilosa_tpu.core.frame import FrameOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pilosa import SLICE_WIDTH

    with tempfile.TemporaryDirectory() as d:
        h = Holder(d)
        h.open()
        idx = h.create_index("g")
        idx.create_frame("f", FrameOptions())
        fr = idx.frame("f")
        for r in range(3):
            for s in range(4):
                fr.set_bit("standard", r, s * SLICE_WIDTH + 7 + r)
                fr.set_bit("standard", r, s * SLICE_WIDTH + 99)
        e_np = Executor(h, engine="numpy")
        e_mesh = Executor(h, engine=MeshEngine(devices=jax.devices()))
        q = (
            'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f"))) '
            'Count(Union(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))'
        )
        mesh_res = e_mesh.execute("g", q)
        exec_ok = mesh_res == e_np.execute("g", q)

        # TopN(src): the ENGINE scorer must run on a multi-process mesh
        # (shard_map'd all-slice scoring + allgather), not the host
        # fallback — round-2 verdict item 7.
        qt = 'TopN(Bitmap(rowID=0, frame="f"), frame="f", n=3)'
        topn_parity_ok = e_mesh.execute("g", qt) == e_np.execute("g", qt)
        frags = [h.fragment("g", "f", "standard", s) for s in range(4)]
        src_b = [f.row_dense(0) for f in frags]
        scorer_for = e_mesh._topn_scorer_factory("g", "f", list(range(4)), src_b)
        sc = scorer_for(1, src_b[1])
        scorer_engaged = sc is not None
        topn_scorer_ok = False
        if scorer_engaged:
            got = [int(v) for v in sc([0, 1, 2])]
            want = [
                int(bw.np_count_and(frags[1].row_dense(r), src_b[1]))
                for r in range(3)
            ]
            topn_scorer_ok = got == want
        h.close()

    print(
        json.dumps(
            {
                "pid": pid,
                "global_devices": jax.device_count(),
                "local_devices": jax.local_device_count(),
                "owned": owned,
                "count": got_count,
                "count_ok": got_count == want_count,
                "union_ok": union_ok,
                "exec_results": [int(v) for v in mesh_res],
                "exec_ok": bool(exec_ok),
                "topn_parity_ok": bool(topn_parity_ok),
                "topn_scorer_engaged": bool(scorer_engaged),
                "topn_scorer_ok": bool(topn_scorer_ok),
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
