"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the installed TPU compiler takes (or refuses) each kernel at
W = 32768 and at the shapes the dispatch layer picks for the 64-slice
deployment ``chip_smoke.py`` serves.  Nothing runs, so this says nothing
about answers or speed — it guards what interpret mode cannot see: tiling,
VMEM budgets, device memory, and the collectives of the four-chip mesh.

One file, on purpose: the process that describes the topology loads the
TPU library and keeps it, so under pytest-xdist only the worker that is
given this file may do so — inside a fixture, never at import.
"""

import os

import numpy as np
import pytest

W = 32768
T = W // 128  # a row's words in the tiled 4D form: [.., W/128, 128]
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, with jax's persistent compilation cache
    off around the compiles (an entry written for a described device
    cannot be read back without the chip)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def slice_mesh(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("slice",))


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _compile(fn, args, one_chip, kernel=True, static=(), **static_kw):
    """Lower the jitted ``fn`` for the described chip at ``args`` =
    [(shape, dtype), ...] after its leading ``static`` arguments; returns
    the compiled program after the checks every single-chip case shares."""
    shapes = [_shape(s, d, one_chip) for s, d in args]
    compiled = fn.lower(*static, *shapes, **static_kw).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, f"program needs {total} bytes of a 16 GiB chip"
    return compiled


def _rm(s, r):
    return ((s, r, T, 128), "uint32")


def _rm_t(r, s):  # row-major
    return ((r, s, T, 128), "uint32")


def _ids(*shape):
    return (shape, "int32")


def _p(fn_name, *static, **kw):
    """A kernel of ops/pallas_kernels.py by name with its static arguments
    bound (looked up when the test runs: importing this file imports no
    jax)."""
    return (fn_name, static, kw)


# name -> ((kernel, static args, static kwargs), [(shape, dtype), ...])
_KERNEL_CASES = {
    # -- every kernel at W = 32768 (ISSUE 21's list).  count1 and
    # count2_tiled_shared_b are also what the deployment's single Counts
    # and its per-slice TopN scorer dispatch --
    "count1": (_p("fused_count1"), [((64, W), "uint32")]),
    "count2": (_p("fused_count2", "and"), [((64, W), "uint32")] * 2),
    "count2_tiled_shared_b": (
        _p("fused_count2", "and", tiled=True),
        [((256, T, 128), "uint32"), ((T, 128), "uint32")]),
    "resident_16x64_b256": (_p("fused_resident_count2", "and"), [_rm(16, 64), _ids(256, 2)]),
    # 16 x 1024 rows, B = 1024: the 8 MB VMEM edge of _resident_chunk_sub.
    "resident_16x1024_b1024": (
        _p("fused_resident_count2", "and"), [_rm(16, 1024), _ids(1024, 2)]),
    "resident_1024x64_b1024": (
        _p("fused_resident_count2", "and"), [_rm(1024, 64), _ids(1024, 2)]),
    "gather_16x64": (_p("fused_gather_count2", "and"), [_rm(16, 64), _ids(256, 2)]),
    "gather_4x4096": (_p("fused_gather_count2", "and"), [_rm(4, 4096), _ids(256, 2)]),
    # S = 16: the row-major kernels' buffer cap (dispatch.rowmajor_ok).
    "gather_rowmajor_s16": (
        _p("fused_gather_count2_rowmajor", "and"), [_rm_t(4096, 16), _ids(256, 2)]),
    "multi": (_p("fused_gather_count_multi", "or"), [_rm(16, 64), _ids(64, 4)]),
    "multi_rowmajor_s8_k4": (
        _p("fused_gather_count_multi_rowmajor", "and"), [_rm_t(4096, 8), _ids(64, 4)]),
    "tree": (_p("fused_gather_count_tree"), [_rm(16, 64), _ids(64, 8), _ids(64, 7)]),
    "src_counts": (
        _p("fused_gather_src_counts"), [_rm(16, 64), _ids(256), ((16, T, 128), "uint32")]),
    "topn_counts_16x64": (_p("fused_topn_counts"), [_rm(16, 64), ((16, T, 128), "uint32")]),
    "topn_counts_1024x96": (
        _p("fused_topn_counts"), [_rm(1024, 96), ((1024, T, 128), "uint32")]),
    # -- what dispatch picks for the 64-slice deployment (found by logging
    # the shapes of a full-size rehearsal of chip_smoke.py): one 2 GiB row
    # pool [64, 256, W], pair groups of 32 and 40 --
    "deploy_gather_b32": (_p("fused_gather_count2", "xor"), [_rm(64, 256), _ids(32, 2)]),
    "deploy_gather_b40": (_p("fused_gather_count2", "andnot"), [_rm(64, 256), _ids(40, 2)]),
    # seg64's paged pair reads: 8 pairs an op a body, 1-8 bodies a pass,
    # at the gather dispatch's power-of-four buckets (engine._pow4).
    "seg64_gather_b16": (_p("fused_gather_count2", "or"), [_rm(64, 256), _ids(16, 2)]),
    "seg64_gather_b64": (_p("fused_gather_count2", "and"), [_rm(64, 256), _ids(64, 2)]),
    "deploy_tree_k8": (_p("fused_gather_count_tree"), [_rm(64, 256), _ids(2, 8), _ids(2, 7)]),
    # TopN(src): the all-slice scorer.
    "deploy_topn_all_slice_scorer": (
        _p("fused_gather_src_counts"), [_rm(64, 256), _ids(256), ((64, T, 128), "uint32")]),
    # Count(Range): the multi-view OR over a 128-combo matrix, cover
    # widths 4 and 16, batch chunk 128.
    "deploy_range_or_k4": (_p("fused_gather_count_multi", "or"), [_rm(64, 128), _ids(128, 4)]),
    "deploy_range_or_k16": (_p("fused_gather_count_multi", "or"), [_rm(64, 128), _ids(128, 16)]),
    # Gram repair after a write: dirty rows x all rows, on the one written
    # slice (engine.gram_update_rows).
    "deploy_gram_update": (_p("fused_resident_count2", "and"), [_rm(1, 256), _ids(256, 2)]),
}


@pytest.mark.parametrize("name", sorted(_KERNEL_CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    from pilosa_tpu.ops import pallas_kernels

    (fn_name, static, kw), args = _KERNEL_CASES[name]
    _compile(getattr(pallas_kernels, fn_name), args, one_chip, static=static, **kw)


def test_strategy_predicate_matches_the_cases():
    """The deployment cases above assume dispatch's own choices: a
    256-row pool is gathered, not streamed resident, at these batches;
    the one-slice Gram repair is resident."""
    from pilosa_tpu.ops.dispatch import rowmajor_ok
    from pilosa_tpu.ops.pallas_kernels import resident_strategy

    assert not resident_strategy(256, W, 32) and not resident_strategy(256, W, 40)
    assert resident_strategy(256, W, 256)
    assert rowmajor_ok(16, W) and not rowmajor_ok(64, W)


@pytest.mark.parametrize("n_rows", [64, 256, 1024, 4096])
def test_pair_gram_compiles_for_v5e(n_rows, one_chip):
    """The all-pairs Gram (plain XLA, MXU int8 matmul): 16 slices at the
    row counts up to gram_rows_max, and the deployment's 64 x 256 pool."""
    import jax

    from pilosa_tpu.ops.bitwise import pair_gram

    n_slices = 64 if n_rows == 256 else 16
    _compile(jax.jit(pair_gram), [_rm(n_slices, n_rows)], one_chip, kernel=False)


@pytest.mark.parametrize("cells", [1, 8])
def test_repair_step_updates_the_deployments_pool_in_place(cells, one_chip):
    """The write repair's compiled step (plain XLA) on the deployment's
    2 GiB pool, donated: output aliased to the pool, and no temporary
    near a slice's rows (32 MiB), let alone a copy of the pool."""
    import jax

    from pilosa_tpu.ops.bitwise import repair_planes

    step = jax.jit(repair_planes, static_argnums=3, donate_argnums=0)
    shapes = [_shape(s, d, one_chip) for s, d in
              (_rm(64, 256), _ids(cells, 2), ((cells, T, 128), "uint32"))]
    mem = step.lower(*shapes, 256).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 64 * 256 * W * 4
    assert mem.temp_size_in_bytes < 4 * 1024**2


def test_bulk_build_kernel_compiles_for_v5e(one_chip):
    """The bulk door's sort/segment/scatter pack kernel at the default
    chunk (65536 pairs) into 1024 planes, and at its per-call cap."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.bulk import build

    kern = build._jax_kernel(jnp, jax)
    for groups in (1024, build._GROUPS_PER_CALL):
        n_out = groups * build.WORDS_PER_PLANE
        lowered = kern.lower(_shape((65536,), "int32", one_chip),
                             _shape((65536,), "int32", one_chip), n_out)
        mem = lowered.compile().memory_analysis()
        assert mem.output_size_in_bytes == n_out * 4
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("bucket", [1, 8])
def test_pool_page_in_compiles_and_fits(bucket, donate, one_chip):
    """Paging a miss's chunk into a full 2 GiB pool (rowpool._page_in:
    ``ops.bitwise.set_rows``), one program per power-of-two bucket of a
    chunk's rows (1 .. 8): the first chunk's into a copy (old and new
    matrix both alive: it stays inside the chip next to a second pool
    and a Range matrix), the others' into that copy itself (donated: the
    output is the input's buffer).  No second copy either way, and its
    ops carry the scope's name."""
    import jax

    from pilosa_tpu.ops.bitwise import set_rows

    compiled = _compile(
        jax.jit(set_rows, static_argnames="axis", donate_argnums=(0,) if donate else ()),
        [_rm(64, 256), _ids(bucket), _rm(64, bucket)], one_chip, kernel=False)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total + 3 * 2**31 < HBM_BYTES
    assert mem.temp_size_in_bytes < 2**20
    assert mem.alias_size_in_bytes == (64 * 256 * W * 4 if donate else 0)
    assert "pool.set_rows" in compiled.as_text()


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("words", [4096, "largest"])
def test_pool_sparse_page_in_compiles_and_fits(words, donate, one_chip):
    """The sparse form of the same (``ops.bitwise.set_row_words``: the
    chunk's 8 slots zeroed, then its words scattered), at the bucket
    ``seg64``'s chunks fall into and at the largest one
    (``rowpool.MISS_WORDS_MAX``): what crosses to the device is 16 bytes a
    word, no second copy of the 2 GiB pool is made either way, the
    donating form writes into its input, and its ops carry the scope's
    name."""
    import jax

    from pilosa_tpu.ops.bitwise import set_row_words
    from pilosa_tpu.rowpool import MISS_CHUNK_ROWS, MISS_WORDS_MAX

    n = MISS_WORDS_MAX if words == "largest" else words
    compiled = _compile(
        jax.jit(set_row_words, static_argnames="axis", donate_argnums=(0,) if donate else ()),
        [_rm(64, 256), _ids(MISS_CHUNK_ROWS), _ids(n, 3), ((n,), "uint32")], one_chip,
        kernel=False)
    mem = compiled.memory_analysis()
    pool = 64 * 256 * W * 4
    assert mem.argument_size_in_bytes < pool + 20 * n + 2**16   # a cell lies padded to four words
    assert mem.output_size_in_bytes == pool
    assert mem.temp_size_in_bytes < 2**20
    assert mem.alias_size_in_bytes == (pool if donate else 0)
    assert "pool.set_rows" in compiled.as_text()


def _all_reduced(text: str) -> set:
    """The shapes a compiled program's all-reduce ops return:
    "%name = s32[C,256]{..} all-reduce("."""
    import re

    return set(re.findall(r"= \(?(\w+\[[\d,]*\])[^=]*? all-reduce(?:-start)?\(", text))


def _mesh_args(slice_mesh, n_slices, n_rows, ids_shape):
    from jax.sharding import NamedSharding, PartitionSpec as P

    rm = _shape((n_slices, n_rows, T, 128), "uint32",
                NamedSharding(slice_mesh, P("slice", None, None, None)))
    ids = _shape(ids_shape, "int32", NamedSharding(slice_mesh, P(None, None)))
    return rm, ids


@pytest.mark.parametrize("strategy", ["resident", "gather", "multi"])
def test_sharded_kernel_compiles_for_four_chips(strategy, slice_mesh):
    """The mesh tier: the same Pallas kernels under shard_map over a
    four-device ``slice`` mesh, their per-shard counts merged by an
    all-reduce (parallel/sharded.py)."""
    from pilosa_tpu.parallel import sharded

    if strategy == "multi":
        kernel = sharded._sharded_multi_kernel(slice_mesh, "slice", "or", False, 4)
        args = _mesh_args(slice_mesh, 64, 128, (128, 4))
    else:
        kernel = sharded._sharded_pair_kernel(
            slice_mesh, "slice", "and", strategy == "resident", False, 4)
        args = _mesh_args(slice_mesh, 64, 256, (32, 2))
    compiled = kernel.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # Each device holds a quarter of the matrix, not all of it.
    assert compiled.memory_analysis().argument_size_in_bytes < args[0].size * 4 // 2


@pytest.mark.parametrize(
    "path", ["count", "and_count", "pair_gram", "tree", "scorer", "page_in"])
def test_mesh_engine_path_compiles_for_four_chips(path, topo, slice_mesh):
    """What else the four-chip deployment runs on mesh-wide arrays.  The
    first two are MeshEngine's own reductions: the single-chip Pallas
    dispatch they replace cannot be partitioned (the control below), which
    only real devices ever showed."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.ops import bitwise, pallas_kernels
    from pilosa_tpu.parallel import sharded

    def on(spec, shape, dtype="uint32"):
        return _shape(shape, dtype, NamedSharding(slice_mesh, P(*spec)))

    rm = on(("slice", None, None, None), (64, 256, T, 128))
    stack = on(("slice", None), (64, W))
    if path == "count":
        with pytest.raises(Exception, match="cannot be automatically partitioned"):
            pallas_kernels.fused_count1.lower(stack).compile()
        lowered = MeshEngine(devices=topo.devices)._count_jit.lower(stack)
    elif path == "and_count":
        # rows = matrix[si][pos] of a sharded matrix: replicated, like src.
        lowered = MeshEngine(devices=topo.devices)._and_count_jit.lower(
            on((None, None, None), (256, T, 128)), on((None, None), (T, 128)), True)
    elif path == "pair_gram":
        lowered = jax.jit(bitwise.pair_gram).lower(rm)
    elif path == "tree":
        lowered = sharded._sharded_tree_kernel(slice_mesh, "slice", False, 4).lower(
            rm, on((None, None), (2, 8), "int32"), on((None, None), (2, 7), "int32"))
    elif path == "scorer":
        lowered = sharded._sharded_scorer_kernel(slice_mesh, "slice", 4, 3).lower(
            rm, on((None,), (64,), "int32"), on(("slice", None, None), (64, T, 128)))
    else:
        # A pool miss block uploaded sharded like the pool (MeshEngine.
        # _match_block): the scatter stays local to each device.
        lowered = sharded._sharded_set_rows_kernel(slice_mesh, "slice", 4).lower(
            rm, on((None,), (8,), "int32"), on(("slice", None, None, None), (64, 8, T, 128)))
        text = lowered.compile().as_text()
        assert "all-gather" not in text and "all-to-all" not in text and "all-reduce" not in text
    mem = lowered.compile().memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < HBM_BYTES // 2


@pytest.mark.parametrize("program", [
    "pair_gram", "set_plane_cells_1", "set_plane_cells_8", "set_rows_8", "set_rows_8_donated",
    "set_row_words_4096", "set_row_words_4096_donated",
    "set_row_words_16384", "set_row_words_16384_donated",   # seg256: a chunk's 2,048-10,240 words
    "gram_update_b256", "gram_update_b1024"])
def test_mesh256_pool_programs_stay_on_their_shards(program, topo, slice_mesh, monkeypatch):
    """What the four-chip dashboard deployment (256 slices x 256 slots: a
    2 GiB shard a device) runs on its pool besides paging: the Gram build
    at load, and a write repair's scatter and rank-k recount.  Left to
    GSPMD, the scatter of one cell and the Gram's scan each gathered the
    whole 8 GiB pool onto every device; under shard_map nothing but the
    counts' all-reduce crosses the mesh.  A repair is ONE scatter of all
    its cells (1, 2, 4 or 8: a scatter per group of cells had as many
    copies of the 2 GiB shard in flight as a burst had groups)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.engine import MeshEngine
    from pilosa_tpu.ops import dispatch
    from pilosa_tpu.parallel import sharded

    def on(spec, shape, dtype="uint32"):
        return _shape(shape, dtype, NamedSharding(slice_mesh, P(*spec)))

    rm = on(("slice", None, None, None), (256, 256, T, 128))
    if program == "pair_gram":
        lowered = sharded._sharded_pair_gram_kernel(slice_mesh, "slice", 4).lower(rm)
    elif program.startswith("set_row_words"):   # a miss's chunk of 8 rows as its words
        n_words = int(program.split("_")[3])
        lowered = sharded._sharded_set_row_words_kernel(
            slice_mesh, "slice", 4, program.endswith("donated")).lower(
            rm, on((None,), (8,), "int32"), on((None, None), (n_words, 3), "int32"),
            on((None,), (n_words,)))
    elif program.startswith("set_rows"):   # the fill's paging: a miss's chunk of 8 rows
        k = 8
        lowered = sharded._sharded_set_rows_kernel(
            slice_mesh, "slice", 4, program.endswith("donated")).lower(
            rm, on((None,), (k,), "int32"), on(("slice", None, None, None), (256, k, T, 128)))
    elif program.startswith("set_plane_cells"):
        c = int(program.rsplit("_", 1)[1])
        lowered = sharded._sharded_set_plane_cells_kernel(slice_mesh, "slice", 4).lower(
            rm, on((None, None), (c, 2), "int32"), on((None, None, None), (c, T, 128)))
    else:
        monkeypatch.setattr(dispatch, "use_pallas", lambda: True)  # as on the chip
        b = int(program.rsplit("b", 1)[1])
        lowered = MeshEngine(devices=topo.devices)._gram_counts_program().lower(
            rm, on((None, None), (b, 2), "int32"))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    mem = compiled.memory_analysis()
    shard = 256 * 256 * W * 4 // 4
    block = 8 * 64 * W * 4 if program.startswith("set_rows") else 0   # a device's slices of 8 rows
    assert mem.argument_size_in_bytes < shard + block + 64 * 2**20
    assert mem.temp_size_in_bytes < 2**30
    if program.startswith("set_"):
        assert mem.output_size_in_bytes == shard and "all-reduce" not in text
        assert mem.alias_size_in_bytes == (shard if program.endswith("donated") else 0)
        assert ("pool.set_rows" if program.startswith("set_row") else "pool.set_plane_rows") in text
        if program.startswith("set_row_words"):
            assert mem.temp_size_in_bytes < 2**20 and "collective-permute" not in text
    else:
        assert "all-reduce" in text
        assert ("tpu_custom_call" in text) == program.startswith("gram_update")
        assert ("pool.gram_update" if program.startswith("gram_update") else "pool.pair_gram") in text


@pytest.mark.parametrize("cells", [1, 8])
def test_mesh256_repair_step_updates_every_shard_in_place(cells, slice_mesh):
    """The mesh's write repair (``sharded_repair_planes``: the compiled step
    under shard_map, the pool donated) at the four-chip deployment's shape:
    each device's output is its own 2 GiB shard, aliased; no temporary near
    a slice's rows (32 MiB), no copy of the shard (the composed form's
    ``copy u32[64,256,256,128]``, 6.5 ms a repair on every device), and
    nothing crosses the mesh but the all-reduce of the delta, int32[C, n]."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel import sharded

    def on(spec, shape, dtype="uint32"):
        return _shape(shape, dtype, NamedSharding(slice_mesh, P(*spec)))

    step = sharded._sharded_repair_planes_kernel(slice_mesh, "slice", 4, 256)
    compiled = step.lower(
        on(("slice", None, None, None), (256, 256, T, 128)),
        on((None, None), (cells, 2), "int32"),
        on((None, None, None), (cells, T, 128)),
    ).compile()
    mem = compiled.memory_analysis()
    shard = 256 * 256 * W * 4 // 4
    assert mem.alias_size_in_bytes == shard
    assert mem.temp_size_in_bytes < 8 * 2**20
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    assert _all_reduced(text) == {f"s32[{cells},256]"}
    assert not re.search(r"= u32\[64,256,256,128\][^=]*? copy(?:-start)?\(", text)


@pytest.mark.parametrize("op", ["and", "xor"])
@pytest.mark.parametrize("pairs", [16, 64])
def test_seg256_gather_dispatch_stays_on_its_shards(pairs, op, slice_mesh):
    """A read of the tall frame on four chips (``MeshEngine.gather_count_dev``:
    ``seg256.tall_pairs``' op groups of 16 and 64 pairs over the 256 slots x
    256 slices of the sharded pool): the pair kernel on every device's own
    2 GiB shard under ``shard_map``, nothing gathered, one all-reduce of the
    counts, int32[pairs], and the kernel under the ``gather.count`` name the
    roofline share is read by."""
    from pilosa_tpu.ops.pallas_kernels import resident_strategy
    from pilosa_tpu.parallel import sharded

    rm, ids = _mesh_args(slice_mesh, 256, 256, (pairs, 2))
    kernel = sharded._sharded_pair_kernel(
        slice_mesh, "slice", op, resident_strategy(256, W, pairs), False, 4)
    compiled = kernel.lower(rm, ids).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gather.count" in text
    assert "all-gather" not in text and "all-to-all" not in text
    assert _all_reduced(text) == {f"s32[{pairs}]"}
    mem = compiled.memory_analysis()
    shard = 256 * 256 * W * 4 // 4
    assert mem.argument_size_in_bytes < shard + 2**20 and mem.temp_size_in_bytes < 2**26
