"""Slice-axis GSPMD sharding: the TPU-native mapReduce.

The reference fans a query out with a goroutine per slice and reduces
through channels (executor.go:1115-1244).  The TPU-native equivalent keeps
the whole slice batch as ONE array ``uint32[n_slices, W]`` sharded along a
``slice`` mesh axis:

- elementwise set ops stay local to each shard (no communication),
- ``Count`` reduces with ``lax.psum`` over the slice axis (ICI all-reduce
  with integer SUM — the analog of the coordinator summing per-node
  counts),
- bitmap materialization all-gathers shards (``lax.all_gather``, the
  analog of streaming per-node segment lists back),
- TopN candidate merge all-gathers per-shard (id, count) pairs.

Two styles are provided: explicit ``shard_map`` kernels (collectives
spelled out — used by the dryrun and the benchmarks) and NamedSharding
placement helpers that let GSPMD infer the same collectives for ad-hoc
jnp expressions.
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np


class SliceMesh:
    """A 1-D device mesh over the ``slice`` axis.

    The in-pod replacement for the reference's hash-ring placement
    (cluster.go:198-240): slice i of a stacked batch lives on device
    ``i * n_devices // n_slices`` deterministically via GSPMD row
    sharding; no per-slice routing table is needed.
    """

    AXIS = "slice"

    def __init__(self, devices: Sequence | None = None):
        import jax
        from jax.sharding import Mesh

        self.jax = jax
        devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(np.array(devices), (self.AXIS,))
        self.n_devices = len(devices)

    def sharding(self, *rest_dims_replicated: int):
        """NamedSharding: leading dim split over slice axis, rest replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.AXIS, *([None] * len(rest_dims_replicated))))

    def shard_stack(self, x: np.ndarray):
        """Place [n_slices, ...] with the leading axis sharded over devices."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.AXIS, *([None] * (x.ndim - 1)))
        return self.jax.device_put(x, NamedSharding(self.mesh, spec))

    def replicate(self, x: np.ndarray):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return self.jax.device_put(x, NamedSharding(self.mesh, P(*([None] * x.ndim))))


def _require_divisible(n_slices: int, n_devices: int) -> None:
    if n_slices % n_devices:
        raise ValueError(
            f"slice count {n_slices} must be a multiple of mesh size {n_devices}; "
            "pad the stack with zero slices"
        )


def sharded_count_and(mesh: SliceMesh, a, b):
    """Global |a & b| over a slice-sharded stack: fused local popcount +
    psum over ICI (the Count(Intersect(..)) hot path, distributed)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    @functools.partial(
        jax.shard_map,
        mesh=mesh.mesh,
        in_specs=(P(mesh.AXIS, None), P(mesh.AXIS, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(a_shard, b_shard):
        local = jnp.sum(
            lax.population_count(jnp.bitwise_and(a_shard, b_shard)).astype(jnp.int32)
        )
        return lax.psum(local, mesh.AXIS)

    return jax.jit(kernel)(a, b)


def sharded_union_reduce(mesh: SliceMesh, stacks):
    """OR together several slice-sharded stacks; result stays sharded.

    Union over operands needs NO communication — each shard ORs its own
    rows.  (The cross-*slice* direction is never reduced for bitmaps; a
    bitmap result is naturally slice-partitioned, as in the reference's
    per-slice segment lists.)
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(*xs):
        out = xs[0]
        for x in xs[1:]:
            out = jnp.bitwise_or(out, x)
        return out

    return kernel(*stacks)


def sharded_count_call(mesh: SliceMesh, op: str, a, b):
    """Fused count of an arbitrary pairwise set op over sharded stacks."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def apply_op(x, y):
        if op == "and":
            return jnp.bitwise_and(x, y)
        if op == "or":
            return jnp.bitwise_or(x, y)
        if op == "xor":
            return jnp.bitwise_xor(x, y)
        if op == "andnot":
            return jnp.bitwise_and(x, jnp.bitwise_not(y))
        raise ValueError(op)

    @functools.partial(
        jax.shard_map,
        mesh=mesh.mesh,
        in_specs=(P(mesh.AXIS, None), P(mesh.AXIS, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(a_shard, b_shard):
        local = jnp.sum(lax.population_count(apply_op(a_shard, b_shard)).astype(jnp.int32))
        return lax.psum(local, mesh.AXIS)

    return jax.jit(kernel)(a, b)


@functools.lru_cache(maxsize=None)
def _sharded_pair_kernel(
    mesh_obj, axis: str, op: str, resident: bool, interpret: bool, rm_ndim: int = 3
):
    """Jitted shard_map'd Pallas pair-count kernel, cached per (mesh, op,
    strategy) — a fresh closure per call would retrace + recompile every
    query (jax.Mesh is hashable, so it keys the cache directly).
    ``rm_ndim`` supports both the 3D logical and 4D tiled matrix forms."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.pallas_kernels import (
        fused_gather_count2,
        fused_resident_count2,
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *([None] * (rm_ndim - 1))), P(None, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(rm_shard, prs):
        if resident:
            local = fused_resident_count2(op, rm_shard, prs, interpret=interpret)
        else:
            local = fused_gather_count2(op, rm_shard, prs, interpret=interpret)
        return lax.psum(local, axis)

    return jax.jit(kernel)


@functools.lru_cache(maxsize=None)
def _sharded_multi_kernel(mesh_obj, axis: str, op: str, interpret: bool, rm_ndim: int = 3):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_multi

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *([None] * (rm_ndim - 1))), P(None, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(rm_shard, ids):
        local = fused_gather_count_multi(op, rm_shard, ids, interpret=interpret)
        return lax.psum(local, axis)

    return jax.jit(kernel)


# The Pallas kernels scalar-prefetch the pair ids into SMEM; bound the
# per-dispatch id footprint exactly like single-chip dispatch does
# (observed hard failure at B=4096 on v5e, see ops/dispatch.py).
_SHARDED_BATCH_MAX = 1024


def sharded_gather_count(
    mesh: SliceMesh, op: str, row_matrix, pairs, interpret: bool = False
):
    """Batched pair counts with the HAND-TUNED Pallas kernels under GSPMD.

    ``shard_map`` gives each device its local ``[S/n, R, W]`` block of the
    slice-sharded row matrix; inside the per-shard body the same Pallas
    kernels as single-chip dispatch run (resident or gather strategy by
    the SHARD's shape, shared predicate), and ``lax.psum`` merges the
    per-shard counts over ICI — multi-chip execution keeps the kernel
    tier instead of demoting to the jnp fallback.  ``interpret=True``
    runs the kernels in Pallas interpret mode (CPU meshes: tests and the
    driver dryrun).

    Requires the slice axis divisible by the mesh; callers fall back to
    the GSPMD-partitioned jnp form otherwise.  Returns the psummed counts
    as a replicated device array: nothing is fetched here (the mesh
    engine's ``_dev`` forms hand it on as it is).
    """
    import jax.numpy as jnp

    from pilosa_tpu.ops.pallas_kernels import resident_strategy, rm_words

    n_slices, n_rows = row_matrix.shape[:2]
    w = rm_words(row_matrix)
    _require_divisible(n_slices, mesh.n_devices)
    b = pairs.shape[0]
    if b > _SHARDED_BATCH_MAX:
        return jnp.concatenate(
            [
                sharded_gather_count(
                    mesh, op, row_matrix, pairs[i : i + _SHARDED_BATCH_MAX], interpret
                )
                for i in range(0, b, _SHARDED_BATCH_MAX)
            ]
        )
    kernel = _sharded_pair_kernel(
        mesh.mesh, mesh.AXIS, op, resident_strategy(n_rows, w, b), interpret,
        row_matrix.ndim,
    )
    return kernel(row_matrix, pairs)


def sharded_gather_count_multi(
    mesh: SliceMesh, op: str, row_matrix, idx, interpret: bool = False
):
    """Multi-operand fold counts (N-ary Intersect/Union/Difference, Range
    covers) through the Pallas multi-gather kernel per shard + psum.
    Chunks the batch so prefetched ids stay inside the SMEM budget."""
    import jax.numpy as jnp

    n_slices = row_matrix.shape[0]
    _require_divisible(n_slices, mesh.n_devices)
    b, k = idx.shape
    chunk = max(1, (2 * _SHARDED_BATCH_MAX) // max(1, k))
    if b > chunk:
        return jnp.concatenate(
            [
                sharded_gather_count_multi(
                    mesh, op, row_matrix, idx[i : i + chunk], interpret
                )
                for i in range(0, b, chunk)
            ]
        )
    kernel = _sharded_multi_kernel(mesh.mesh, mesh.AXIS, op, interpret, row_matrix.ndim)
    return kernel(row_matrix, idx)


@functools.lru_cache(maxsize=None)
def _sharded_xframe_kernel(mesh_obj, axis: str, op: str, interpret: bool, rm_ndim: int = 3):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_xframe

    rm_spec = P(axis, *([None] * (rm_ndim - 1)))

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(rm_spec, P(None, None), rm_spec, P(None, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(a_shard, ids_a, b_shard, ids_b):
        local = fused_gather_count_xframe(op, a_shard, ids_a, b_shard, ids_b, interpret=interpret)
        return lax.psum(local, axis)

    return jax.jit(kernel)


def sharded_gather_count_xframe(
    mesh: SliceMesh, op: str, rm_a, idx_a, rm_b, idx_b, interpret: bool = False
):
    """Cross-frame fold counts: two frames' row matrices, both split along
    the slice axis alike (every frame of a slice lives on the device that
    owns the slice), the Pallas cross-frame fold on each shard, one psum.
    Chunks the batch so both id tables together stay inside the SMEM
    budget, as ``sharded_gather_count_multi`` does."""
    import jax.numpy as jnp

    n_slices = rm_a.shape[0]
    _require_divisible(n_slices, mesh.n_devices)
    b = idx_a.shape[0]
    chunk = max(1, (2 * _SHARDED_BATCH_MAX) // (idx_a.shape[1] + idx_b.shape[1]))
    if b > chunk:
        return jnp.concatenate(
            [
                sharded_gather_count_xframe(
                    mesh, op, rm_a, idx_a[i : i + chunk], rm_b, idx_b[i : i + chunk], interpret
                )
                for i in range(0, b, chunk)
            ]
        )
    kernel = _sharded_xframe_kernel(mesh.mesh, mesh.AXIS, op, interpret, rm_a.ndim)
    return kernel(rm_a, idx_a, rm_b, idx_b)


@functools.lru_cache(maxsize=None)
def _sharded_tree_kernel(mesh_obj, axis: str, interpret: bool, rm_ndim: int = 3):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.pallas_kernels import fused_gather_count_tree

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *([None] * (rm_ndim - 1))), P(None, None), P(None, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(rm_shard, leaves, opc):
        local = fused_gather_count_tree(rm_shard, leaves, opc, interpret=interpret)
        return lax.psum(local, axis)

    return jax.jit(kernel)


def sharded_gather_count_tree(
    mesh: SliceMesh, row_matrix, leaves, opc, interpret: bool = False
):
    """Arbitrary nested tree counts through the Pallas tree kernel per
    shard + psum (the multi-chip form of dispatch.gather_count_tree —
    executor.go:261-276 fused over the mesh).  Chunks the batch so the
    prefetched leaf ids + opcodes stay inside the SMEM budget."""
    import jax.numpy as jnp

    n_slices = row_matrix.shape[0]
    _require_divisible(n_slices, mesh.n_devices)
    b, k = leaves.shape
    chunk = max(1, (2 * _SHARDED_BATCH_MAX) // max(1, 2 * k - 1))
    if b > chunk:
        return jnp.concatenate(
            [
                sharded_gather_count_tree(
                    mesh, row_matrix, leaves[i : i + chunk], opc[i : i + chunk],
                    interpret,
                )
                for i in range(0, b, chunk)
            ]
        )
    kernel = _sharded_tree_kernel(mesh.mesh, mesh.AXIS, interpret, row_matrix.ndim)
    return kernel(row_matrix, leaves, opc)


@functools.lru_cache(maxsize=None)
def _sharded_set_plane_cells_kernel(mesh_obj, axis: str, rm_ndim: int):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.bitwise import set_plane_cells

    rest = [None] * (rm_ndim - 1)

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *rest), P(None, None), P(None, *rest[1:])),
        out_specs=P(axis, *rest),
        check_vma=False,
    )
    def set_plane_cells_shards(rm_shard, cells, planes):
        return set_plane_cells(
            rm_shard, cells, planes, first_slice=lax.axis_index(axis) * rm_shard.shape[0]
        )

    return jax.jit(set_plane_cells_shards)


def sharded_set_plane_cells(mesh: SliceMesh, row_matrix, cells, planes):
    """``ops.bitwise.set_plane_cells`` on a slice-sharded pool matrix: every
    device writes the cells whose slice it holds into a copy of its own
    shard and drops the rest - no communication, and the result is born
    with the matrix's sharding.  (Left to GSPMD, the scatter of one cell
    gathers the WHOLE pool onto every device: 8 GiB a device at 256 slices
    x 256 slots, found by compiling for a described v5e:2x2.)  ``cells``:
    int32[C, 2] of (slice, slot); ``planes``: [C, ...words], replicated."""
    _require_divisible(row_matrix.shape[0], mesh.n_devices)
    kernel = _sharded_set_plane_cells_kernel(mesh.mesh, mesh.AXIS, row_matrix.ndim)
    return kernel(row_matrix, cells, planes)


@functools.lru_cache(maxsize=None)
def _sharded_set_rows_kernel(mesh_obj, axis: str, rm_ndim: int, donate: bool = False):
    import jax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.bitwise import set_rows

    rest = [None] * (rm_ndim - 1)

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *rest), P(None), P(axis, *rest)),
        out_specs=P(axis, *rest),
        check_vma=False,
    )
    def set_rows_shards(rm_shard, slots, block_shard):
        return set_rows(rm_shard, slots, block_shard)

    return jax.jit(set_rows_shards, donate_argnums=(0,) if donate else ())


def sharded_set_rows(mesh: SliceMesh, row_matrix, slots, block, donate: bool = False):
    """``ops.bitwise.set_rows`` on a slice-sharded pool matrix: a pool
    miss's block ``[S, k, ...]`` arrives sharded like the matrix, and every
    device scatters its own slices' rows into a copy of its own shard (with
    ``donate`` into the shard itself) - no communication, the result born
    with the matrix's sharding (GSPMD is not asked: it gathered the whole
    pool for ``set_plane_cells``)."""
    _require_divisible(row_matrix.shape[0], mesh.n_devices)
    kernel = _sharded_set_rows_kernel(mesh.mesh, mesh.AXIS, row_matrix.ndim, donate)
    return kernel(row_matrix, slots, block)


@functools.lru_cache(maxsize=None)
def _sharded_set_row_words_kernel(
    mesh_obj, axis: str, rm_ndim: int, n_slots: int, donate: bool = False
):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.bitwise import set_row_word_columns

    rest = [None] * (rm_ndim - 1)

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *rest), P(axis, None)),
        out_specs=P(axis, *rest),
        check_vma=False,
    )
    def set_row_words_shards(rm_shard, share):
        # This device's row of the packed chunk: its own words' local
        # slices, slots, words and values' bits, B each, then the slots.
        share = share[0]
        b = (share.shape[0] - n_slots) // 4
        si, slot, w, bits = (share[i * b : (i + 1) * b] for i in range(4))
        return set_row_word_columns(
            rm_shard, share[4 * b :], si, slot, w, lax.bitcast_convert_type(bits, jnp.uint32)
        )

    return jax.jit(set_row_words_shards, donate_argnums=(0,) if donate else ())


def sharded_set_row_words(
    mesh: SliceMesh, row_matrix, shares, n_slots: int, donate: bool = False
):
    """``ops.bitwise.set_row_words`` on a slice-sharded pool matrix: the
    sparse form of a pool miss.  ``shares``: int32[devices, 4 * B +
    n_slots] sharded on the slice axis (``engine._word_shares``), so each
    device receives its own row alone - the words of its own slices as
    four columns of ``B`` (slice less the shard's first, slot, word,
    value's bits; padded with -1, -1, -1, 0), then the chunk's
    ``n_slots`` slots.  Every device
    zeroes the slots' rows in a copy of its own shard (with ``donate`` in
    the shard itself) and writes its own words, B of them and not the
    chunk's - no communication, the result born with the matrix's
    sharding."""
    _require_divisible(row_matrix.shape[0], mesh.n_devices)
    kernel = _sharded_set_row_words_kernel(
        mesh.mesh, mesh.AXIS, row_matrix.ndim, n_slots, donate
    )
    return kernel(row_matrix, shares)


@functools.lru_cache(maxsize=None)
def _sharded_repair_planes_kernel(mesh_obj, axis: str, rm_ndim: int, n: int):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.bitwise import repair_planes

    rest = [None] * (rm_ndim - 1)

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *rest), P(None, None), P(None, *rest[1:])),
        out_specs=(P(axis, *rest), P()),
        check_vma=False,
    )
    def repair_planes_shards(rm_shard, cells, planes):
        # This device's cells to the front, in the burst's order (the
        # step runs a prefix, and the deltas of one slice telescope in
        # that order); the others, and a bucket's tail, become (-1, -1).
        si = cells[:, 0] - lax.axis_index(axis) * rm_shard.shape[0]
        mine = (si >= 0) & (si < rm_shard.shape[0])
        order = jnp.argsort(~mine, stable=True)
        own = jnp.where(mine[:, None], jnp.stack([si, cells[:, 1]], axis=1), -1)[order]
        rm_shard, delta = repair_planes(rm_shard, own, planes[order], n)
        # Back to the burst's order; the rows of other devices' cells are
        # zero here, so the psum hands every device the whole delta.
        return rm_shard, lax.psum(jnp.zeros_like(delta).at[order].set(delta), axis)

    return jax.jit(repair_planes_shards, donate_argnums=0)


def sharded_repair_planes(mesh: SliceMesh, row_matrix, cells, planes, n: int):
    """``ops.bitwise.repair_planes`` on a slice-sharded pool matrix,
    DONATED: every device runs the step on its own shard for the cells
    whose slice it holds - per cell one pass over that slice's ``n`` rows
    and one plane written into the shard's own buffer, no copy of the
    shard and no pass over it - and a psum of int32[C, n] gives every
    device the whole delta (slices are disjoint column ranges, so the
    devices' deltas add; a cell's row is zero on every device but one).
    ``cells``: int32[C, 2] of (slice, slot), a bucket's tail (-1, -1);
    ``planes``: [C, ...words]; both replicated.  Returns ``(row_matrix,
    delta)``: the matrix with its sharding, the delta replicated."""
    _require_divisible(row_matrix.shape[0], mesh.n_devices)
    kernel = _sharded_repair_planes_kernel(mesh.mesh, mesh.AXIS, row_matrix.ndim, n)
    return kernel(row_matrix, cells, planes)


@functools.lru_cache(maxsize=None)
def _sharded_pair_gram_kernel(mesh_obj, axis: str, rm_ndim: int):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.bitwise import pair_gram

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(P(axis, *([None] * (rm_ndim - 1))),),
        out_specs=P(),
        check_vma=False,
    )
    def pair_gram_shards(rm_shard):
        with jax.named_scope("pool.pair_gram"):
            return lax.psum(pair_gram(rm_shard), axis)

    return jax.jit(pair_gram_shards)


def sharded_pair_gram(mesh: SliceMesh, row_matrix):
    """The all-pairs AND-count Gram of a slice-sharded matrix: slices are
    disjoint bit ranges, so every device builds the Gram of its own slices
    (``ops.bitwise.pair_gram``, one streamed pass of MXU work) and a psum
    adds them.  (Left to GSPMD, ``pair_gram``'s scan indexes the sharded
    axis and the partitioner gathers the whole matrix onto every device
    first.)  Returns int32[R, R], replicated."""
    _require_divisible(row_matrix.shape[0], mesh.n_devices)
    return _sharded_pair_gram_kernel(mesh.mesh, mesh.AXIS, row_matrix.ndim)(row_matrix)


@functools.lru_cache(maxsize=None)
def _sharded_scorer_kernel(mesh_obj, axis: str, rm_ndim: int, src_ndim: int):
    """Jitted shard_map'd scorer kernel, cached per (mesh, layouts) — a
    fresh closure per call would retrace + recompile every candidate
    chunk (same policy as _sharded_pair_kernel above)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        in_specs=(
            P(axis, *([None] * (rm_ndim - 1))),
            P(None),
            P(axis, *([None] * (src_ndim - 1))),
        ),
        out_specs=P(axis, None),
        check_vma=False,
    )
    def kernel(rm, idv, s):
        g = jnp.take(rm, idv, axis=1)  # [s_local, k, ...words]
        inter = g & s[:, None]
        axes = tuple(range(2, g.ndim))
        return jnp.sum(lax.population_count(inter).astype(jnp.int32), axis=axes)

    return jax.jit(kernel)


def sharded_scorer_counts(mesh: SliceMesh, rows, ids, src, chunk: int = 64):
    """Per-(slice, candidate) intersection counts for TopN scoring on a
    slice-sharded row matrix — the multi-host-safe form of the engine row
    scorer (eagerly indexing ``matrix[si]`` only works when every shard
    is process-addressable).

    rows: uint32[S, cap, ...] sharded on slice (3D logical or 4D tiled);
    ids: int32[K] replicated slot ids; src: [S, ...] sharded, same word
    layout as rows.  Returns int32[S, K] sharded on slice — each rank
    fetches it with an allgather-aware fetch and feeds its per-fragment
    heap logic.  The gather transient is bounded by ``chunk`` candidates
    per dispatch.
    """
    import jax.numpy as jnp

    _require_divisible(rows.shape[0], mesh.n_devices)
    kernel = _sharded_scorer_kernel(mesh.mesh, mesh.AXIS, rows.ndim, src.ndim)
    k = ids.shape[0]
    if k > chunk:
        return jnp.concatenate(
            [kernel(rows, ids[i : i + chunk], src) for i in range(0, k, chunk)],
            axis=1,
        )
    return kernel(rows, ids, src)


def sharded_topn_counts(mesh: SliceMesh, rows, src):
    """Per-row global intersection counts for TopN over a sharded slice axis.

    rows: uint32[n_slices, n_rows, W] sharded on slice; src: uint32[n_slices, W]
    sharded on slice.  Returns int32[n_rows] — each row's count summed over
    every slice (psum over ICI), ready for host-side heap/threshold logic.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    @functools.partial(
        jax.shard_map,
        mesh=mesh.mesh,
        in_specs=(P(mesh.AXIS, None, None), P(mesh.AXIS, None)),
        out_specs=P(),
        check_vma=False,
    )
    def kernel(rows_shard, src_shard):
        inter = jnp.bitwise_and(rows_shard, src_shard[:, None, :])
        local = jnp.sum(lax.population_count(inter).astype(jnp.int32), axis=(0, 2))
        return lax.psum(local, mesh.AXIS)

    return jax.jit(kernel)(rows, src)


# ---------------------------------------------------------------------------
# Replica groups: 2-D (slice x replica) mesh
# ---------------------------------------------------------------------------

class ReplicaMesh(SliceMesh):
    """A 2-D device mesh (slice x replica): the ReplicaN analog.

    The reference assigns each partition to ``ReplicaN`` consecutive
    ring nodes (cluster.go:220-240) so every slice has replica_n owners.
    The TPU-native form: devices arranged as a 2-D mesh whose ``slice``
    axis shards the bitmap stacks and whose ``replica`` axis holds full
    copies — placement is the sharding annotation, no routing table.

    What the replicas buy, TPU-first:
    - fault tolerance: either replica group holds the full index; a
      failed host's job restarts against the surviving group (the
      in-pod analog of query-time replica failover,
      executor.go:1147-1159);
    - READ parallelism: a query batch splits across the replica axis —
      each replica group answers its sub-batch against its full copy,
      psum runs over ``slice`` WITHIN each group (XLA emits the
      all-reduce with replica-group participant lists), and the batch
      reassembles over the ``replica`` axis.  replica_n groups serve
      replica_n x the read throughput, the same reason the reference
      fans reads over any owner node.

    Multi-pod: pass ``hybrid=True`` to lay the replica axis across DCN
    (``mesh_utils.create_hybrid_device_mesh``) so the slice-axis psum
    rides ICI inside each pod and only rare cross-replica traffic
    crosses DCN.  Single-pod/virtual meshes use a plain 2-D reshape.
    """

    REPLICA_AXIS = "replica"

    def __init__(self, n_replicas: int = 2, devices: Sequence | None = None,
                 hybrid: bool = False):
        import jax
        from jax.sharding import Mesh

        self.jax = jax
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) % n_replicas:
            raise ValueError(
                f"{len(devices)} devices not divisible into {n_replicas} replica groups"
            )
        n_slice = len(devices) // n_replicas
        if hybrid:
            from jax.experimental import mesh_utils

            # DCN granules (pods) are the OUTER blocks of the returned
            # array: flat = [pod0 devices..., pod1 devices...].  Each pod
            # is one replica group, so pods index the REPLICA axis —
            # reshape (n_replicas, n_slice) then transpose, keeping the
            # slice-axis psum on ICI within a pod and only cross-replica
            # traffic on DCN.
            try:
                dev_array = np.asarray(
                    mesh_utils.create_hybrid_device_mesh(
                        (n_slice,), (n_replicas,), devices=devices,
                    )
                ).reshape(n_replicas, n_slice).T
            # analysis-ok: exception-hygiene: topology probe; the guarded fallback below is the point (mesh.hybrid records which was built)
            except Exception:  # noqa: BLE001 — no DCN topology on this host
                # Hosts without a DCN topology (single-process CPU runs,
                # one-host TPU boxes: every device is one granule, and
                # create_hybrid_device_mesh needs >= n_replicas of them)
                # fall back to a plain create_device_mesh reshape, so a
                # hybrid request never needs real multi-pod hardware.
                hybrid = False
                dev_array = self._flat_2d(n_replicas, n_slice, devices)
        else:
            dev_array = self._flat_2d(n_replicas, n_slice, devices)
        self.hybrid = hybrid  # the layout actually BUILT, post-fallback
        self.mesh = Mesh(dev_array, (self.AXIS, self.REPLICA_AXIS))
        # SliceMesh API compat: helpers divide the slice axis by this.
        self.n_devices = n_slice
        self.n_replicas = n_replicas

    @staticmethod
    def _flat_2d(n_replicas: int, n_slice: int, devices) -> np.ndarray:
        """(slice, replica) layout without DCN awareness: consecutive
        (ICI-adjacent) devices run along the slice axis within one
        replica group.  ``create_device_mesh`` keeps physical adjacency
        on real TPU topologies; virtual/CPU device lists (no coords)
        fall through to a plain reshape with the same orientation."""
        try:
            from jax.experimental import mesh_utils

            return np.asarray(
                mesh_utils.create_device_mesh(
                    (n_replicas, n_slice), devices=devices
                )
            ).T
        # analysis-ok: exception-hygiene: topology probe; plain reshape is the documented fallback
        except Exception:  # noqa: BLE001 — virtual devices without topology
            return np.array(devices).reshape(n_replicas, n_slice).T


def replica_gather_count(mesh: ReplicaMesh, op: str, row_matrix, pairs,
                         interpret: bool = False):
    """Batched pair counts on a (slice x replica) mesh with the batch
    SPLIT over the replica axis: each replica group runs the Pallas
    kernel on its sub-batch against its full slice-sharded copy, psum
    reduces over ``slice`` within the group (replica-group all-reduce),
    and the result reassembles along ``replica``.

    pairs: int32[B, 2] with B divisible by n_replicas.  Returns int32[B].
    """
    from pilosa_tpu.ops.pallas_kernels import resident_strategy, rm_words

    n_slices, n_rows = row_matrix.shape[:2]
    _require_divisible(n_slices, mesh.n_devices)
    b = pairs.shape[0]
    if b % mesh.n_replicas:
        raise ValueError(f"batch {b} not divisible by {mesh.n_replicas} replicas")
    kernel = _replica_pair_kernel(
        mesh.mesh, mesh.AXIS, mesh.REPLICA_AXIS, op,
        resident_strategy(n_rows, rm_words(row_matrix), b // mesh.n_replicas),
        interpret, row_matrix.ndim,
    )
    return kernel(row_matrix, pairs)


@functools.lru_cache(maxsize=None)
def _replica_pair_kernel(mesh_obj, slice_axis: str, replica_axis: str, op: str,
                         resident: bool, interpret: bool, rm_ndim: int):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops.pallas_kernels import (
        fused_gather_count2,
        fused_resident_count2,
    )

    @functools.partial(
        jax.shard_map,
        mesh=mesh_obj,
        # Matrix: sharded over slice, REPLICATED over replica (each
        # group holds a full copy).  Pairs: split over replica.
        in_specs=(P(slice_axis, *([None] * (rm_ndim - 1))), P(replica_axis, None)),
        out_specs=P(replica_axis),
        check_vma=False,
    )
    def kernel(rm_shard, prs_shard):
        if resident:
            local = fused_resident_count2(op, rm_shard, prs_shard, interpret=interpret)
        else:
            local = fused_gather_count2(op, rm_shard, prs_shard, interpret=interpret)
        # Replica-group reduce: psum over the slice axis only — XLA emits
        # the all-reduce with one participant group per replica.
        return lax.psum(local, slice_axis)

    return jax.jit(kernel)
