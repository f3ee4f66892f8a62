"""Multi-host slice meshes: one GLOBAL device mesh spanning processes.

The reference scales past one node with an HTTP+protobuf data plane and a
hash ring (cluster.go, executor.go:1009-1091).  That path survives here
for heterogeneous clusters (pilosa_tpu/cluster.py), but homogeneous TPU
pods get the TPU-native alternative: every host joins one
``jax.distributed`` job, the slice axis shards over the GLOBAL device
list, and XLA emits the cross-host collectives — psum riding ICI within
a pod slice and DCN between pods — where the reference serialized
protobuf over TCP.  The coordinator/worker topology mirrors the
reference's cluster config (a coordinator address + a static host list,
config.go:37-64); there is no gossip because membership is the jax
distributed runtime's job.

All SliceMesh kernels (sharded.py) work unchanged on a multi-host mesh:
they only see a Mesh and globally-sharded arrays.  What this module adds
is the process boundary: initialization, and building global arrays from
process-LOCAL slice shards (each host densifies only the fragments it
owns — the analog of per-node fragment ownership, cluster.go:243-254).

Tested with real multi-process meshes over the gloo CPU backend in
tests/test_multihost.py; on TPU pods ``jax.distributed.initialize()``
discovers the topology from the TPU runtime instead.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from pilosa_tpu.parallel.sharded import ReplicaMesh, SliceMesh, _require_divisible


def init_multihost(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_count: Optional[int] = None,
) -> None:
    """Join this process to a multi-host jax job.

    On TPU pods call with no arguments (topology comes from the runtime).
    On CPU (tests, dev rigs) pass coordinator/num_processes/process_id
    and optionally local_device_count virtual devices per process; the
    gloo collectives backend carries the cross-process reductions.

    Must run before any jax computation initializes a backend.
    """
    import jax

    if local_device_count is not None:
        # A CPU dev rig: N virtual devices per process, gloo collectives
        # between processes.  jax may already be imported (the config
        # keys are read at backend init, which by this function's
        # contract hasn't happened), so set the config, not just the env.
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", local_device_count)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if coordinator is None:
        jax.distributed.initialize()
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )


class MultiHostSliceMesh(SliceMesh):
    """SliceMesh over the GLOBAL device list of a jax.distributed job.

    Inherits every kernel-facing behavior; adds construction of global
    slice stacks from per-process local data.  Slice ownership is
    deterministic and contiguous: device k owns slices
    [k*per_dev, (k+1)*per_dev) of the stack, so host ownership is the
    devices it holds — the mesh replaces the reference's
    jump-consistent-hash ring (cluster.go:220-240) inside the job.
    """

    def __init__(self, devices: Sequence | None = None):
        import jax

        super().__init__(devices if devices is not None else jax.devices())
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()

    def _local_device_ranges(self, n_slices: int) -> list[tuple[object, range]]:
        """(local device, owned global slice range) pairs — the ONE place
        the ownership rule lives.  Local devices outside an explicit mesh
        device subset own nothing (skipped, not an error)."""
        import jax

        _require_divisible(n_slices, self.n_devices)
        per_dev = n_slices // self.n_devices
        positions = {d: k for k, d in enumerate(self.mesh.devices.flat)}
        out = []
        for d in jax.local_devices():
            k = positions.get(d)
            if k is not None:
                out.append((d, range(k * per_dev, (k + 1) * per_dev)))
        return out

    def owned_slices(self, n_slices: int) -> list[int]:
        """Global slice indices whose shards live on THIS process."""
        return [s for _, r in self._local_device_ranges(n_slices) for s in r]

    def shard_stack_local(
        self,
        local_data: dict[int, np.ndarray],
        n_slices: int,
        row_shape: tuple,
        dtype=np.uint32,
    ):
        """Build a global [n_slices, *row_shape] array from THIS process's
        slices only (missing owned slices are zero).

        ``local_data`` maps global slice index -> np.ndarray of
        ``row_shape``; only slices owned by this process are consulted.
        No host ever materializes the full stack — the multi-host analog
        of each node opening only its own fragments (holder.go:73-121).
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.AXIS, *([None] * len(row_shape)))
        sharding = NamedSharding(self.mesh, spec)
        # dtype is an explicit parameter (not inferred from local_data): a
        # host owning only empty slices must still agree with its peers on
        # the global aval, or cross-process collectives fail.
        for v in local_data.values():
            if v.dtype != dtype:
                raise TypeError(f"local slice dtype {v.dtype} != declared {np.dtype(dtype)}")
        shards = []
        for d, owned in self._local_device_ranges(n_slices):
            block = np.zeros((len(owned), *row_shape), dtype=dtype)
            for j, s in enumerate(owned):
                if s in local_data:
                    block[j] = local_data[s]
            shards.append(jax.device_put(block, d))
        return jax.make_array_from_single_device_arrays(
            (n_slices, *row_shape), sharding, shards
        )

    def fetch_global(self, arr) -> np.ndarray:
        """Gather a globally-sharded array to every host (DCN all-gather;
        the analog of streaming result segments back to the coordinator)."""
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


class MultiHostReplicaMesh(ReplicaMesh):
    """2-D (slice x replica) mesh over the GLOBAL device list of a
    ``jax.distributed`` job — the device plane of one replicated serving
    group at pod scale.

    ``hybrid`` defaults to True: the replica axis is laid across DCN
    granules (``mesh_utils.create_hybrid_device_mesh``) so every
    slice-axis psum stays on ICI inside a pod and only cross-replica
    traffic crosses DCN — the multi-pod layout BACKLOG.md prescribes.
    ReplicaMesh's guarded fallback keeps construction working on dev
    rigs without a DCN topology (gloo CPU jobs), so the same code path
    is testable with multi-process CPU meshes.

    Adds the process-boundary helpers the serving path needs: which
    replica column this process's devices sit in, and which global
    slices it owns WITHIN that column (the 2-D analog of
    MultiHostSliceMesh's contiguous ownership rule).
    """

    def __init__(self, n_replicas: int = 2, devices: Sequence | None = None,
                 hybrid: bool = True):
        import jax

        super().__init__(
            n_replicas=n_replicas,
            devices=devices if devices is not None else jax.devices(),
            hybrid=hybrid,
        )
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()

    def _local_positions(self) -> list[tuple[int, int]]:
        """(slice row, replica column) of every local device in the
        mesh.  Local devices outside an explicit device subset own
        nothing (skipped, not an error) — the SliceMesh rule in 2-D."""
        import jax

        pos = {d: (int(r), int(c))
               for (r, c), d in np.ndenumerate(self.mesh.devices)}
        return [pos[d] for d in jax.local_devices() if d in pos]

    def local_replica_groups(self) -> list[int]:
        """Replica columns this process participates in.  A well-formed
        hybrid layout keeps each process inside ONE column (its pod);
        flat CPU fallbacks may straddle several."""
        return sorted({c for _, c in self._local_positions()})

    def owned_slices(self, n_slices: int) -> list[int]:
        """Global slice indices whose shards live on THIS process (in
        any replica column it holds — each column is a full copy, so
        ownership is per (row, column) device)."""
        _require_divisible(n_slices, self.n_devices)
        per_dev = n_slices // self.n_devices
        out = set()
        for r, _c in self._local_positions():
            out.update(range(r * per_dev, (r + 1) * per_dev))
        return sorted(out)
