"""View: a named bitmap matrix within a frame, split into per-slice fragments.

Reference analog: view.go.  Names: "standard", "inverse", and time-quantum
suffixed forms like "standard_2017" (view.go:31-34).  A view routes global
column ids to fragments by ``slice = columnID // SLICE_WIDTH``
(view.go:266-283) and notifies the server (for CreateSliceMessage
broadcast) when a fragment for a new max slice appears (view.go:219-254).
"""

from __future__ import annotations

import os
import threading

from pilosa_tpu.analysis import lockcheck
from typing import Callable, Optional

from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.columns import ViewColumns
from pilosa_tpu.core.fragment import DEFAULT_CACHE_SIZE, Fragment
from pilosa_tpu.pilosa import SLICE_WIDTH

VIEW_STANDARD = "standard"
VIEW_INVERSE = "inverse"


def is_valid_view(name: str) -> bool:
    return name in (VIEW_STANDARD, VIEW_INVERSE)


def is_inverse_view(name: str) -> bool:
    """The base inverse view or any time-quantum inverse sub-view
    (view.go IsInverseView prefix semantics)."""
    return name == VIEW_INVERSE or name.startswith(VIEW_INVERSE + "_")


class View:
    def __init__(
        self,
        path: str,
        index: str,
        frame: str,
        name: str,
        cache_type: str = cache_mod.DEFAULT_CACHE_TYPE,
        cache_size: int = DEFAULT_CACHE_SIZE,
        row_attr_store=None,
        on_new_fragment: Optional[Callable[[str, str, str, int], None]] = None,
        stats=None,
        ranking_debounce_s=None,
    ):
        self.path = path
        self.index = index
        self.frame = frame
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.ranking_debounce_s = ranking_debounce_s
        self.row_attr_store = row_attr_store
        from pilosa_tpu.stats import NOP_STATS

        self.on_new_fragment = on_new_fragment  # broadcast hook (CreateSliceMessage)
        self.stats = stats if stats is not None else NOP_STATS
        # Guards fragment create against concurrent writers (view.go mu analog).
        self._mu = lockcheck.named_rlock("core.view._mu")
        self.fragments: dict[int, Fragment] = {}
        # The fragments' array containers as columns, for a block's walk
        # (executor._walk_block); built by what the walks observe.
        self.columns = ViewColumns(self.fragments)

    # -- lifecycle ------------------------------------------------------

    def open(self) -> None:
        frag_dir = os.path.join(self.path, "fragments")
        os.makedirs(frag_dir, exist_ok=True)
        for entry in sorted(os.listdir(frag_dir)):
            if not entry.isdigit():
                continue
            self._open_fragment(int(entry))

    def close(self) -> None:
        for f in list(self.fragments.values()):
            f.close()
        self.fragments.clear()
        self.columns.drop()

    def flush_caches(self) -> None:
        # list() snapshots: writers may insert fragments concurrently
        for f in list(self.fragments.values()):
            f.flush_cache()

    def fragment_path(self, slice_i: int) -> str:
        return os.path.join(self.path, "fragments", str(slice_i))

    def _open_fragment(self, slice_i: int) -> Fragment:
        f = Fragment(
            self.fragment_path(slice_i),
            self.index,
            self.frame,
            self.name,
            slice_i,
            cache_type=self.cache_type,
            cache_size=self.cache_size,
            row_attr_store=self.row_attr_store,
            stats=self.stats.with_tags(f"slice:{slice_i}"),
            ranking_debounce_s=self.ranking_debounce_s,
        )
        f.open()
        self.fragments[slice_i] = f
        return f

    # -- fragments ------------------------------------------------------

    def fragment(self, slice_i: int) -> Optional[Fragment]:
        return self.fragments.get(slice_i)

    def create_fragment_if_not_exists(self, slice_i: int) -> Fragment:
        with self._mu:
            f = self.fragments.get(slice_i)
            if f is not None:
                return f
            is_new_max = not self.fragments or slice_i > self.max_slice()
            f = self._open_fragment(slice_i)
        if is_new_max:
            self.stats.count("maxSlice", 1)  # view.go:251
            if self.on_new_fragment is not None:
                self.on_new_fragment(self.index, self.frame, self.name, slice_i)
        return f

    def max_slice(self) -> int:
        return max(list(self.fragments.keys()), default=0)

    # -- bit ops (view.go:266-283) ---------------------------------------

    def set_bit(self, row_id: int, column_id: int) -> bool:
        slice_i = column_id // SLICE_WIDTH
        return self.create_fragment_if_not_exists(slice_i).set_bit(row_id, column_id)

    def set_bits(self, row_ids, column_ids):
        """Batched SetBit routed per slice; returns per-input changed bools
        (order preserved).  One fragment pass + WAL append per slice."""
        import numpy as np

        row_ids = np.asarray(row_ids, dtype=np.uint64)
        column_ids = np.asarray(column_ids, dtype=np.uint64)
        if len(row_ids) != len(column_ids):
            raise ValueError("row/column id length mismatch")
        changed = np.zeros(len(row_ids), dtype=bool)
        if len(row_ids) <= 8:
            # Tiny batches (group-commit queue): plain-python slice
            # grouping — the vectorized unique/nonzero/fancy-index route
            # below costs ~40 us of numpy dispatch per call.
            by_slice: dict[int, list[int]] = {}
            cols = column_ids.tolist()
            for i, c in enumerate(cols):
                by_slice.setdefault(c // SLICE_WIDTH, []).append(i)
            rows = row_ids.tolist()
            for s, idx in by_slice.items():
                frag = self.create_fragment_if_not_exists(s)
                ch = frag.set_bits(
                    np.asarray([rows[i] for i in idx], dtype=np.uint64),
                    np.asarray([cols[i] for i in idx], dtype=np.uint64),
                )
                for k, i in enumerate(idx):
                    changed[i] = ch[k]
            return changed
        slices = (column_ids // np.uint64(SLICE_WIDTH)).astype(np.int64)
        for s in np.unique(slices).tolist():
            idx = np.nonzero(slices == s)[0]
            frag = self.create_fragment_if_not_exists(int(s))
            changed[idx] = frag.set_bits(row_ids[idx], column_ids[idx])
        return changed

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        slice_i = column_id // SLICE_WIDTH
        f = self.fragments.get(slice_i)
        if f is None:
            return False
        return f.clear_bit(row_id, column_id)
