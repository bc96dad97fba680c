"""The pages of a stack's WINDOW layers: a host allocator and a block table
of their own, beside the engine's (``engine/inference_server.py``), whose
pool and table hold the layers that attend the whole context.

Why two pools with a table each, and not one pool with per-kind tables:
the two kinds need different NUMBERS of pages (a global layer's page
lives as long as its row, a window layer's until every holder's window
has passed it: 262k and 197k tokens in the benchmark's cell), and a pool
``[layers, pages, ...]`` has one page count for all its layers; and every
device function that moves pages (``paged.copy_blocks``,
``paged.write_kv_runs``, the kernel's layered pool argument) then serves
either pool as it is, where per-kind tables over one pool would give each
of them a layer range.

**The page rule.**  A row's pages are listed by their number in the row
(``GONE`` where released).  A holder at cached length ``n`` (a decoding
row: what the host knows it to hold; a fill: its position; a cached
prefix: its length) reads no position before ``n - window + 1`` again, so
the pages wholly before ``n - window`` go: :meth:`release_behind` (one
position of room: :meth:`first_kept`).  A page shared by
siblings (or by a row and the prefix cache) is refcounted and returns to
the free stack when its last holder lets go.  Everything is host-side and
deterministic (LIFO free stack, no clock), like the engine's own
allocator.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

#: a page of a row that was released (its table entry reads 0 and is never
#: visited: the kernel starts at the window's first page)
GONE = -1


class WindowPages:
    def __init__(
        self, n_blocks: int, page_size: int, window: int, max_batch: int,
        blocks_per_row: int,
    ):
        self.n_blocks = n_blocks
        self.page_size = page_size
        self.window = window
        self._free = list(range(n_blocks - 1, -1, -1))
        self._ref = np.zeros((n_blocks,), np.int32)
        self.rows: List[List[int]] = [[] for _ in range(max_batch)]
        self.tables_np = np.zeros((max_batch, blocks_per_row), np.int32)
        self.dirty = False
        #: what the prefix cache holds: the window-layer page that goes
        #: with a cached global block (same tokens, same prefix)
        self.cached: Dict[int, int] = {}
        self.allocated_total = 0  # pages handed out
        self.released_total = 0  # pages let go behind a window, by a holder
        self.freed_behind_total = 0  # ... of which by their LAST holder
        self.row_pages_max = 0  # most pages one decoding row held

    # -- the allocator ------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if len(self._free) < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        self._ref[out] = 1
        self.allocated_total += n
        return out

    def incref(self, blocks):
        for b in blocks:
            if b != GONE:
                self._ref[b] += 1

    def free(self, blocks):
        for b in blocks:
            if b == GONE:
                continue
            self._ref[b] -= 1
            assert self._ref[b] >= 0, f"double free of window block {b}"
            if self._ref[b] == 0:
                self._free.append(b)

    # -- rows ---------------------------------------------------------------

    def set_row(self, row_id: int, blocks: List[int]):
        """``blocks`` becomes row ``row_id``'s list (the SAME object: a
        fill and the row that carries it see one list)."""
        self.rows[row_id] = blocks
        self.sync_row(row_id)

    def sync_row(self, row_id: int):
        blocks = self.rows[row_id]
        t = self.tables_np[row_id]
        t[:] = 0
        t[: len(blocks)] = np.maximum(blocks, 0)
        self.dirty = True

    def release_row(self, row_id: int):
        self.free(self.rows[row_id])
        self.set_row(row_id, [])

    def first_read(self, length: int) -> int:
        """The number of the first page a holder at cached length
        ``length`` still reads: its next query stands at position
        ``length`` and attends ``length - window + 1`` on."""
        return max(length - self.window + 1, 0) // self.page_size

    def first_kept(self, length: int) -> int:
        """The number of the first page a holder at cached length
        ``length`` keeps: one position more than it reads, so that a
        request that reuses all but the last token of the same sequence
        (a cached prefix is matched up to ``length - 1``) finds the pages
        of ITS window."""
        return max(length - self.window, 0) // self.page_size

    def release_behind(
        self, blocks: List[int], length: int, row_id: Optional[int] = None
    ) -> int:
        """Let go of ``blocks``' pages wholly before ``length - window``
        (in place; row ``row_id``'s table follows where ``blocks`` is that
        row's list); returns how many went."""
        n, free0 = 0, len(self._free)
        for i in range(min(self.first_kept(length), len(blocks))):
            if blocks[i] != GONE:
                self.free([blocks[i]])
                blocks[i] = GONE
                n += 1
        self.released_total += n
        self.freed_behind_total += len(self._free) - free0
        if n and row_id is not None and self.rows[row_id] is blocks:
            self.sync_row(row_id)
        return n

    def held(self, blocks: List[int]) -> int:
        return sum(b != GONE for b in blocks)

    def table_of(self, blocks: List[int], out: np.ndarray):
        out[: len(blocks)] = np.maximum(blocks, 0)

    # -- what the prefix cache holds ----------------------------------------

    def cache_pair(self, global_block: int, window_block: int):
        """The cache took a reference on ``global_block``: it holds the
        window-layer page of the same tokens with it."""
        if window_block != GONE and global_block not in self.cached:
            self.cached[global_block] = window_block
            self.incref([window_block])

    def cache_drop(self, global_block: int):
        w = self.cached.pop(global_block, None)
        if w is not None:
            self.free([w])

    def cached_tail(self, pages: List[int], n_tokens: int) -> Optional[List[int]]:
        """The window-layer pages that go with a cached prefix of
        ``n_tokens`` tokens whose global pages (by number, a copied tail
        page last) are ``pages``: ``GONE`` before the window of a fill
        that starts there, the cached page from there on; None where one
        of those is no longer held (the prefix cannot be reused)."""
        out = [GONE] * len(pages)
        for i in range(self.first_read(n_tokens), len(pages)):
            w = self.cached.get(pages[i])
            if w is None:
                return None
            out[i] = w
        return out
