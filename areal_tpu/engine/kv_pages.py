"""The host side of the generation server's page pools: ONE allocator
(:class:`PagePool`) for every device pool of KV pages the engine holds,
and the ONE table of what a cache kind rules out (:func:`refuse`).

A pool ``[layers, pages, ...]`` on the device has a ``PagePool`` on the
host: a LIFO free stack, a refcount a page, each batch row's list of pages,
the block table ``[max_batch, blocks_per_row]`` the step programs read, and
the counts.  The engine (``engine/inference_server.py``) holds one for the
layers that attend the whole context (``window=None``: a page lives as long
as its row) and, for a stack with window layers, a second one
(``window=W``).  Everything here is host-side and deterministic (LIFO, no
clock): SPMD controllers replay one command stream and must hand out the
same pages.

Why two pools with a table each, and not one pool with per-kind tables:
the two kinds need different NUMBERS of pages (a global layer's page
lives as long as its row, a window layer's until every holder's window
has passed it: 262k and 197k tokens in the benchmark's cell), and a pool
``[layers, pages, ...]`` has one page count for all its layers; and every
device function that moves pages (``paged.copy_blocks``,
``paged.write_kv_runs``, the kernel's layered pool argument) then serves
either pool as it is, where per-kind tables over one pool would give each
of them a layer range.

**The page rule of a window pool.**  A row's pages are listed by their
number in the row (``GONE`` where released).  A holder at cached length
``n`` (a decoding row: what the host knows it to hold; a fill: its
position; a cached prefix: its length) reads no position before
``n - window + 1`` again, so the pages wholly before ``n - window`` go:
:meth:`PagePool.release_behind` (one position of room:
:meth:`PagePool.first_kept`).  A page shared by siblings (or by a row and
the prefix cache) is refcounted and returns to the free stack when its
last holder lets go.  Without a window nothing is ever behind a holder
and the rule releases nothing.

**What a cache kind rules out.**  Recurrent state slots are not pages (a
slot is its row's) and have no allocator; they enter here as a row of
:data:`REFUSED`, beside the window pool and the stacks whose programs
``models/hybrid.py`` writes, and the pool of index keys that rides the
whole-context pages of a stack with an indexer (one page id names a latent
page and its index page: sharing, tail copies, the prefix cache, parking
and release move both; what moves pages OUT of the pools does not know the
second array).  A new cache kind adds a row.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: a page of a row that was released (its table entry reads 0 and is never
#: visited: the kernel starts at the window's first page)
GONE = -1


class PagePool:
    def __init__(
        self, n_blocks: int, page_size: int, max_batch: int,
        blocks_per_row: int, window: Optional[int] = None,
    ):
        self.n_blocks = n_blocks
        self.page_size = page_size
        self.window = window
        self._free = list(range(n_blocks - 1, -1, -1))
        self._ref = np.zeros((n_blocks,), np.int32)
        self.rows: List[List[int]] = [[] for _ in range(max_batch)]
        self.tables_np = np.zeros((max_batch, blocks_per_row), np.int32)
        self.dirty = True
        self.upload()
        #: a window pool beside the prefix cache: how many references the
        #: cache holds on a global block, and the window-layer page it
        #: holds with one (same tokens, same prefix)
        self.cache_refs: Dict[int, int] = {}
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
            assert self._ref[b] >= 0, f"double free of block {b}"
            if self._ref[b] == 0:
                self._free.append(b)

    def held(self, blocks: List[int]) -> int:
        return sum(b != GONE for b in blocks)

    def live(self, row_ids: Iterable[int]) -> int:
        """Pages held by the rows ``row_ids``, each page once however many
        siblings share it."""
        pages = set()
        for row_id in row_ids:
            pages.update(self.rows[row_id])
        pages.discard(GONE)
        return len(pages)

    # -- rows and the table -------------------------------------------------

    def set_row(self, row_id: int, blocks: List[int]):
        """``blocks`` becomes row ``row_id``'s list (the SAME object: a
        fill and the row that carries it see one list)."""
        self.rows[row_id] = blocks
        self.sync_row(row_id)

    def sync_row(self, row_id: int):
        t = self.tables_np[row_id]
        t[:] = 0
        self.table_of(self.rows[row_id], t)
        self.dirty = True

    def extend_row(self, row_id: int, blocks: List[int]):
        self.set_row(row_id, self.rows[row_id] + blocks)

    def release_row(self, row_id: int):
        if self.rows[row_id]:
            self.free(self.rows[row_id])
            self.set_row(row_id, [])

    def table_of(self, blocks: List[int], out: np.ndarray):
        out[: len(blocks)] = np.maximum(blocks, 0)

    def upload(self) -> jax.Array:
        """The host block table as a device array — through a COPY.  The
        host table is mutated in place by the allocator, and a transfer
        may alias (CPU backend) or still be reading (async H2D) the numpy
        buffer it was given: dispatched-but-not-yet-run chunks then saw a
        LATER table, and streams differed run to run under host load."""
        if self.dirty:
            self._uploaded = jnp.array(self.tables_np)
            self.dirty = False
        return self._uploaded

    # -- the page rule ------------------------------------------------------

    def first_read(self, length: int) -> int:
        """The number of the first page a holder at cached length
        ``length`` still reads: its next query stands at position
        ``length`` and attends ``length - window + 1`` on."""
        if self.window is None:
            return 0
        return max(length - self.window + 1, 0) // self.page_size

    def first_kept(self, length: int) -> int:
        """The number of the first page a holder at cached length
        ``length`` keeps: one position more than it reads, so that a
        request that reuses all but the last token of the same sequence
        (a cached prefix is matched up to ``length - 1``) finds the pages
        of ITS window."""
        if self.window is None:
            return 0
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

    # -- what the prefix cache holds of a window pool -----------------------

    def cache_hold(self, global_blocks: List[int]):
        """The cache took a reference on each of ``global_blocks``."""
        for b in global_blocks:
            self.cache_refs[b] = self.cache_refs.get(b, 0) + 1

    def cache_pair(self, global_block: int, window_block: int):
        """The cache holds ``global_block``: it holds the window-layer
        page of the same tokens with it."""
        if (
            window_block != GONE
            and global_block in self.cache_refs
            and global_block not in self.cached
        ):
            self.cached[global_block] = window_block
            self.incref([window_block])

    def cache_drop(self, global_blocks: List[int]):
        """The cache let go of a reference on each of ``global_blocks``:
        the page paired with one goes with the cache's last."""
        for b in global_blocks:
            self.cache_refs[b] -= 1
            if self.cache_refs[b] == 0:
                del self.cache_refs[b]
                w = self.cached.pop(b, None)
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


# -- the fills a stateful engine keeps ----------------------------------------


class KeptFills:
    """The finished fills a stateful engine keeps for their prompts' late
    siblings, by the prompt's tokens: the host side (which snapshot slot,
    which pages, who goes first, the counts).  A stack with a recurrent
    state can reuse a prompt only where its state was saved, and the one
    point every fill has is its end: the engine copies the end state to a
    SNAPSHOT slot (arrays of their own on the device, ``n_slots`` slots,
    the fill's last logits row beside them) and this table holds a
    reference on the fill's pages in every pool (the tail page too, and
    in a window pool the prompt's last window: a holder that is no row,
    so the window rule does not let them go behind the fill's first
    target).  A request whose prompt equals a kept fill's joins it where
    it would have prefilled again.

    Least recently joined goes first: when the slots run out, when a live
    row needs a page (recompute insurance always yields to a live row),
    and all of them at a weight swap.  A fill with targets (requests that
    are joining it in this engine step) is never let go.  Deterministic
    (insertion order, no clock), as the pools are."""

    CAUSES = ("slots", "pages", "swap")

    def __init__(self, n_slots: int, pools: List[PagePool]):
        self.n_slots = n_slots
        self._pools = pools
        self._free = list(range(n_slots - 1, -1, -1))
        self._fills: Dict[tuple, Any] = {}  # least recently joined first
        self.kept_total = 0
        self.late_joins_total = 0
        self.evicted = dict.fromkeys(self.CAUSES, 0)

    def __len__(self) -> int:
        return len(self._fills)

    @staticmethod
    def _pages_of(fill):
        return (fill.blocks, fill.wblocks)

    def peek(self, key: tuple):
        return self._fills.get(key)

    def join(self, key: tuple):
        """The kept fill of prompt ``key`` for one more late sibling (it
        becomes the most recently joined), or None."""
        fill = self._fills.pop(key, None)
        if fill is not None:
            self._fills[key] = fill
            self.late_joins_total += 1
        return fill

    def keep(self, fill) -> bool:
        """Keep ``fill`` (ended, its pages listed by number in every
        pool): take a slot, letting the least recently joined go for it,
        and a reference on every page.  False where no slot can be had."""
        assert fill.key not in self._fills, "a prompt is kept once"
        if not self._free and not self.evict("slots"):
            return False
        fill.snap = self._free.pop()
        for pool, held in zip(self._pools, self._pages_of(fill)):
            pool.incref(held)
        self._fills[fill.key] = fill
        self.kept_total += 1
        return True

    def evict(self, cause: str) -> bool:
        """Let the least recently joined fill go (slot and pages); False
        where none can."""
        fill = next((f for f in self._fills.values() if not f.targets), None)
        if fill is None:
            return False
        del self._fills[fill.key]
        for pool, held in zip(self._pools, self._pages_of(fill)):
            pool.free(held)
        self._free.append(fill.snap)
        fill.snap = -1
        self.evicted[cause] += 1
        return True

    def counts(self) -> Dict[str, int]:
        """The running totals, as the span ``areal.engine.fill.dispatch``
        carries them."""
        return dict(
            state_late_joins=self.late_joins_total,
            state_fills_kept=self.kept_total,
            **{f"state_fills_evicted_{c}": n for c, n in self.evicted.items()},
        )


# -- what a cache kind rules out ---------------------------------------------


class CacheKindRefuses(NotImplementedError):
    """``feature`` was asked of an engine one of whose cache kinds rules
    it out; the message names the model, the kind and its reason."""

    def __init__(self, feature: str, model: str, why: str):
        super().__init__(f"{feature} is not supported for {model}: {why}")
        self.feature = feature


class StatefulModelUnsupported(CacheKindRefuses):
    """A feature that assumes a sequence's cache is per-token blocks was
    asked of a model whose layers also keep a recurrent state per
    sequence (``cfg.n_mamba_layers > 0``): that state exists at the end
    of what was computed and nowhere else, so it cannot be cut at a page
    boundary or rebuilt from KV pages another server sends.  Its message
    lists the cache kinds the model holds (the state slots first: they
    are what refuses)."""


#: the cache kinds that rule something out -> (why, as the message says it;
#: the error raised)
STATE_SLOTS, WINDOW_POOL, BY_KIND = "state slots", "window pool", "by kind"
INDEX_POOL = "index pool"
_KINDS = {
    INDEX_POOL: (
        "the index pool refuses it (each latent page has a page of index "
        "keys of another width beside it, which only the two programs of "
        "models/hybrid.py and the page copies read and write)",
        CacheKindRefuses,
    ),
    STATE_SLOTS: (
        "the state slots refuse it (a state exists where its sequence "
        "ends, not at page boundaries)",
        StatefulModelUnsupported,
    ),
    WINDOW_POOL: (
        "the window pool refuses it (its pages live in a pool and a table "
        "of their own, engine/kv_pages.py, which it does not move)",
        CacheKindRefuses,
    ),
    BY_KIND: (
        "its fill and decode programs (models/hybrid.py) do not write it",
        CacheKindRefuses,
    ),
}

#: feature -> the cache kinds that rule it out (the first one held refuses).
#: The first four are what the two programs of a stack stated by kind do
#: not write, whatever its kinds; the last three move or keep whole rows'
#: pages outside their pool, which assumes ONE table of per-token blocks.
REFUSED = {
    "the dense (unpaged) KV cache": (STATE_SLOTS, BY_KIND),
    "a tensor- or expert-parallel serving mesh": (STATE_SLOTS, INDEX_POOL, BY_KIND),
    "int8 KV storage": (STATE_SLOTS, INDEX_POOL, BY_KIND),
    "int8 serving weights": (STATE_SLOTS, BY_KIND),
    "prefix-cache host spill": (STATE_SLOTS, INDEX_POOL, WINDOW_POOL),
    "P/D handoff": (STATE_SLOTS, INDEX_POOL, WINDOW_POOL),
    "prefix pulls": (STATE_SLOTS, INDEX_POOL, WINDOW_POOL),
}


def kinds_held(cfg) -> Dict[str, str]:
    """The cache kinds of :data:`REFUSED` that a model of ``cfg`` holds,
    each with what a refusal calls the model."""
    held = {}
    if cfg.n_mamba_layers:
        names = ["recurrent state slots"]
        if cfg.n_window_layers:
            names.append("a window pool")
        names.append("a pool of whole-context pages")
        held[STATE_SLOTS] = "a model with " + ", ".join(names)
    if cfg.is_indexed:
        held[INDEX_POOL] = "a stack whose latent layers keep index keys"
    if cfg.n_window_layers:
        held[WINDOW_POOL] = (
            "a stack with latent window layers" if cfg.is_latent_window
            else "a stack with window layers"
        )
    if cfg.is_hybrid:
        held[BY_KIND] = (
            f"a stack stated by kind {sorted(set(cfg.layer_types))}"
        )
    return held


def refuse(feature: str, held: Dict[str, str]):
    """Raise where one of the cache kinds ``held`` (:func:`kinds_held`)
    rules ``feature`` out."""
    for kind in REFUSED[feature]:
        if kind in held:
            why, error = _KINDS[kind]
            raise error(feature, held[kind], why)
