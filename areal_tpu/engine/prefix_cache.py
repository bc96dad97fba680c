"""Cross-request radix prefix cache over the paged KV pool.

The reference's decoupled rollout cluster leans on SGLang's radix cache to
make multi-turn agent loops affordable: every turn re-sends the whole
growing conversation and the server recomputes only the new suffix
(reference: realhf/system/partial_rollout.py + SGLang's RadixCache /
cache-aware load balancing).  Our engine reproduced that role only in two
narrow slices — same-qid continuation parking and group-prompt block
sharing.  This module is the general mechanism: a radix/trie index over
TOKEN-ID prefixes whose nodes hold refcounted blocks in the engine's
existing paged pool (areal_tpu/models/paged.py), so any new request first
walks the tree, pins the longest matched prefix's blocks, and enters the
fill queue needing only the suffix prefilled.

Design constraints, in order:

* **Blocks are the unit of sharing.**  A trie node covers exactly one
  FULL pool block (``page_size`` tokens), keyed by that block's token
  tuple.  Full blocks are append-frozen — once a row has written past a
  block it never writes into it again — so sharing them by reference is
  safe while the donor row keeps decoding.  The one mutable block per
  row (its tail) is shared only by VALUE: a node may carry a *partial
  tail entry* (block id + the token prefix it holds), and a match on it
  returns a copy-on-write instruction — the engine copies the block
  (``paged.copy_blocks``) and owns the copy.  KV values depend only on
  (token prefix, weights), so mixing blocks cached by different donor
  rows along one trie path is exact, not approximate.
* **The cache owns references, never blocks.**  It speaks to the
  engine's allocator through two callbacks (``acquire``/``release`` =
  ``incref``/``free`` of the engine's ``kv_pages.PagePool``); eviction only
  drops the cache's OWN reference, so a prefix pinned by a live row can
  never be yanked from under it — the pool recycles a block only when
  every holder is gone.
* **Deterministic under SPMD lockstep.**  Multi-host serving replays one
  command stream on every controller; all cache decisions (LRU order,
  eviction victims, capacity trims) key on the engine's step counter and
  a monotone node sequence — never wall time.
* **Weight swaps invalidate.**  Cached KV is only valid under the
  weights that computed it; ``flush()`` (called by the engine before a
  swap's re-prefill) drops every entry and bumps ``version`` so a
  concurrent insert of pre-swap KV is rejected.  Stale-KV reuse across
  a swap would be a silent correctness bug — the engine's test suite
  pins this.

**Host spill tier** (``host_bytes_budget`` > 0): the cache is
hierarchical — HBM blocks on top, host RAM below.  When eviction would
drop a full-block node, the node instead SPILLS: the engine's
``spill_fetch`` callback gathers the victims' block KV into host
buffers (one batched ``device_get`` per reclamation round), the device
references are released, and the trie node stays alive in a ``spilled``
state carrying its host payload.  A later ``match()`` that lands on
spilled nodes reports them in ``PrefixMatch.restore_nodes``; the engine
allocates fresh pool blocks, dispatches an async scatter of the host
payloads back into them (the swap-in rides the decode ring's overlap),
and hands the blocks back via :meth:`complete_restore` — the node is
usable again from ``ready_step`` on (a step-keyed gate, never a device
readiness probe, so SPMD lockstep replay stays deterministic).  LRU
spans both tiers: device eviction picks (last_use, seq)-LRU residents,
and a spill that overflows ``host_bytes_budget`` first trims the
LRU spilled entry — admitting the newcomer only if something older
yields.  On any root-to-leaf path residents precede spilled nodes (a
node spills only once every child has), so a spilled chain is always
restorable top-down.  ``flush()`` drops BOTH tiers — stale KV across a
weight swap stays impossible, host copies included.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class PrefixMatch:
    """Result of a longest-prefix walk.

    ``blocks`` are the matched FULL blocks, in sequence order — the
    caller must pin them (its own incref) before using them.  When
    ``tail_block`` is set, the node also held a partial tail whose first
    ``tail_tokens`` tokens extend the match; the caller must COPY that
    block into one it owns (copy-on-write) — the donor may still be
    appending to it.  ``n_tokens`` is the total matched prefix length
    (``len(blocks) * page_size + tail_tokens``).

    Host-tier extension: ``restore_nodes`` are spilled trie nodes that
    would extend the resident match by ``restore_tokens`` more tokens
    once swapped back in (the caller starts the restore and requeues the
    admission).  ``pending`` is True when a node on the path has a
    swap-in already dispatched but not yet usable (its ``ready_step`` is
    in the future) — the caller requeues WITHOUT starting a new restore.
    When either is set the resident fields above cover only the usable
    resident prefix and the tail scan was skipped."""

    blocks: List[int] = dataclasses.field(default_factory=list)
    n_tokens: int = 0
    tail_block: Optional[int] = None
    tail_tokens: int = 0
    restore_nodes: List["_Node"] = dataclasses.field(default_factory=list)
    restore_tokens: int = 0
    pending: bool = False


@dataclasses.dataclass
class _TailEntry:
    """A partially-filled block cached by value: ``tokens`` are the block's
    valid prefix; a longer donor with the same first token replaces it."""

    block: int
    tokens: Tuple[int, ...]
    last_use: int = 0
    seq: int = 0


#: max cached partial tails per node, keyed by the tail's FIRST token.  One
#: slot per node would let concurrent sessions shorter than ``page_size``
#: thrash each other out (every sub-page conversation is all-tail at the
#: root); a small per-first-token set keeps several live sessions hot while
#: bounding the per-node candidate scan.
TAILS_PER_NODE = 4


class _Node:
    """One full block of one cached sequence.  ``key`` is the block's
    ``page_size``-token tuple; children extend the prefix by one block.

    ``spilled`` nodes hold their KV in ``host_kv`` (a host (k, v) pair
    the engine's spill_fetch produced) instead of a pool block;
    ``ready_step`` gates a freshly restored node until the engine step
    after its swap-in dispatch (step-keyed, SPMD-deterministic)."""

    __slots__ = ("key", "block", "children", "parent", "last_use", "seq",
                 "tails", "spilled", "host_kv", "ready_step")

    def __init__(self, key, block, parent, last_use, seq):
        self.key: Tuple[int, ...] = key
        self.block: int = block
        self.children: Dict[Tuple[int, ...], _Node] = {}
        self.parent: Optional[_Node] = parent
        self.last_use: int = last_use
        self.seq: int = seq  # insertion order: deterministic LRU tie-break
        # first token -> cached partial tail (bounded by TAILS_PER_NODE)
        self.tails: Dict[int, _TailEntry] = {}
        self.spilled: bool = False
        self.host_kv: Optional[Tuple[Any, Any]] = None
        self.ready_step: int = 0


def _insort_lru(cands: List[_Node], node: _Node):
    """Insert ``node`` into an LRU-sorted ``(last_use, seq)`` candidate
    list, keeping order (the host-trim list shared across one
    reclamation round)."""
    bisect.insort(cands, node, key=lambda n: (n.last_use, n.seq))


class RadixPrefixCache:
    """Block-granularity radix index over cached token prefixes.

    ``capacity_blocks`` caps how many pool blocks the cache may hold
    references to (the engine derives it from a pool fraction); ``0``
    disables insertion entirely.  ``min_match_tokens`` suppresses matches
    shorter than the configured floor — pinning and COW-copying for a
    handful of cached tokens costs more than it saves.

    ``host_bytes_budget`` > 0 enables the host spill tier (see module
    docstring): ``block_bytes`` is one full block's TRUE storage
    footprint (derived by the engine from the pool arrays' itemsize —
    int8 data + scales for quantized pools — the budget's accounting
    unit) and ``spill_fetch(blocks)`` is the engine's batched
    device->host gather, returning a tuple of per-block host arrays
    (``(k, v)``, plus scale components for quantized pools) indexed
    ``[i] -> blocks[i]``; the cache round-trips the tuple opaquely.
    """

    def __init__(
        self,
        page_size: int,
        capacity_blocks: int,
        acquire: Callable[[List[int]], None],
        release: Callable[[List[int]], None],
        min_match_tokens: int = 1,
        host_bytes_budget: int = 0,
        block_bytes: int = 0,
        spill_fetch: Optional[Callable[[List[int]], Tuple[Any, Any]]] = None,
        ledger_handle=None,
    ):
        assert page_size >= 1
        self.page_size = page_size
        self.capacity_blocks = max(0, int(capacity_blocks))
        self.min_match_tokens = max(1, int(min_match_tokens))
        self._acquire = acquire
        self._release = release
        self.host_bytes_budget = max(0, int(host_bytes_budget))
        self.block_bytes = max(0, int(block_bytes))
        self._spill_fetch = spill_fetch
        self._root = _Node(key=(), block=-1, parent=None, last_use=0, seq=0)
        self._seq = 0
        self.version = 0
        self.blocks_held = 0
        #: HBM-ledger handle (``prefix_spill_host`` tag) tracking the
        #: spill tier's host bytes; None = unledgered (standalone use)
        self.ledger_handle = ledger_handle
        self._host_bytes_held = 0
        self.host_blocks_held = 0
        # stats (cumulative; the engine mirrors them into the registry)
        self.hits_total = 0
        self.misses_total = 0
        self.cached_tokens_total = 0
        self.insertions_total = 0
        self.evictions_total = 0
        self.flushes_total = 0
        self.spilled_blocks_total = 0
        self.restored_blocks_total = 0
        self.host_dropped_blocks_total = 0

    @property
    def host_bytes_held(self) -> int:
        return self._host_bytes_held

    @host_bytes_held.setter
    def host_bytes_held(self, nbytes: int) -> None:
        # every mutation flows through here, so the ledger attribution
        # can never drift from the cache's own accounting
        self._host_bytes_held = nbytes
        if self.ledger_handle is not None:
            self.ledger_handle.set(nbytes)

    @property
    def _host_enabled(self) -> bool:
        return (
            self.host_bytes_budget > 0
            and self.block_bytes > 0
            and self._spill_fetch is not None
        )

    # -- lookup -------------------------------------------------------------

    def match(
        self, tokens: Sequence[int], step: int, record: bool = True
    ) -> PrefixMatch:
        """Longest cached prefix of ``tokens``, capped at
        ``len(tokens) - 1`` so at least one suffix token remains to
        prefill (the engine samples the request's first output from the
        suffix prefill's final logits).  Touches every node on the path
        (LRU refresh).  Counts a hit iff the match clears
        ``min_match_tokens`` — callers that may re-match the same
        request (a requeued admission retries every engine step) pass
        ``record=False`` and call :meth:`record` once the match is
        actually consumed, so stats count served tokens, not attempts.

        A walk that lands on host-tier nodes returns a BLOCKED match:
        ``restore_nodes``/``pending`` set (see :class:`PrefixMatch`),
        resident fields covering only the usable resident prefix, and
        no stats recorded (the caller requeues and re-matches)."""
        BS = self.page_size
        max_match = len(tokens) - 1
        node = self._root
        out = PrefixMatch()
        depth = 0
        blocked = False
        while (depth + 1) * BS <= max_match:
            key = tuple(tokens[depth * BS : (depth + 1) * BS])
            child = node.children.get(key)
            if child is None:
                break
            child.last_use = step
            if not blocked and not child.spilled and child.ready_step <= step:
                out.blocks.append(child.block)
            else:
                # the resident run ends at the first spilled/not-yet-ready
                # node; everything past it (resident or not) counts only
                # as extension tokens the restore would unlock
                blocked = True
                if child.spilled:
                    out.restore_nodes.append(child)
                elif child.ready_step > step:
                    out.pending = True
                out.restore_tokens += BS
            node = child
            depth += 1
        out.n_tokens = len(out.blocks) * BS
        if blocked:
            # gate on the full potential: a restore is only worth
            # triggering when the unblocked match would clear the floor
            if out.n_tokens + out.restore_tokens < self.min_match_tokens:
                if record:
                    self.misses_total += 1
                return PrefixMatch()
            return out
        # partial extension of the deepest matched node: its cached
        # partial tail, or the head of a FULL child block (a shorter or
        # diverging prompt re-using part of a longer cached sequence).
        # The longest COMMON prefix counts — the caller's copy-on-write
        # gives it the whole block, and its suffix fill overwrites the
        # positions past the divergence point.
        rem = tokens[depth * BS :]
        limit = max_match - out.n_tokens
        if limit <= 0 or not rem:
            if out.n_tokens < self.min_match_tokens:
                if record:
                    self.misses_total += 1
                return PrefixMatch()
            if record:
                self.hits_total += 1
                self.cached_tokens_total += out.n_tokens
            return out
        # only candidates sharing the FIRST remaining token can extend the
        # match — the cheap pre-filter keeps this scan O(#children) single
        # compares instead of O(#children x page_size) LCP loops (requeued
        # admissions re-match every engine step, so this is hot under pool
        # pressure)
        first = rem[0]
        cands: List[Tuple[Tuple[int, ...], int, Optional[_Node]]] = []
        tail = node.tails.get(first)
        if tail is not None:
            cands.append((tail.tokens, tail.block, None))
        for child in node.children.values():
            if child.key[0] != first:
                continue
            if child.spilled or child.ready_step > step:
                # host-tier blocks have no device block to COW from, and
                # a restoring one isn't usable until its ready step
                continue
            cands.append((child.key, child.block, child))
        best_block, best_lcp, best_node = None, 0, None
        for t, blk, child in cands:
            n = min(len(t), limit)
            lcp = 0
            while lcp < n and rem[lcp] == t[lcp]:
                lcp += 1
            if lcp > best_lcp:  # strict: first-best wins ties (the
                best_block, best_lcp, best_node = blk, lcp, child
                # candidate order is insertion order — deterministic
                # under SPMD lockstep replay)
        if best_lcp > 0:
            out.tail_block = best_block
            out.tail_tokens = best_lcp
            out.n_tokens += best_lcp
            if best_node is not None:
                best_node.last_use = step
            else:
                tail.last_use = step
                node.last_use = step
        if out.n_tokens < self.min_match_tokens:
            if record:
                self.misses_total += 1
            return PrefixMatch()
        if record:
            self.hits_total += 1
            self.cached_tokens_total += out.n_tokens
        return out

    def record(self, m: PrefixMatch):
        """Count a match returned by ``match(..., record=False)`` that
        the caller actually consumed (its fill was built)."""
        if m.n_tokens > 0:
            self.hits_total += 1
            self.cached_tokens_total += m.n_tokens
        else:
            self.misses_total += 1

    def export_walk(
        self, tokens: Sequence[int], step: int
    ) -> List[Tuple[str, Any]]:
        """Walk the longest cached full-block run covering ``tokens``
        for a FLEET EXPORT (a peer's prefix pull), returning ordered
        per-block entries: ``("device", block_id)`` for resident blocks,
        ``("host", host_kv)`` for spilled ones — the exporter gathers
        the device run in one batch and ships spill payloads directly
        (they are already the wire format).  Unlike :meth:`match`, both
        tiers export in place: no restore round trip, no pinning, no
        hit/miss stats (the pull is the owner serving a peer, not the
        owner serving itself).  Stops at the first gap: a missing
        child, a swap-in still in flight (``ready_step`` in the future
        — its KV is not host-readable anymore and not device-complete
        yet), or a spilled node whose payload was trimmed.  Refreshes
        LRU on the exported path (a fleet-hot prefix should not be the
        next eviction victim).  Capped at ``len(tokens) - 1`` like
        every match, so the puller keeps a suffix token to prefill."""
        BS = self.page_size
        max_match = len(tokens) - 1
        node = self._root
        out: List[Tuple[str, Any]] = []
        depth = 0
        while (depth + 1) * BS <= max_match:
            key = tuple(tokens[depth * BS : (depth + 1) * BS])
            child = node.children.get(key)
            if child is None:
                break
            if child.spilled:
                if child.host_kv is None:
                    break
                out.append(("host", child.host_kv))
            elif child.ready_step > step:
                break
            else:
                out.append(("device", child.block))
            child.last_use = step
            node = child
            depth += 1
        return out

    # -- insertion ----------------------------------------------------------

    def insert(
        self,
        tokens: Sequence[int],
        blocks: Sequence[int],
        step: int,
        version: int,
    ) -> int:
        """Register a sequence's KV: ``blocks[i]`` holds tokens
        ``[i*page_size, (i+1)*page_size)``; a trailing partial block (if
        ``len(tokens)`` is not page-aligned) is cached as a tail entry.
        Where a path node already exists the existing block is kept (its
        KV is identical by construction) and only new segments acquire
        references.  Returns the number of blocks newly referenced.
        Inserts from a stale ``version`` (a swap raced the caller) are
        dropped."""
        if self.capacity_blocks <= 0 or version != self.version:
            return 0
        BS = self.page_size
        n_full = len(tokens) // BS
        tail_len = len(tokens) - n_full * BS
        if n_full + (1 if tail_len else 0) > len(blocks):
            n_full = min(n_full, len(blocks))
            tail_len = 0
        node = self._root
        added = 0
        for i in range(n_full):
            key = tuple(tokens[i * BS : (i + 1) * BS])
            # a tail cached while this block was still partial is
            # SUBSUMED once the same prefix arrives full: drop it, or
            # blocks_held double-counts the physical block (early
            # capacity trims, overreported residency) and the dead
            # entry squats in a tail slot it can never win from
            stale = node.tails.get(key[0])
            if stale is not None and key[: len(stale.tokens)] == stale.tokens:
                self._release([stale.block])
                del node.tails[key[0]]
                self.blocks_held -= 1
            child = node.children.get(key)
            if child is None:
                self._seq += 1
                child = _Node(
                    key=key, block=int(blocks[i]), parent=node,
                    last_use=step, seq=self._seq,
                )
                self._acquire([child.block])
                self.blocks_held += 1
                added += 1
                node.children[key] = child
            elif child.spilled:
                # repatriate for free: the donor just recomputed this
                # block's KV on device, so adopt its block and drop the
                # host copy (resident beats spilled for the same prefix)
                self._drop_host_payload(child)
                child.block = int(blocks[i])
                child.ready_step = 0
                self._acquire([child.block])
                self.blocks_held += 1
                added += 1
                child.last_use = step
            else:
                child.last_use = step
            node = child
        if tail_len:
            t = tuple(tokens[n_full * BS :])
            first = t[0]
            cur = node.tails.get(first)
            if cur is None or len(cur.tokens) < len(t):
                # longer donors replace shorter SAME-FIRST-TOKEN tails
                # (a same-length one is identical by construction: same
                # tokens -> same KV); different first tokens coexist up
                # to TAILS_PER_NODE so concurrent sub-page sessions
                # don't thrash one slot
                self._seq += 1
                self._acquire([int(blocks[n_full])])
                self.blocks_held += 1
                added += 1
                if cur is not None:
                    self._release([cur.block])
                    self.blocks_held -= 1
                node.tails[first] = _TailEntry(
                    block=int(blocks[n_full]), tokens=t,
                    last_use=step, seq=self._seq,
                )
                if len(node.tails) > TAILS_PER_NODE:
                    # deterministic LRU drop among the OTHER tails
                    k = min(
                        (f for f in node.tails if f != first),
                        key=lambda f: (
                            node.tails[f].last_use, node.tails[f].seq
                        ),
                    )
                    self._release([node.tails.pop(k).block])
                    self.blocks_held -= 1
                    self.evictions_total += 1
            else:
                cur.last_use = step
            node.last_use = step
        if added:
            self.insertions_total += 1
        # capacity trim: never evict what this very call touched
        if self.blocks_held > self.capacity_blocks:
            self.evict(
                self.blocks_held - self.capacity_blocks, protect_step=step
            )
        return added

    # -- eviction -----------------------------------------------------------

    def _evictable(self, protect_step: Optional[int]) -> List[_Node]:
        """Every node holding a device unit that may be reclaimed, sorted
        LRU-first by (last_use, seq): any node carrying tail entries, or
        a RESIDENT node none of whose children are resident — evicting a
        node with resident children would orphan their prefix, while
        all-spilled children survive a spill (the chain stays walkable)
        but not a drop (see :meth:`_drop_node`).  A node with tails is
        one candidate per round (each selection drops its LRU tail)."""
        out: List[_Node] = []
        stack = [self._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if protect_step is not None and n.last_use >= protect_step:
                continue
            if n.tails:
                out.append(n)
                continue
            if n is self._root or n.spilled:
                continue  # no device block of its own to reclaim
            if any(not c.spilled for c in n.children.values()):
                continue
            out.append(n)
        out.sort(key=lambda n: (n.last_use, n.seq))
        return out

    def _drop_host_payload(self, node: _Node):
        """Release a node's host-tier accounting (payload + spilled
        flag).  Keyed on ``spilled``, not the payload: a victim marked
        mid-round counts bytes before its batched gather lands, and must
        release them if trimmed in that same window."""
        if node.spilled:
            node.spilled = False
            node.host_kv = None
            self.host_bytes_held -= self.block_bytes
            self.host_blocks_held -= 1

    def _drop_node(self, victim: _Node):
        """Remove ``victim`` from the trie, releasing its device block.
        Its children (all spilled by selection) lose their prefix with
        it: the whole spilled subtree's host payloads and tail blocks
        are dropped too."""
        self._release([victim.block])
        self.blocks_held -= 1
        self.evictions_total += 1
        if victim.parent is not None:
            del victim.parent.children[victim.key]
        stack = list(victim.children.values())
        victim.children.clear()
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.spilled:
                self._drop_host_payload(n)
                self.host_dropped_blocks_total += 1
            else:  # unreachable by the selection invariant; stay safe
                self._release([n.block])
                self.blocks_held -= 1
                self.evictions_total += 1
            if n.tails:
                self._release([t.block for t in n.tails.values()])
                self.blocks_held -= len(n.tails)
                self.evictions_total += len(n.tails)
                n.tails.clear()

    def _spilled_leaves_lru(self) -> List[_Node]:
        """Spilled nodes with no children, LRU-first — the host tier's
        trim candidates (dropping a childless spilled node orphans
        nothing; its parent becomes the next candidate)."""
        out: List[_Node] = []
        stack = [self._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.spilled and not n.children:
                out.append(n)
        out.sort(key=lambda n: (n.last_use, n.seq))
        return out

    def _trim_host_one(
        self,
        before: Optional[Tuple[int, int]] = None,
        cands: Optional[List[_Node]] = None,
    ) -> bool:
        """Drop the LRU childless spilled node from the host tier; with
        ``before`` only if it is strictly LRU-older than that
        (last_use, seq) key — the cross-tier LRU gate for admitting a
        new spill into a full budget.  Returns True iff dropped.

        ``cands`` is a mutable LRU list one reclamation round reuses
        across its trims (entries are re-validated before use, and a
        parent that just became a childless spilled leaf is pushed back
        in) — without it every saturated-budget spill would pay a full
        trie DFS + sort on the admission hot path."""
        if cands is None:
            cands = self._spilled_leaves_lru()
        while cands:
            victim = cands[0]
            if not (
                victim.spilled
                and not victim.children
                and victim.parent is not None
                and victim.parent.children.get(victim.key) is victim
            ):
                cands.pop(0)  # stale: dropped/repatriated since collected
                continue
            if before is not None and (
                victim.last_use, victim.seq
            ) >= before:
                return False
            cands.pop(0)
            self._drop_host_payload(victim)
            self.host_dropped_blocks_total += 1
            if victim.tails:
                self._release([t.block for t in victim.tails.values()])
                self.blocks_held -= len(victim.tails)
                self.evictions_total += len(victim.tails)
                victim.tails.clear()
            parent = victim.parent
            del parent.children[victim.key]
            if parent.spilled and not parent.children:
                _insort_lru(cands, parent)
            return True
        return False

    def _spill_admissible(
        self, victim: _Node, cands: Optional[List[_Node]] = None
    ) -> bool:
        """May ``victim``'s block enter the host tier?  Yes while the
        byte budget has headroom; on a full budget only by trimming a
        strictly LRU-older spilled entry first (LRU spans both tiers —
        a newcomer never displaces a hotter host entry).  ``cands`` is
        the round's shared trim list (see :meth:`_trim_host_one`)."""
        if not self._host_enabled or victim.block < 0:
            return False
        while (
            self.host_bytes_held + self.block_bytes > self.host_bytes_budget
        ):
            if not self._trim_host_one(
                before=(victim.last_use, victim.seq), cands=cands
            ):
                return False
        return True

    def _evict_node(self, victim: _Node):
        """Drop ONE unit from ``victim``: its LRU tail entry if any, else
        the node itself (back-compat single-unit path — ``evict`` routes
        block-holding victims through the spill batch instead)."""
        if victim.tails:
            k = min(
                victim.tails,
                key=lambda f: (
                    victim.tails[f].last_use, victim.tails[f].seq
                ),
            )
            self._release([victim.tails.pop(k).block])
            self.blocks_held -= 1
            self.evictions_total += 1
        else:
            self._drop_node(victim)

    def evict(self, n_blocks: int, protect_step: Optional[int] = None) -> int:
        """Reclaim up to ``n_blocks`` device units LRU-first, releasing
        the cache's references; returns how many were freed (0 = nothing
        evictable).  With the host tier enabled, full-block victims
        SPILL instead of dying: they are marked spilled during selection
        and their KV is gathered to host in ONE batched ``spill_fetch``
        per call (per reclamation round) before the device references
        are released.  Tail entries never spill (they are by-value
        partial blocks) and victims the budget rejects are dropped.

        ONE trie walk serves a whole reclamation round — the
        per-victim-DFS cost of repeated single evictions was
        O(evicted x trie) on the admission hot path.  A round's
        evictions can make parents newly evictable, so the walk repeats
        only while short AND progressing.  Only the cache's own
        reference is ever dropped: blocks pinned by live rows stay
        resident in the pool until those rows finish — evicting a
        pinned prefix cannot corrupt it."""
        freed = 0
        spill_nodes: List[_Node] = []
        spill_blocks: List[int] = []
        # the round's shared host-trim LRU list, built lazily on the
        # first saturated-budget spill and maintained incrementally —
        # one DFS+sort per round, not one per victim
        trim_cands: Optional[List[_Node]] = None
        while freed < n_blocks:
            cands = self._evictable(protect_step)
            if not cands:
                break
            for victim in cands[: n_blocks - freed]:
                if victim.tails:
                    self._evict_node(victim)
                    freed += 1
                    continue
                if (
                    trim_cands is None
                    and self._host_enabled
                    and self.host_bytes_held + self.block_bytes
                    > self.host_bytes_budget
                ):
                    trim_cands = self._spilled_leaves_lru()
                if self._spill_admissible(victim, cands=trim_cands):
                    # mark now so the next walk sees the parent as
                    # spill-eligible; the payload lands in the batched
                    # gather below and the device ref is released there
                    victim.spilled = True
                    victim.ready_step = 0
                    spill_nodes.append(victim)
                    spill_blocks.append(victim.block)
                    self.host_bytes_held += self.block_bytes
                    self.host_blocks_held += 1
                    self.blocks_held -= 1
                    if trim_cands is not None and not victim.children:
                        # a later same-round spill may LRU-displace it
                        _insort_lru(trim_cands, victim)
                else:
                    self._drop_node(victim)
                freed += 1
        if spill_nodes:
            # component tuple: (k, v) for model-dtype pools, (k, v,
            # k_scale, v_scale) for int8 pools — the cache is agnostic
            # and round-trips whatever the engine's gather produced
            payload = self._spill_fetch(spill_blocks)
            for i, node in enumerate(spill_nodes):
                if node.spilled:  # a later trim in this round may have
                    # dropped it.  Per-block COPIES, not views: a view
                    # would pin the round's whole padded gather buffer
                    # for as long as ONE sibling survives, letting real
                    # RSS outgrow host_bytes_held without bound under
                    # trim churn
                    node.host_kv = tuple(a[i].copy() for a in payload)
            self._release(spill_blocks)
            self.spilled_blocks_total += len(spill_nodes)
        return freed

    # -- host-tier restore (swap-in) ----------------------------------------

    def begin_restore(self, nodes: Sequence[_Node]) -> List[Tuple[Any, Any]]:
        """Host (k, v) payloads for ``nodes`` (an admission's
        ``PrefixMatch.restore_nodes``), in order — the engine stacks
        them, allocates destination pool blocks, and dispatches one
        batched scatter (the async swap-in)."""
        assert all(n.spilled and n.host_kv is not None for n in nodes)
        return [n.host_kv for n in nodes]

    def complete_restore(
        self, nodes: Sequence[_Node], blocks: Sequence[int], ready_step: int
    ):
        """Hand restored ``nodes`` their fresh pool ``blocks`` (ownership
        of the engine-allocated references transfers to the cache) and
        gate their use on ``ready_step`` — the engine step after the
        swap-in dispatch, so the requeued admission re-matches into a
        resident prefix deterministically (step-keyed, never a device
        readiness probe)."""
        for node, blk in zip(nodes, blocks):
            self._drop_host_payload(node)
            node.block = int(blk)
            node.ready_step = int(ready_step)
        self.blocks_held += len(nodes)
        self.restored_blocks_total += len(nodes)
        if self.blocks_held > self.capacity_blocks:
            # restores can overshoot the device budget; trim LRU-first but
            # never what this very restore touched (ready_step - 1 is the
            # step the triggering match stamped on the path)
            self.evict(
                self.blocks_held - self.capacity_blocks,
                protect_step=int(ready_step) - 1,
            )

    def flush(self, new_version: Optional[int] = None):
        """Drop every entry IN BOTH TIERS (weight swap: all cached KV —
        device-resident and host-spilled alike — is stale) and move
        ``version`` (to ``new_version``, else +1) so inserts tagged with
        the pre-swap version are rejected."""
        blocks: List[int] = []
        stack = list(self._root.children.values())
        blocks.extend(t.block for t in self._root.tails.values())
        self._root.tails.clear()
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.spilled:
                self._drop_host_payload(n)
                self.host_dropped_blocks_total += 1
            else:
                blocks.append(n.block)
            blocks.extend(t.block for t in n.tails.values())
        if blocks:
            self._release(blocks)
        self._root.children.clear()
        self.blocks_held = 0
        assert self.host_bytes_held == 0 and self.host_blocks_held == 0
        self.version = (
            self.version + 1 if new_version is None else int(new_version)
        )
        self.flushes_total += 1

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return self.blocks_held

    def stats(self) -> Dict[str, int]:
        return {
            "hits_total": self.hits_total,
            "misses_total": self.misses_total,
            "cached_tokens_total": self.cached_tokens_total,
            "insertions_total": self.insertions_total,
            "evictions_total": self.evictions_total,
            "flushes_total": self.flushes_total,
            "blocks_held": self.blocks_held,
            "version": self.version,
            # host spill tier (all zero while host_bytes_budget == 0)
            "spilled_blocks_total": self.spilled_blocks_total,
            "restored_blocks_total": self.restored_blocks_total,
            "host_dropped_blocks_total": self.host_dropped_blocks_total,
            "host_bytes_held": self.host_bytes_held,
            "host_blocks_held": self.host_blocks_held,
            # effective configuration — a mis-tuned fleet (e.g. the
            # config-vs-engine min_match default split) is diagnosable
            # from the metrics RPC instead of invisible at runtime
            "min_match_tokens": self.min_match_tokens,
            "capacity_blocks": self.capacity_blocks,
            "host_bytes_budget": self.host_bytes_budget,
        }

    @staticmethod
    def zero_stats() -> Dict[str, int]:
        """The all-zero stats dict a cache-disabled engine reports (same
        keys as :meth:`stats`, no throwaway cache instance needed)."""
        return {
            "hits_total": 0,
            "misses_total": 0,
            "cached_tokens_total": 0,
            "insertions_total": 0,
            "evictions_total": 0,
            "flushes_total": 0,
            "blocks_held": 0,
            "version": 0,
            "spilled_blocks_total": 0,
            "restored_blocks_total": 0,
            "host_dropped_blocks_total": 0,
            "host_bytes_held": 0,
            "host_blocks_held": 0,
            "min_match_tokens": 0,
            "capacity_blocks": 0,
            "host_bytes_budget": 0,
        }
