"""Radix-tree prefix cache over *historical* token prefixes.

The engine's live-donor prefix sharing (``Engine._find_prefix_donor``)
only forks blocks from sequences that are concurrently resident — the
moment a request finishes, its prefix blocks go back to the free list
and the next request with the same system prompt recomputes them. At
serving scale that is exactly backwards: the shared prefix (system
prompt, few-shot preamble) outlives any single request by hours.

``RadixCache`` generalizes the fork to *all past requests*: when the
engine evicts a sequence, its fully-written whole-block prefix is
inserted into a radix tree keyed by the token stream, and the cache
**pins** those block ids in the ``BlockAllocator`` (an extra named
reference, ``paged.BlockAllocator.pin``) so they survive the sequence.
Admission walks the tree over the new prompt and forks the longest
matching block path instead of recomputing it.

Structure: a fixed-stride radix tree — every edge is exactly one
cache block's worth of tokens (a ``block_size``-tuple), because whole
blocks are the only shareable unit (partially-written blocks are
owner-exclusive by the copy-on-write contract, DESIGN.md §7). A node at
depth d therefore holds the physical block id whose rows cover
positions ``[(d-1)·BS, d·BS)`` of every sequence whose tokens start
with the node's path.

Safety argument (why a cached block can never be written again): the
engine only ever inserts blocks whose every position was already
written, owners only write at their own monotonically-increasing
position, and any future borrower forks the block (refcount +1) and
starts its own writes at the block boundary *after* its forked prefix.
So cached rows are immutable for as long as the node exists.

Eviction is LRU over **leaves** (interior nodes are, by construction,
more-recently-usable than at least one descendant path): when the
allocator cannot serve an admission, the engine asks ``evict(n)`` to
unpin the n least-recently-touched leaf blocks. Unpinning a block that
an active sequence has forked merely drops the cache's own reference —
the sequence keeps its fork, so eviction is always safe.

Dedup: inserting a path that already exists keeps the incumbent block
(equal token prefixes imply bit-equal rows), so concurrent forks of the
same system prompt collapse to one pinned copy.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Sequence


@dataclasses.dataclass
class RadixNode:
    """One block-granular edge of the tree: ``chunk`` is the
    ``block_size``-token edge label, ``block`` the physical block id
    whose rows hold those positions."""
    chunk: tuple
    block: int
    parent: "RadixNode | None"
    children: dict = dataclasses.field(default_factory=dict)
    last_used: int = 0

    @property
    def is_leaf(self) -> bool:
        return not self.children


class RadixCache:
    """Block-granular radix tree over historical prompt prefixes.

    ``allocator`` must expose ``pin(ids)`` / ``unpin(ids)``
    (``serving/paged.BlockAllocator``); the cache owns exactly one pin
    per stored node and nothing else.
    """

    def __init__(self, allocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._root = RadixNode(chunk=(), block=-1, parent=None)
        self._clock = 0                 # monotonic touch counter (LRU)
        self._nodes = 0
        # stats (all monotonic counters; hit_rate derives from them)
        self.lookups = 0                # match() calls
        self.lookup_blocks = 0          # full blocks the prompts offered
        self.hits = 0                   # match() calls returning >= 1 block
        self.hit_blocks = 0             # blocks returned across matches
        self.inserted_blocks = 0        # nodes ever created
        self.evicted_blocks = 0         # nodes ever LRU-evicted

    # ------------------------------------------------------------ queries
    def __len__(self) -> int:
        """Nodes (== pinned blocks) currently in the tree."""
        return self._nodes

    @property
    def hit_rate(self) -> float:
        """Fraction of offered full prompt blocks served from the tree
        (0.0 before any lookup)."""
        return self.hit_blocks / self.lookup_blocks \
            if self.lookup_blocks else 0.0

    def _touch(self, node: RadixNode):
        self._clock += 1
        node.last_used = self._clock

    # ------------------------------------------------------------- verbs
    def match(self, tokens: Sequence[int],
              max_blocks: int | None = None) -> list[int]:
        """Longest-prefix walk: block ids covering the leading whole
        blocks of ``tokens`` that the tree holds, in position order
        (at most ``max_blocks``). Touches the matched path (LRU) and
        records hit stats against what was *offered* — the caller
        forks the ids it actually uses."""
        BS = self.block_size
        offered = len(tokens) // BS
        if max_blocks is not None:
            offered = min(offered, max_blocks)
        self.lookups += 1
        self.lookup_blocks += offered
        ids: list[int] = []
        node = self._root
        for i in range(offered):
            chunk = tuple(tokens[i * BS:(i + 1) * BS])
            child = node.children.get(chunk)
            if child is None:
                break
            ids.append(child.block)
            self._touch(child)
            node = child
        if ids:
            self.hits += 1
            self.hit_blocks += len(ids)
        return ids

    def peek(self, tokens: Sequence[int],
             max_blocks: int | None = None) -> int:
        """Non-mutating probe: how many leading whole blocks of
        ``tokens`` the tree holds. No LRU touch, no stats — the replica
        router's radix-affinity policy scores EVERY replica's cache per
        placement decision, and a probe that counted as a lookup would
        skew hit rates and promote untaken paths in the LRU order."""
        BS = self.block_size
        offered = len(tokens) // BS
        if max_blocks is not None:
            offered = min(offered, max_blocks)
        depth = 0
        node = self._root
        for i in range(offered):
            child = node.children.get(tuple(tokens[i * BS:(i + 1) * BS]))
            if child is None:
                break
            depth += 1
            node = child
        return depth

    def insert(self, tokens: Sequence[int], block_ids: Sequence[int]
               ) -> int:
        """Store the whole-block prefix ``tokens`` (length must be
        ``len(block_ids) * block_size``) → pins every *newly* stored
        block in the allocator. Existing paths are kept (dedup) and
        merely touched. Returns the number of blocks newly pinned."""
        BS = self.block_size
        if len(tokens) != len(block_ids) * BS:
            raise ValueError(
                f"insert of {len(tokens)} tokens vs "
                f"{len(block_ids)} blocks of {BS} — whole blocks only")
        node = self._root
        created = 0
        for i, bid in enumerate(block_ids):
            chunk = tuple(tokens[i * BS:(i + 1) * BS])
            child = node.children.get(chunk)
            if child is None:
                self.allocator.pin([bid])
                child = RadixNode(chunk=chunk, block=bid, parent=node)
                node.children[chunk] = child
                self._nodes += 1
                self.inserted_blocks += 1
                created += 1
            self._touch(child)
            node = child
        return created

    def evict(self, n: int) -> int:
        """Unpin up to ``n`` blocks, least-recently-touched leaves
        first (removing a leaf may expose its parent as the next
        candidate). Returns blocks actually unpinned."""
        freed = 0
        while freed < n:
            leaf = self._lru_leaf()
            if leaf is None:
                break
            self._drop(leaf)
            freed += 1
        return freed

    def clear(self) -> int:
        """Unpin everything (engine shutdown / tests)."""
        return self.evict(self._nodes)

    def reset_stats(self):
        """Zero the counters. Tree contents are untouched."""
        self.lookups = self.lookup_blocks = 0
        self.hits = self.hit_blocks = 0
        self.inserted_blocks = self.evicted_blocks = 0

    # ---------------------------------------------------------- internals
    def _lru_leaf(self) -> RadixNode | None:
        best = None
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.is_leaf:
                if best is None or node.last_used < best.last_used:
                    best = node
            else:
                stack.extend(node.children.values())
        return best

    def _drop(self, leaf: RadixNode):
        self.allocator.unpin([leaf.block])
        del leaf.parent.children[leaf.chunk]
        self._nodes -= 1
        self.evicted_blocks += 1

    def stats(self) -> dict:
        """Counter snapshot (plain dict — metrics/report food)."""
        return {"nodes": self._nodes, "lookups": self.lookups,
                "lookup_blocks": self.lookup_blocks, "hits": self.hits,
                "hit_blocks": self.hit_blocks,
                "hit_rate": self.hit_rate,
                "inserted_blocks": self.inserted_blocks,
                "evicted_blocks": self.evicted_blocks}
