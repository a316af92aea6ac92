"""KV block pool: free-list block allocation + block-granularity
prefix caching over one preallocated paged slab.

The memory tier under the paged serving path (vLLM's PagedAttention
allocator role, Kwon et al. SOSP '23): the slab is carved into
fixed-size token blocks, requests hold per-request BLOCK TABLES of
block ids, and capacity is proportional to tokens actually held — not
to ``max_slots * max_seq`` as with dense slabs. This module is pure
host-side bookkeeping (the device arrays never move); it generalizes
``serving/generative.SlotAllocator``'s free-list + freed-exactly-once
discipline to refcounted, content-addressed blocks:

- **block 0 is the NULL block** — never allocated, the target of every
  unused table entry and every inactive decode lane's write, so the
  compiled gather/scatter step needs no masking of table indices.
- **refcounts** — a block is held by every request whose table points
  at it; prefix-cache hits retain shared blocks, so one block serves
  many requests. ``release()`` of a block not currently held raises
  (the double-free invariant, enforced here like ``SlotAllocator``).
- **prefix cache** — full blocks of a prompt are content-addressed by
  a CHAIN hash (each block's hash folds in its predecessor's, so equal
  hashes mean equal whole prefixes, not just equal block contents).
  A cached block whose refcount drops to zero becomes EVICTABLE (its
  K/V stay valid in the slab) and parks in an LRU; allocation evicts
  from that LRU only when the free list is empty, so caching never
  reduces usable capacity.
- **leak detection** — :meth:`check_invariant` asserts
  ``free + held + evictable == num_blocks - 1`` and (given the active
  block tables) that every refcount equals the number of tables
  holding the block; the paged server runs it every scheduler step
  under ``debug_leaks=True`` (tests/test_paged.py).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.serving.queue import ServerOverloadedError

#: the reserved null/trash block id (see module docstring)
NULL_BLOCK = 0


class PoolExhaustedError(ServerOverloadedError):
    """Typed capacity shed: the block pool cannot hold the request's
    worst-case token footprint right now. A
    :class:`~deeplearning4j_tpu.serving.queue.ServerOverloadedError`,
    so clients back off with ``retry_after_s`` exactly as for a full
    queue — pool pressure is load, not a crash."""


def prefix_block_hashes(tokens: np.ndarray, block_size: int,
                        n_blocks: Optional[int] = None) -> List[bytes]:
    """Chain hashes of the FULL blocks of ``tokens``: entry ``u`` is
    ``H(entry[u-1] || tokens[u*bs:(u+1)*bs])``, so two requests share
    hash ``u`` iff their first ``(u+1)*block_size`` tokens are
    identical — the content address of a reusable KV block. Partial
    trailing blocks are never hashed (their KV rows are still being
    appended to)."""
    toks = np.asarray(tokens, np.int32).reshape(-1)
    full = int(toks.size) // int(block_size)
    if n_blocks is not None:
        full = min(full, int(n_blocks))
    out: List[bytes] = []
    h_prev = b""
    for u in range(full):
        block = toks[u * block_size:(u + 1) * block_size]
        h = hashlib.blake2b(h_prev + block.tobytes(),
                            digest_size=16).digest()
        out.append(h)
        h_prev = h
    return out


class BlockPool:
    """Refcounted free-list allocator + prefix cache over
    ``num_blocks`` KV blocks of ``block_size`` tokens each.

    Block states (block 0 excluded — it is the permanent null block):

    - *free*: on the free list, contents meaningless;
    - *held*: refcount >= 1 — referenced by that many live block
      tables (a private block has refcount 1, a shared cached prefix
      block has one per reader);
    - *evictable*: refcount 0 but registered in the prefix cache — its
      K/V rows are intact and a future prefix hit revives it for free;
      reclaimed LRU-first when the free list runs dry.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"need at least 2 blocks (1 null + 1 usable), "
                f"got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # pop() hands out block 1 first — block 0 is never listed
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # content addressing: hash -> block id, block id -> hash
        self._by_hash: Dict[bytes, int] = {}
        self._hash_of: Dict[int, bytes] = {}
        # zero-ref cached blocks, oldest-released first
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        self.evictions = 0

    # -- capacity -------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Usable blocks (the null block is overhead)."""
        return self.num_blocks - 1

    def free_count(self) -> int:
        """Blocks on the free list proper."""
        return len(self._free)

    def usable_free_count(self) -> int:
        """Blocks allocatable RIGHT NOW: free + evictable-cached."""
        return len(self._free) + len(self._evictable)

    def held_count(self) -> int:
        return len(self._refs)

    def cached_count(self) -> int:
        """Blocks with live cache registrations (held or evictable)."""
        return len(self._by_hash)

    # -- allocation -----------------------------------------------------
    def alloc(self) -> int:
        """Pop a free block (evicting the LRU cached block if the free
        list is empty). The caller holds one reference. Raises
        :class:`PoolExhaustedError` when nothing is reclaimable."""
        if not self._free:
            if not self._evictable:
                raise PoolExhaustedError(
                    f"KV block pool exhausted: all {self.capacity} "
                    f"blocks held by live requests", retry_after_s=0.1)
            b, _ = self._evictable.popitem(last=False)      # LRU
            self._uncache(b)
            self.evictions += 1
            self._free.append(b)
        b = self._free.pop()
        self._refs[b] = 1
        return b

    def retain(self, b: int) -> None:
        """Take one more reference on a held or evictable block (the
        prefix-cache hit path revives evictable blocks here)."""
        if b == NULL_BLOCK:
            raise ValueError("the null block cannot be retained")
        if b in self._refs:
            self._refs[b] += 1
        elif b in self._evictable:
            del self._evictable[b]
            self._refs[b] = 1
        else:
            raise RuntimeError(f"block {b} retained while free")

    def release(self, b: int) -> None:
        """Drop one reference. At zero the block returns to the free
        list — or parks evictable when it is a registered prefix block.
        Releasing an unheld block raises (the double-free invariant)."""
        refs = self._refs.get(b)
        if refs is None:
            raise RuntimeError(
                f"block {b} released twice (or never allocated)")
        if refs > 1:
            self._refs[b] = refs - 1
            return
        del self._refs[b]
        if b in self._hash_of:
            self._evictable[b] = None       # newest at the MRU end
        else:
            self._free.append(b)

    # -- prefix cache ---------------------------------------------------
    def lookup(self, hashes: Sequence[bytes],
               max_blocks: Optional[int] = None) -> List[int]:
        """Longest cached prefix of ``hashes`` (bounded by
        ``max_blocks``), each returned block RETAINED for the caller —
        chain hashing makes a per-position match imply the whole
        prefix matches."""
        out: List[int] = []
        limit = len(hashes) if max_blocks is None \
            else min(len(hashes), int(max_blocks))
        for u in range(limit):
            b = self._by_hash.get(hashes[u])
            if b is None:
                break
            self.retain(b)
            out.append(b)
        return out

    def register(self, h: bytes, b: int) -> bool:
        """Content-address a HELD block the caller just filled. A block
        already registered under another hash, or a hash already naming
        another block (a concurrent fill of the same prefix), leaves
        the cache unchanged — the caller's block stays private."""
        if b == NULL_BLOCK or b not in self._refs:
            raise RuntimeError(f"block {b} must be held to register")
        if h in self._by_hash or b in self._hash_of:
            return False
        self._by_hash[h] = b
        self._hash_of[b] = h
        return True

    def _uncache(self, b: int) -> None:
        h = self._hash_of.pop(b, None)
        if h is not None:
            self._by_hash.pop(h, None)

    def flush_cache(self) -> int:
        """Drop every prefix-cache registration — the hot-reload path:
        cached blocks content-address K/V computed with superseded
        weights, so no FUTURE lookup may reuse them. Evictable blocks
        (refcount 0, kept alive only by their registration) return to
        the free list; held shared blocks keep their refcounts so
        in-flight readers finish — the same accepted in-flight
        staleness as the dense server's ``update_model`` — and, now
        unregistered, go straight back to the free list on their last
        release. Returns the number of registrations dropped."""
        dropped = len(self._by_hash)
        self._by_hash.clear()
        self._hash_of.clear()
        self._free.extend(self._evictable)
        self._evictable.clear()
        return dropped

    # -- lifecycle ------------------------------------------------------
    def reset(self) -> None:
        """Forget everything — the crash-recovery path: a respawned
        worker's slab contents are mid-dispatch garbage, so every held
        block is released and the prefix cache (which addresses slab
        CONTENTS) is dropped wholesale."""
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._refs.clear()
        self._by_hash.clear()
        self._hash_of.clear()
        self._evictable.clear()

    # -- leak detection -------------------------------------------------
    def check_invariant(
            self,
            tables: Optional[Iterable[Sequence[int]]] = None) -> None:
        """Assert pool accounting is exact: every usable block is in
        exactly one of {free, held, evictable}, and — when the live
        block ``tables`` are provided — every refcount equals the
        number of tables holding that block. Raises AssertionError on
        any leak or double-count (satellite 1's debug-flag check)."""
        free = set(self._free)
        held = set(self._refs)
        evict = set(self._evictable)
        assert NULL_BLOCK not in free | held | evict, \
            "null block entered the pool"
        assert not (free & held), f"blocks both free and held: " \
            f"{sorted(free & held)}"
        assert not (free & evict), f"blocks both free and evictable: " \
            f"{sorted(free & evict)}"
        assert not (held & evict), f"blocks both held and evictable: " \
            f"{sorted(held & evict)}"
        n = len(free) + len(held) + len(evict)
        assert n == self.capacity, \
            (f"block leak: {len(free)} free + {len(held)} held + "
             f"{len(evict)} evictable = {n} != capacity {self.capacity}")
        for b, h in self._hash_of.items():
            assert self._by_hash.get(h) == b, \
                f"cache maps out of sync for block {b}"
        assert len(self._by_hash) == len(self._hash_of)
        if tables is not None:
            counts: Dict[int, int] = {}
            for table in tables:
                for b in table:
                    b = int(b)
                    if b != NULL_BLOCK:
                        counts[b] = counts.get(b, 0) + 1
            assert counts == dict(self._refs), \
                (f"refcounts diverge from live tables: pool="
                 f"{dict(sorted(self._refs.items()))} "
                 f"tables={dict(sorted(counts.items()))}")

    def stats(self) -> Dict[str, int]:
        return {"capacity": self.capacity,
                "free": len(self._free),
                "held": len(self._refs),
                "evictable": len(self._evictable),
                "cached": len(self._by_hash),
                "evictions": self.evictions}


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` KV rows."""
    return -(-int(n_tokens) // int(block_size))


#: how many widths a decode program's table comes in (a ring that
#: tumbles: ``RING_RUNGS``), and the multiple of entries each is rounded
#: up to (:func:`table_widths`)
TABLE_RUNGS = 3
RING_RUNGS = 2
TABLE_WIDTH_MULTIPLE = 8


def table_widths(entries: int, rungs: int = TABLE_RUNGS) -> Tuple[int, ...]:
    """The ladder of widths in which the decode program is handed a
    table of ``entries`` blocks a request: ``rungs`` of them, rising,
    each an equal share of the table rounded up to a multiple of
    ``TABLE_WIDTH_MULTIPLE`` entries, the last the whole table (24: 8,
    16, 24; 512: 176, 344, 512; 128 on two rungs: 64, 128). At every
    step boundary the server sends the narrowest that covers the longest
    active lane, so a program gathers, masks and multiplies that many
    blocks a lane and not ``max_seq_len``'s. The rule reads the table
    alone. A table too short to split gives the same width on every
    rung, which is one program. Every rung is one more decode program to
    warm, and the tiers of one spec multiply theirs, so a second tier on
    the ladder (a ring that tumbles, beside the tier that keeps every
    block) takes ``RING_RUNGS``: half and whole."""
    entries, rungs = int(entries), int(rungs)
    widths = []
    for k in range(1, rungs + 1):
        share = blocks_for_tokens(entries * k, rungs)
        rounded = TABLE_WIDTH_MULTIPLE * blocks_for_tokens(
            share, TABLE_WIDTH_MULTIPLE)
        widths.append(min(entries, rounded))
    return tuple(widths)


@dataclass(frozen=True)
class KVLeaf:
    """What a layer caches a token, as one array of the pool: ``width``
    numbers a row, the leaf ``[num_blocks, block_size, width]``. A layer
    of K and V heads has two (``k`` and ``v``, each ``heads x head_dim``
    wide with the heads outermost, which is what the tensor-parallel path
    splits); a latent-attention layer has ONE, the compressed row every
    head reads, and no heads to split (``heads`` 0). ``filled`` is how
    many of the row's numbers the model fills where that is fewer than
    ``width`` (the rest completes a lane tile and holds zeros; 0: all of
    them): the pool is sized and admits by ``width``, and
    ``memory_report`` gives both.

    ``tier`` None: the leaf lies, layer by layer, in the one tier that
    names the layer. A leaf that NAMES its tier lies in that tier for
    every layer of it: two tiers may then cover the same layers, each
    with leaves of its own (a layer of exact K and V rows beside summary
    rows caches four leaves on two tiers)."""

    name: str
    width: int
    heads: int = 0
    filled: int = 0
    tier: Optional[str] = None


class KVLeafUnsupportedError(ValueError):
    """Asked of a leaf what only a K-and-V pair of heads can give: a
    split over ``tp`` chips (the leaf has no heads), int8 rows (the
    scales are per head and channel), or a dense draft beside it."""


@dataclass(frozen=True)
class StateLeaf:
    """What a layer keeps of a request that is NO function of a block's
    tokens: a state of fixed size a lane, whatever the length of its
    context (a short convolution's last inputs). The server holds one
    array a layer ``[max_slots + 1, *shape]`` in the spec's ``kv_dtype``,
    row ``s`` slot ``s``'s and the last a spare row that nothing reads;
    ``layers`` are the model's layers that keep it, in the order of the
    arrays."""

    name: str
    layers: Tuple[int, ...]
    shape: Tuple[int, ...]


class StateUnsupportedError(ValueError):
    """Asked of a spec with a per-lane state what cannot bring a state
    back: a split over ``tp`` chips, or a draft and its verify round (a
    rejected token cannot be taken back out of the state without a
    snapshot)."""


@dataclass(frozen=True)
class KVTier:
    """The layers of a model whose KV leaves share one block pool and
    one table a request.

    ``window`` None: every block of a request is kept until it retires,
    and block ``u`` of a request sits in entry ``u`` of its table. With a
    ``window`` a query at position ``p`` reads positions ``j > p -
    window`` only, so a block that lies wholly behind that is given back
    at the next step boundary, and the table is a RING of
    :meth:`table_blocks` entries with block ``u`` in entry ``u %
    table_blocks``: a request's table, and what a program gathers
    through it, stop growing with the request. A window that ``tumbles``
    does not slide with the query: a query at ``p`` reads the positions
    from ``window * (p // window)`` on, so the whole window is given
    back at once when ``p`` reaches the next multiple (a whole number of
    blocks: the server refuses another ``block_size``), and a prompt's
    runs are cut at those multiples.

    ``row_tokens``: how many tokens one row of the tier stands for. 1 is
    a row a token; with ``c`` the tier takes one row for every ``c``
    tokens, when the last of them is written (a summary of a chunk), so
    ``n`` tokens are ``n // c`` rows and the tier's blocks, tables and
    reservations count those.

    ``name`` suffixes the tier's keys in a program's io
    (``tables.<name>``); the one unnamed tier of a model whose layers
    are all alike keeps the bare keys."""

    name: str
    layers: Tuple[int, ...]
    window: Optional[int] = None
    row_tokens: int = 1
    tumbles: bool = False

    def __post_init__(self):
        if self.row_tokens < 1:
            raise ValueError(f"row_tokens must be >= 1, got "
                             f"{self.row_tokens}")
        if self.tumbles and (self.window is None or self.row_tokens != 1):
            raise ValueError("a tier that tumbles has a window of rows "
                             "that stand for one token each")
        if self.window is not None and self.row_tokens != 1:
            raise ValueError("a window is counted in rows of one token")

    def key(self, base: str) -> str:
        return f"{base}.{self.name}" if self.name else base

    def rows(self, n_tokens):
        """Rows that ``n_tokens`` tokens (a number or an array) have
        written."""
        return n_tokens // self.row_tokens

    def blocks(self, n_tokens: int, block_size: int) -> int:
        """Blocks that hold the rows of ``n_tokens`` tokens."""
        return blocks_for_tokens(self.rows(int(n_tokens)), block_size)

    def table_blocks(self, block_size: int, max_blocks: int) -> int:
        """Entries of a request's table: every block of the longest
        request (``max_blocks`` blocks of one token a row), or the most
        blocks one query's window can touch."""
        if self.window is None:
            return self.blocks(int(max_blocks) * int(block_size), block_size)
        if self.tumbles:
            return min(int(max_blocks),
                       blocks_for_tokens(self.window, block_size))
        return min(int(max_blocks),
                   blocks_for_tokens(self.window, block_size) + 1)

    def first_live_block(self, position: int, block_size: int) -> int:
        """The lowest block a query at ``position`` or later reads."""
        if self.window is None:
            return 0
        if self.tumbles:
            return self.window * (int(position) // self.window) \
                // int(block_size)
        return max(0, int(position) - self.window + 1) // int(block_size)

    def peak_blocks(self, n_tokens: int, block_size: int,
                    run_tokens: int) -> int:
        """The most blocks a request of ``n_tokens`` holds at once when
        its rows arrive in runs of at most ``run_tokens`` (a prompt's
        chunks; one token a decode step): a run's own blocks and those
        its first query still reads. Under a window that tumbles a run
        lies in one window, so that is the window's blocks."""
        whole = self.blocks(n_tokens, block_size)
        if self.window is None:
            return whole
        if self.tumbles:
            return min(whole, blocks_for_tokens(self.window, block_size))
        return min(whole, blocks_for_tokens(
            self.window + int(run_tokens), block_size) + 1)


__all__ = ["BlockPool", "PoolExhaustedError", "NULL_BLOCK", "KVTier",
           "KVLeaf", "KVLeafUnsupportedError", "StateLeaf",
           "StateUnsupportedError",
           "TABLE_RUNGS", "RING_RUNGS", "TABLE_WIDTH_MULTIPLE",
           "table_widths",
           "prefix_block_hashes", "blocks_for_tokens"]
