"""Paged-KV generative serving: block pool + prefix cache +
tensor-parallel dispatch over the continuous-batching scheduler.

The memory tier vLLM proved out (PagedAttention, Kwon et al. SOSP '23)
under the Orca-style step scheduler PR 15 built: instead of one dense
``[layers, max_slots, heads, max_seq, head_dim]`` row per slot, K/V
live in fixed-size token BLOCKS carved from a preallocated pool, one
array a layer ``[num_blocks, block_size, heads * head_dim]`` for K and
one for V, and each request holds a BLOCK TABLE grown one block at a
time at decode-step boundaries. Capacity is proportional to tokens
actually held — a 12-token chat costs one block, not a ``max_seq`` row
— so the same HBM holds more concurrent requests.

Three layers, all riding :class:`GenerativeServer`'s scheduler/queue/
resilience plumbing unchanged:

- **block pool** (``pool.py``) — refcounted free-list allocator with
  the null-block-0 convention; admission is gated on BLOCKS two ways:
  ``submit`` reserves each request's worst-case block footprint against
  pool capacity (shedding typed :class:`PoolExhaustedError` with a
  ``retry_after_s`` hint when the pool cannot ever hold it — the
  reservation is released exactly once via the request future's done
  callback), and ``_can_place`` holds a queued request at the FRONT
  until enough blocks are actually free. The conservative reservation
  means a placed request can never fail a block allocation mid-decode.
- **prefix caching** — full prompt blocks are content-addressed by
  chain hash; a repeated system prompt/few-shot prefix prefills only
  its SUFFIX (``hist`` cached tokens skip straight to reused blocks),
  so repeated-prefix TTFT approaches one decode step. Refcounts release
  exactly once on completion, shed, cancel AND crash-recovery requeue
  (``pool.reset()`` on worker respawn — the pool is mid-dispatch
  garbage, so the cache addressing its contents drops wholesale); a
  hot reload (``update_model``) fences the cache too — cached K/V
  belong to the superseded weights, so the worker flushes every
  registration at its next step boundary before admitting anyone.
- **tensor parallel** — ``tp > 1`` builds a ``{model: tp}`` mesh from
  the PR-7 :class:`~deeplearning4j_tpu.parallel.sharding.ShardingSpec`
  ("transformer" preset: qkv/fc column, proj row, wte vocab-sharded),
  shards every KV leaf by HEADS (its last axis), replicates the tiny
  host io (tables, tokens, positions), and lets GSPMD propagate through the
  jitted step — a model larger than one chip's HBM serves, and greedy
  tokens still match the single-chip server (tests/test_paged.py).

Two more things a model may ask of the tier (``zoo/smallthinker.py``
does): its layers may fall into several TIERS (``pool.KVTier``), each
with a block pool, a table a request and a block count of its own,
reserved and admitted against one by one, a WINDOW tier giving a block
back at the first step boundary at which it lies wholly behind the
window; and a prompt longer than the largest bucket runs through the
one prefill program in CHUNKS of that bucket, ``hist`` advancing, the
first token from the last run. A spec whose layers are all alike has
one unnamed tier and takes the path it always took, with the same
programs and arguments.

What a layer caches a token is the spec's to say (``pool.KVLeaf``): K
and V rows of ``heads x head_dim`` where it says nothing, ONE compressed
row for a latent-attention model (``zoo/glm_moe_lite.py``), whose pool has
one array a layer and no V at all; allocation, the bytes accounted and
admission follow the row. A leaf may NAME its tier: two tiers then cover
the same layers, a layer caches four leaves, a tier's row may stand for
several tokens (``KVTier.row_tokens``: one summary row a chunk, beside
the exact rows) and a window may TUMBLE, given back whole when the query
enters the next one (``zoo/evabyte.py``); blocks, tables, reservations
and reports count ROWS, tier by tier.

The DECODE program reads as many table entries as its longest lane
holds: at every step boundary the server sends each tier's tables cut to
the narrowest of ``pool.table_widths`` (three widths, two for a ring
that tumbles, a rule on the table's entries alone) that covers THAT
TIER's longest active lane, a rung a tier, and warm-up builds the
program once a combination of the tiers' widths. A tier is on this
ladder if it keeps every block or if its window TUMBLES: such a ring
refills from entry 0 after every turn, so the entries a lane's query can
see are the first ``stop - first`` of it, as in a tier that keeps every
block. A window that SLIDES is addressed ``u % entries`` with every entry
live once a lane has wrapped, and is never cut; prefill and verify take
whole tables.

Correctness contract: with ``max_blocks_per_req * block_size ==
max_seq`` the gathered paged context is elementwise identical to the
dense slab context (zoo/gpt.py ``gpt_paged_decode_fns``), so greedy
output is bit-identical to :func:`~deeplearning4j_tpu.serving.
generative.greedy_decode` — paged vs dense is a memory-layout change,
not a numerics change. See docs/serving.md "Paged KV & prefix caching".
"""
from __future__ import annotations

import threading
from bisect import bisect_left
from dataclasses import dataclass
from itertools import product
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.compilecache.aot import AOTDispatch, ph_shape_sig
from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.serving.generative import (GenerationHandle,
                                                   GenerationRequest,
                                                   GenerativeMetrics,
                                                   GenerativeServer,
                                                   SlotAllocator)
from deeplearning4j_tpu.serving.metrics import safe_ratio
from deeplearning4j_tpu.serving.paged.pool import (NULL_BLOCK, RING_RUNGS,
                                                   TABLE_RUNGS,
                                                   BlockPool, KVLeaf,
                                                   KVLeafUnsupportedError,
                                                   KVTier,
                                                   PoolExhaustedError,
                                                   blocks_for_tokens,
                                                   prefix_block_hashes,
                                                   table_widths)


@dataclass
class PagedGenerativeSpec:
    """A model's PAGED generative-serving contract (produced by e.g.
    ``zoo.gpt.gpt_paged_spec``) — the block-table analogue of
    :class:`~deeplearning4j_tpu.serving.generative.GenerativeSpec`.

    - ``params()`` pulls the current trained parameter arrays by name.
    - ``make_fns(block_size, max_blocks_per_req)`` builds the pure
      ``(prefill_fn, decode_fn)`` pair — or ``(prefill_fn, decode_fn,
      verify_fn)`` triple when the model supports speculative decoding
      — for one block geometry (the server memoizes the jitted
      dispatchers per geometry, so every server over the same model +
      geometry shares one compile set). Io contracts are documented on
      ``zoo.gpt.gpt_paged_decode_fns``. ``decode_fn`` takes the width of
      a tier's table from its INPUT: for a tier without a window, and
      for one whose window tumbles, the server hands it ``tables[:,
      :W]`` with ``W`` no more than the tier's entries and covering
      every active lane's live blocks (``pool.table_widths``; each tier
      its own ``W``), and the program must read no further. A tumbling
      ring is still addressed by the RING's entries (block ``u`` in
      entry ``u % entries``), of which the table handed over is the
      first ``W``: an entry beyond holds nothing a query sees. A
      sliding window's ring, and every table of ``prefill_fn`` and
      ``verify_fn``, come whole.
    - ``kv_shape(num_blocks, block_size)`` gives the pool's five
      numbers ``(layers, num_blocks, heads, block_size, head_dim)``.
      The server holds K and V each as a tuple of ``layers`` arrays
      ``[num_blocks, block_size, heads * head_dim]``, one a layer (the
      tensor-parallel path shards the last axis, whose outermost part is
      the heads), hands both tuples to every program and takes them
      back: the programs donate each leaf and write it in place.
    - ``kv_leaves`` says what a layer caches a token where that is not
      the pair above (``pool.KVLeaf``: a name and the row's width; one
      or two a layer). A latent-attention model names ONE leaf, its
      compressed row: the pool then holds one array a layer ``[num_blocks,
      block_size, width]``, the programs are handed ``(that tuple, ())``
      where a pair's get ``(K, V)``, and the bytes the server sizes,
      reports and admits by are that row's. Of ``kv_shape`` the layers,
      blocks and block size are then read, the heads and head size not.
      A leaf without heads has nothing for ``tp`` to split, no int8
      scales and no dense draft beside it: each is refused typed
      (``pool.KVLeafUnsupportedError``). More than two leaves come in
      (K-like, V-like) PAIRS, every leaf naming its tier
      (``KVLeaf.tier``): the programs are then handed ``(the first
      leaves of the pairs, the second leaves)``, each a tuple of one
      tuple of arrays a leaf, a leaf's arrays one a layer of its tier.
    - ``kv_tiers`` says, layer by layer, which tier a leaf belongs to
      and the tier's window (``pool.KVTier``); ``None`` is one unnamed
      tier of every layer. A leaf has its TIER's ``num_blocks``, and the
      programs take one table per tier under the tier's keys. A tier
      whose rows stand for several tokens (``KVTier.row_tokens``) is
      told where the rows go that a run completes: ``write_block.<t>``
      holds one block a ROW the run can complete (the null block for a
      row it does not), and the program finds the offset from the
      positions.
    - ``program_counters`` names what the decode program counts on the
      device (an expert layer's routing): its next tokens are then
      ``[max_slots + len(program_counters)]``, the counts of the step
      behind the tokens in this order, and the server adds them to its
      counters of these names at the sync the tokens pay for.
    """

    params: Callable[[], Dict[str, object]]
    make_fns: Callable[[int, int], tuple]
    kv_shape: Callable[[int, int], tuple]
    vocab_size: int
    max_seq_len: int
    num_heads: int
    kv_dtype: str = "float32"
    eos_id: Optional[int] = None
    kv_tiers: Optional[Sequence[KVTier]] = None
    program_counters: Tuple[str, ...] = ()
    kv_leaves: Optional[Sequence[KVLeaf]] = None


class PrefixCacheUnsupportedError(ValueError):
    """Asked for the prefix cache over a spec with a window tier or more
    than one tier: a cached prefix would have to bring back the window
    tier's blocks too, which were given back while the request ran."""


class _TierState:
    """The host's books of one KV tier: its pool and, per slot, the
    block indices ``[first, stop)`` the slot holds and the table the
    programs read through. Block ``u`` sits in entry ``u % entries`` of
    the table. In a tier that keeps every block that is entry ``u``, a
    block is in the table from the moment it is allocated, and ``first``
    stays 0. A window tier's table is a ring of the blocks whose rows
    are WRITTEN: while a run of a program fills fresh blocks, the
    entries they will take still hold blocks the run reads, so fresh
    blocks wait in ``pending`` (the program is told where its rows go)
    until :meth:`advance`. ``widths`` are the widths the decode program
    reads the table in, rung by rung (``pool.table_widths``). A tier is
    ``on_ladder`` if it keeps every block or its window tumbles (the
    ring then refills from entry 0 after every turn, ``first`` a
    multiple of the ring: a lane's live blocks sit in the first ``stop -
    first`` entries; on ``pool.RING_RUNGS`` rungs, since the tiers'
    widths multiply the programs to warm); a ring that slides is read
    whole on every rung."""

    def __init__(self, tier: KVTier, block_size: int, entries: int,
                 num_blocks: int, max_slots: int):
        self.tier, self.BS, self.entries = tier, int(block_size), int(entries)
        self.on_ladder = tier.window is None or tier.tumbles
        self.widths = (
            table_widths(self.entries) if tier.window is None
            else table_widths(self.entries, RING_RUNGS) if tier.tumbles
            else (self.entries,) * TABLE_RUNGS)
        self.pool = BlockPool(num_blocks, block_size)
        self.tables = np.zeros((max_slots, self.entries), np.int32)
        self.first = np.zeros(max_slots, np.int32)
        self.stop = np.zeros(max_slots, np.int32)
        self.pending: List[Dict[int, int]] = [{} for _ in range(max_slots)]

    def grow(self, s: int, stop: int) -> int:
        """Blocks for slot ``s`` up to index ``stop``; how many were
        allocated. Raises :class:`PoolExhaustedError` with what it did
        allocate on the books, for :meth:`clear` to give back."""
        n = 0
        for u in range(int(self.stop[s]), int(stop)):
            b = self.pool.alloc()
            if self.tier.window is None:
                self.tables[s, u] = b
            else:
                self.pending[s][u] = b
            self.stop[s] = u + 1
            n += 1
        return n

    def advance(self, s: int, position: int) -> int:
        """The rows of slot ``s`` before ``position`` are written and the
        next query is at ``position`` or later: fresh blocks enter the
        table, and every block that lies wholly behind that query's
        window is given back. Returns how many were. (Window tiers
        only: a tier that keeps every block has nothing to advance.)"""
        live = min(self.tier.first_live_block(position, self.BS),
                   int(self.stop[s]))
        if live <= self.first[s] and not self.pending[s]:
            return 0
        n = 0
        for u in range(int(self.first[s]), live):
            b = self.pending[s].pop(u, None)
            if b is None:
                e = u % self.entries
                b, self.tables[s, e] = int(self.tables[s, e]), NULL_BLOCK
            self.pool.release(b)
            n += 1
        self.first[s] = max(int(self.first[s]), live)
        for u, b in self.pending[s].items():
            e = u % self.entries
            if self.tables[s, e] != NULL_BLOCK:
                raise RuntimeError(
                    f"tier {self.tier.name!r}, slot {s}: block {u} has no "
                    f"free table entry at position {position}")
            self.tables[s, e] = b
        self.pending[s].clear()
        return n

    def decode_width(self, active: np.ndarray) -> int:
        """Entries of the table the decode program is handed for the
        ``active`` lanes: the narrowest of ``widths`` that covers the
        entries the longest of them takes, its live blocks ``stop -
        first`` (one still in ``pending`` counted: its entry is the next
        one); the whole ring where it slides."""
        if not self.on_ladder:
            return self.entries
        need = int((self.stop[active] - self.first[active]).max())
        return self.widths[bisect_left(self.widths, need)]

    def blocks(self, s: int) -> List[int]:
        """The blocks slot ``s`` holds, in order."""
        return [self.pending[s][u] if u in self.pending[s]
                else int(self.tables[s, u % self.entries])
                for u in range(int(self.first[s]), int(self.stop[s]))]

    def block_at(self, s: int, u: int) -> int:
        """Block ``u`` of slot ``s``, in the table or still pending."""
        b = self.pending[s].get(u) if self.pending[s] else None
        return self.tables[s, u % self.entries] if b is None else b

    def clear(self, s: int) -> int:
        held = self.blocks(s)
        for b in held:
            self.pool.release(b)
        self.tables[s, :] = NULL_BLOCK
        self.first[s] = self.stop[s] = 0
        self.pending[s].clear()
        return len(held)

    def reset(self) -> None:
        self.pool.reset()
        self.tables[:] = NULL_BLOCK
        self.first[:] = 0
        self.stop[:] = 0
        for p in self.pending:
            p.clear()


def _paged_dispatchers(spec: PagedGenerativeSpec, kv_shape: tuple,
                       block_size: int, max_blocks: int,
                       mesh_key) -> Dict[str, AOTDispatch]:
    """One (decode, prefill) dispatcher pair per (spec, pool geometry,
    mesh), memoized on the spec object — the paged analogue of
    ``generative._spec_dispatchers``. ``make_fns`` builds fresh closure
    objects each call, so without this memo a second server (a restart,
    a canary) would recompile every program; the mesh key keeps AOT
    executables lowered for one device layout from colliding with a
    differently-sharded server's identical io signature."""
    cache = getattr(spec, "_disp_cache", None)
    if cache is None:
        cache = {}
        spec._disp_cache = cache
    key = (tuple(int(d) for d in kv_shape), int(block_size),
           int(max_blocks), mesh_key)
    pair = cache.get(key)
    if pair is None:
        import jax
        fns = spec.make_fns(int(block_size), int(max_blocks))
        prefill_fn, decode_fn = fns[0], fns[1]
        verify_fn = fns[2] if len(fns) > 2 else None
        pair = {
            "decode": AOTDispatch(
                jax.jit(decode_fn, donate_argnums=(1, 2)), ph_arg=3),
            "prefill": AOTDispatch(
                jax.jit(prefill_fn, donate_argnums=(1, 2)), ph_arg=3)}
        if verify_fn is not None:
            pair["verify"] = AOTDispatch(
                jax.jit(verify_fn, donate_argnums=(1, 2)), ph_arg=3)
        cache[key] = pair
    return pair


class PagedMetrics(GenerativeMetrics):
    """GenerativeMetrics plus the paged lanes: pool occupancy (held
    blocks per decode step over capacity), prefix-cache hit rate,
    blocks-per-retired-request, alloc/release/eviction counters. All
    ratios are :func:`~deeplearning4j_tpu.serving.metrics.safe_ratio`
    — 0.0 at cold start, never NaN (the fold_serving/ui contract)."""

    def __init__(self, max_slots: int = 0, num_blocks: int = 0,
                 block_size: int = 0):
        super().__init__(max_slots)
        self.num_blocks = int(num_blocks)     # usable (non-null) blocks
        self.block_size = int(block_size)
        self.kv_bytes_per_token = 0           # one row of every leaf
        for c in ("prefix_lookups", "prefix_hits", "prefix_blocks_hit",
                  "prefix_cache_flushes",
                  "blocks_allocated", "blocks_released",
                  "blocks_held_sum", "pool_samples",
                  "request_blocks_sum", "requests_retired",
                  # a window tier, sampled where blocks_held_sum is: the
                  # blocks it holds, the blocks it has (a sum, so that a
                  # difference of two readings divides by the samples
                  # between them), and what it gave back behind a window
                  "window_blocks_held_sum", "window_blocks_capacity_sum",
                  "window_blocks_released",
                  # table entries a lane the decode program was handed,
                  # and the whole table's, summed over plain decode steps
                  # and the tiers on the ladder (every tier but a window
                  # that slides)
                  "decode_table_entries_sum", "decode_table_capacity_sum",
                  # rows BY KIND, a plain decode step over its active
                  # lanes, each in rows x layers: what the lanes' tiers
                  # hold, the positions they stand at (what a row a
                  # token and layer would hold), what the program was
                  # handed through every tier's table; what a program
                  # counts itself (kv_rows_attended_sum) comes with its
                  # next tokens (PagedGenerativeSpec.program_counters)
                  "kv_rows_held_sum", "kv_positions_sum",
                  "kv_rows_gathered_sum",
                  # rows of a tier whose row stands for several tokens
                  # (one a lane and completed chunk), and windows that
                  # tumbled (one a lane and turn)
                  "summary_rows_written", "window_turns"):
            self.counters[c] = 0
        self._pool_stats: Dict[str, int] = {}

    def observe_pool(self, held: int, stats: Optional[dict] = None,
                     window_held: int = 0, window_capacity: int = 0) -> None:
        """One per-decode-step occupancy sample: blocks held over all
        tiers, and the window tiers' own share of that."""
        with self._lock:
            self.counters["blocks_held_sum"] += int(held)
            self.counters["pool_samples"] += 1
            self.counters["window_blocks_held_sum"] += int(window_held)
            self.counters["window_blocks_capacity_sum"] += \
                int(window_capacity)
            if stats is not None:
                self._pool_stats = dict(stats)

    def observe_tables(self, entries: int, capacity: int) -> None:
        """One decode step's tables: ``entries`` a lane sent of
        ``capacity``, over the tiers on the ladder."""
        with self._lock:
            self.counters["decode_table_entries_sum"] += int(entries)
            self.counters["decode_table_capacity_sum"] += int(capacity)

    def observe_rows(self, held: int, positions: int,
                     gathered: int) -> None:
        """One decode step's rows over its active lanes and the layers:
        held by the tiers, one a position, handed to the program."""
        with self._lock:
            self.counters["kv_rows_held_sum"] += int(held)
            self.counters["kv_positions_sum"] += int(positions)
            self.counters["kv_rows_gathered_sum"] += int(gathered)

    def observe_prefix(self, looked_up: bool, blocks_hit: int) -> None:
        with self._lock:
            if looked_up:
                self.counters["prefix_lookups"] += 1
            if blocks_hit > 0:
                self.counters["prefix_hits"] += 1
                self.counters["prefix_blocks_hit"] += int(blocks_hit)

    def observe_blocks(self, allocated: int = 0, released: int = 0,
                       behind_window: int = 0, turns: int = 0) -> None:
        """``behind_window`` of the ``released`` were a window tier's,
        given back while their request ran, ``turns`` times a whole
        window at once."""
        with self._lock:
            self.counters["blocks_allocated"] += int(allocated)
            self.counters["blocks_released"] += int(released)
            self.counters["window_blocks_released"] += int(behind_window)
            self.counters["window_turns"] += int(turns)

    def observe_request_blocks(self, n: int) -> None:
        with self._lock:
            self.counters["request_blocks_sum"] += int(n)
            self.counters["requests_retired"] += 1

    def to_record(self) -> dict:
        rec = super().to_record()
        with self._lock:
            c = self.counters
            rec["paged"] = {
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "pool_occupancy": round(safe_ratio(
                    c["blocks_held_sum"],
                    c["pool_samples"] * self.num_blocks), 4),
                "prefix_hit_rate": round(safe_ratio(
                    c["prefix_hits"], c["prefix_lookups"]), 4),
                "prefix_blocks_hit": c["prefix_blocks_hit"],
                "blocks_per_request": round(safe_ratio(
                    c["request_blocks_sum"], c["requests_retired"]), 3),
                "blocks_allocated": c["blocks_allocated"],
                "blocks_released": c["blocks_released"],
                "prefix_cache_flushes": c["prefix_cache_flushes"],
                "evictions": self._pool_stats.get("evictions", 0),
                "cached_blocks": self._pool_stats.get("cached", 0),
                "held_blocks": self._pool_stats.get("held", 0)}
        return rec

    def stats(self) -> str:
        rec = self.to_record()
        p = rec["paged"]
        return "\n".join([
            super().stats(),
            f"  paged: {p['num_blocks']} blocks x {p['block_size']} "
            f"tokens, occupancy {p['pool_occupancy']:.1%}, prefix hit "
            f"rate {p['prefix_hit_rate']:.1%} "
            f"({p['prefix_blocks_hit']} blocks), "
            f"{p['blocks_per_request']} blocks/request, "
            f"{p['evictions']} evictions"])


class PagedGenerativeServer(GenerativeServer):
    """Continuous-batching server over a paged KV block pool.

    ::

        spec = zoo.gpt.gpt_paged_spec(sd, cfg)
        srv = PagedGenerativeServer(spec, max_slots=8, block_size=16,
                                    kv_hbm_bytes=1 << 30)
        tokens = srv.generate([1, 2, 3], max_new_tokens=32)

    - ``block_size``: tokens per KV block (16 is the vLLM default —
      small enough that a short chat wastes < block_size rows, large
      enough that table gathers stay coarse).
    - ``num_blocks`` / ``kv_hbm_bytes``: pool size, directly or as an
      HBM budget (``num_blocks = budget // bytes_per_block``). Default:
      the dense-equivalent worst case (``max_slots`` requests at full
      ``max_seq``) — same capacity floor as the dense server, but
      short requests release what they don't use.
    - ``tp``: tensor-parallel ways over the ``model`` mesh axis
      (params sharded per the "transformer" preset, every KV leaf
      sharded by heads; requires ``num_heads % tp == 0``).
    - ``prefix_cache=False`` disables content-addressed block reuse
      (every prefill allocates fresh blocks). The default is on wherever
      the spec allows it: a spec with a window tier or several tiers
      serves without, and asking for it there raises
      :class:`PrefixCacheUnsupportedError`.
    - ``num_blocks``, ``kv_hbm_bytes``, ``tp > 1`` and a draft are for
      a spec with one tier that keeps every block; every tier of any
      other spec has the default size.
    - ``debug_leaks=True`` runs the pool's full accounting invariant
      against the live block tables after EVERY decode step (test/CI
      flag; O(blocks) per step).

    Everything else (admission, queueing, SLO shed, streaming,
    supervision, crash requeue) is inherited from
    :class:`GenerativeServer` unchanged.
    """

    def __init__(self, spec, max_slots: int = 8, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_hbm_bytes: Optional[int] = None,
                 max_blocks_per_req: Optional[int] = None,
                 tp: int = 1, devices: Optional[Sequence] = None,
                 prefix_cache: Optional[bool] = None,
                 debug_leaks: bool = False, **kw):
        if int(block_size) < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if int(tp) < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        # subclass knobs FIRST: super().__init__ calls the _make_metrics
        # and _init_kv hooks below, which read them
        self.block_size = int(block_size)
        self._num_blocks_arg = num_blocks
        self._kv_hbm_bytes_arg = kv_hbm_bytes
        self._maxb_arg = max_blocks_per_req
        self.tp = int(tp)
        self._devices_arg = devices
        self._prefix_cache_arg = prefix_cache
        self.debug_leaks = bool(debug_leaks)
        self._strategy = None
        self._kv_sharding = None
        self._commit_lock = threading.Lock()
        self._reserved: List[int] = []   # worst-case blocks, a tier
        self._turns = 0       # windows that tumbled at the last boundary
        # hot-reload fence: set by update_model(), consumed by the
        # worker at its next step boundary (the pool is worker-owned)
        self._prefix_flush_pending = threading.Event()
        super().__init__(spec, max_slots=max_slots, **kw)

    # -- hook overrides -------------------------------------------------
    def _coerce_spec(self, spec):
        if not isinstance(spec, PagedGenerativeSpec):
            if hasattr(spec, "paged_spec"):
                spec = spec.paged_spec()
            else:
                raise TypeError(
                    f"{type(spec).__name__} is not paged-servable: pass "
                    f"a PagedGenerativeSpec (e.g. from "
                    f"zoo.gpt.gpt_paged_spec)")
        return spec

    def _make_metrics(self) -> PagedMetrics:
        # pool geometry is resolved later in _init_kv, which backfills
        # num_blocks/block_size on this instance
        metrics = PagedMetrics(self.max_slots, 0, self.block_size)
        self._program_counters = tuple(self.spec.program_counters)
        for c in self._program_counters:
            metrics.counters[c] = 0
        return metrics

    def _init_kv(self) -> None:
        """Allocate the paged memory tier: for each leaf the spec names
        (K and V where it names none) ``layers`` arrays ``[num_blocks,
        block_size, width]`` (block 0 reserved as the null block), the
        block pool, per-slot block tables, and the geometry-memoized
        dispatchers. With ``tp > 1`` also builds the mesh and shards
        params + leaves."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.memory import AllocationsTracker
        from deeplearning4j_tpu.monitor import memstats
        from deeplearning4j_tpu.ndarray.dtype import DataType
        spec = self.spec
        BS = self.block_size
        self._maxb = int(self._maxb_arg) if self._maxb_arg is not None \
            else blocks_for_tokens(self.max_seq_len, BS)
        if self._maxb * BS < self.max_seq_len:
            raise ValueError(
                f"max_blocks_per_req {self._maxb} x block_size {BS} "
                f"cannot hold max_seq_len {self.max_seq_len}")
        self._kv_dtype = DataType.from_any(spec.kv_dtype).jnp
        itemsize = jnp.zeros((), self._kv_dtype).dtype.itemsize
        layers, _, heads, _, head_dim = (int(d) for d in
                                         spec.kv_shape(1, BS))
        leaves = tuple(spec.kv_leaves) if spec.kv_leaves is not None \
            else (KVLeaf("k", heads * head_dim, heads),
                  KVLeaf("v", heads * head_dim, heads))
        on_tier = [lf.tier is not None for lf in leaves]
        if not (1 <= len(leaves) <= 2 and not any(on_tier)
                or len(leaves) % 2 == 0 and all(on_tier)):
            raise ValueError(
                f"a layer caches one or two leaves, or pairs of leaves "
                f"that each name their tier; the spec names "
                f"{[(lf.name, lf.tier) for lf in leaves]}")
        for lf in leaves:
            asked = None if lf.heads else (
                f"tp={self.tp}" if self.tp > 1 else
                "int8 rows" if jnp.dtype(self._kv_dtype) == jnp.int8 else
                "a dense draft" if self.draft_spec is not None else None)
            if asked:
                raise KVLeafUnsupportedError(
                    f"KV leaf {lf.name!r} ({lf.width} numbers a token, no "
                    f"heads) cannot serve {asked}: that is for a K-and-V "
                    f"pair of heads")
        self._kv_leaves = leaves
        tiers = tuple(spec.kv_tiers) if spec.kv_tiers is not None \
            else (KVTier("", tuple(range(layers))),)
        if all(on_tier):
            if {lf.tier for lf in leaves} != {t.name for t in tiers} \
                    or any(a.tier != b.tier
                           for a, b in zip(leaves[0::2], leaves[1::2])):
                raise ValueError(
                    f"leaves {[(lf.name, lf.tier) for lf in leaves]} do "
                    f"not pair up over the tiers {[t.name for t in tiers]}")
        elif sorted(i for t in tiers for i in t.layers) \
                != list(range(layers)):
            raise ValueError("kv_tiers must name every layer once")
        # a prompt's runs start at multiples of the largest bucket and
        # of a window that tumbles: both must end on a row of every tier,
        # and such a window on a block
        starts = [self._buckets.max_rows] + [t.window for t in tiers
                                             if t.tumbles]
        for t in tiers:
            if t.tumbles and t.window % BS:
                raise ValueError(
                    f"tier {t.name!r} gives a window of {t.window} rows "
                    f"back whole: not a whole number of blocks of {BS}")
            if any(n % t.row_tokens for n in starts):
                raise ValueError(
                    f"tier {t.name!r} has a row every {t.row_tokens} "
                    f"tokens: the largest bucket and a window that "
                    f"tumbles ({starts}) must be multiples of it")
        self._tumbling = tuple(starts[1:])

        tier_leaves = self._tier_leaves

        def bytes_per_token(width_of):
            # rows x layers a token of a request that keeps them all
            return sum(len(t.layers) * width_of(lf) * itemsize
                       // t.row_tokens
                       for t in tiers for lf in tier_leaves(t))

        self.kv_bytes_per_token = bytes_per_token(lambda lf: lf.width)
        self._kv_bytes_per_token_filled = bytes_per_token(
            lambda lf: lf.filled or lf.width)
        plain = len(tiers) == 1 and tiers[0].window is None \
            and tiers[0].row_tokens == 1
        if self._prefix_cache_arg and not plain:
            raise PrefixCacheUnsupportedError(
                "the prefix cache serves one tier that keeps every block; "
                f"this spec has {[(t.name, t.window) for t in tiers]}")
        self.prefix_cache_enabled = plain and self._prefix_cache_arg \
            is not False
        if not plain and (self.tp > 1 or self.draft_spec is not None
                          or self._kv_hbm_bytes_arg is not None
                          or self._num_blocks_arg is not None):
            raise ValueError("tp > 1, a draft, num_blocks and kv_hbm_bytes "
                             "are for a spec with one tier that keeps "
                             "every block")

        def per_block(t):
            return len(t.layers) * BS * itemsize * sum(
                lf.width for lf in tier_leaves(t))

        self.bytes_per_block = sum(per_block(t) for t in tiers)
        self._tiers: List[_TierState] = []
        self.kv_slab_bytes = 0
        for t in tiers:
            if self._num_blocks_arg is not None:
                num_blocks = int(self._num_blocks_arg)
            elif self._kv_hbm_bytes_arg is not None:
                num_blocks = max(2, int(self._kv_hbm_bytes_arg)
                                 // self.bytes_per_block)
            else:
                # dense-equivalent floor: every slot at its worst fits
                num_blocks = 1 + self.max_slots * self._peak_blocks(
                    t, self.max_seq_len)
            self._tiers.append(_TierState(
                t, BS, t.table_blocks(BS, self._maxb), num_blocks,
                self.max_slots))
            self.kv_slab_bytes += num_blocks * per_block(t)
        by_name = {ts.tier.name: ts for ts in self._tiers}
        tier_of = {i: ts for ts in self._tiers for i in ts.tier.layers}
        self._window_tiers = [ts for ts in self._tiers
                              if ts.tier.window is not None]
        self._ladder_tiers = [ts for ts in self._tiers if ts.on_ladder]
        self._ladder_capacity = sum(ts.entries for ts in self._ladder_tiers)
        memstats.check_headroom(
            self.kv_slab_bytes,
            "paged KV pool (" + ", ".join(
                f"{ts.tier.name or 'all'}: {ts.pool.num_blocks} blocks"
                for ts in self._tiers) + f" x {BS} tokens)")
        shape = tuple(spec.kv_shape(self._tiers[0].pool.num_blocks, BS))
        mesh_key = None
        if self.tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS
            from deeplearning4j_tpu.parallel.sharding import ShardingSpec
            if spec.num_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide num_heads "
                    f"{spec.num_heads} (a KV leaf is split by heads)")
            devices = list(self._devices_arg
                           if self._devices_arg is not None
                           else jax.devices())
            sspec = ShardingSpec(axes={MODEL_AXIS: self.tp},
                                 preset="transformer", batch_axes=())
            sspec.validate(
                params={n: tuple(np.shape(a))
                        for n, a in self._params.items()},
                device_count=len(devices))
            self._strategy = strat = sspec.build(devices=devices)
            with COMPILE_STATS.span("serving.build.params", cat="serving"):
                self._params = {
                    n: jax.device_put(a,
                                      strat.param_sharding(n, np.ndim(a)))
                    for n, a in self._params.items()}
            # leaf layout contract: the last axis is heads x head_dim,
            # heads outermost, so an even split of it is a split by head
            self._kv_sharding = NamedSharding(
                strat.mesh.mesh, PartitionSpec(None, None, MODEL_AXIS))
            self._io_sharding = NamedSharding(strat.mesh.mesh,
                                              PartitionSpec())
            mesh_key = (self.tp,
                        tuple(str(d) for d in strat.mesh.mesh.devices.flat))
        # a leaf's arrays: one a layer, of the layer's tier; or one a
        # layer of the tier the leaf names
        self._kv_leaf_shapes = tuple(
            tuple((ts.pool.num_blocks, BS, lf.width)
                  for ts in ([tier_of[i] for i in range(layers)]
                             if lf.tier is None
                             else [by_name[lf.tier]]
                             * len(by_name[lf.tier].tier.layers)))
            for lf in leaves)
        self._kv_layers = layers
        self._kc, self._vc = self._fresh_leaves()
        AllocationsTracker.get_instance().allocate("kv_slab",
                                                   self.kv_slab_bytes)
        # host scheduler state (worker thread owns mutation). The first
        # tier's pool, tables and block counts under the names they had
        # when there was one tier (the prefix cache and speculation,
        # which need that one tier to keep every block, use them)
        self.pool = self._tiers[0].pool
        self._reserved = [0] * len(self._tiers)
        self.metrics.num_blocks = sum(ts.pool.capacity
                                      for ts in self._tiers)
        self.metrics.block_size = BS
        self.metrics.kv_bytes_per_token = self.kv_bytes_per_token
        self._slots = SlotAllocator(self.max_slots)
        self._slot_reqs: List[Optional[GenerationRequest]] = \
            [None] * self.max_slots
        self._tokens = np.zeros(self.max_slots, np.int32)
        self._positions = np.zeros(self.max_slots, np.int32)
        self._active = np.zeros(self.max_slots, bool)
        self._lane_ids = np.arange(self.max_slots)
        self._tables = self._tiers[0].tables
        self._nblocks = self._tiers[0].stop
        # chain hashes of the prefix being prefilled (worker thread)
        self._hashes: List[bytes] = []
        disp = _paged_dispatchers(
            spec, shape + tuple(ts.pool.num_blocks
                                for ts in self._tiers[1:]),
            BS, self._maxb, mesh_key)
        self._decode_disp = disp["decode"]
        self._prefill_disp = disp["prefill"]
        self._verify_disp = disp.get("verify")
        # a program that packs its counts behind its next tokens: the
        # tokens cut out on the device, for the step launched ahead
        # (warm-up compiles it; without warm-up it compiles like the rest)
        self._cut_tokens = self._cut_program() \
            if self._program_counters else None

    def _cut_program(self):
        import jax
        S = self.max_slots
        return jax.jit(lambda packed: packed[:S])

    def _next_tokens(self, nxt):
        return nxt if self._cut_tokens is None else self._cut_tokens(nxt)

    def _fresh_leaves(self) -> tuple:
        """The pool, zeroed, as the two arguments every program takes
        and gives back: a tuple of one array a layer for each leaf (K,
        then V), each array made where it will live; a spec of ONE leaf
        has nothing on the second side, ``()``."""
        import jax.numpy as jnp
        return self._two_sides(tuple(
            tuple(jnp.zeros(shape, self._kv_dtype, device=self._kv_sharding)
                  for shape in side) for side in self._kv_leaf_shapes))

    def _tier_leaves(self, tier: KVTier) -> List[KVLeaf]:
        """The leaves whose rows lie in ``tier``."""
        return [lf for lf in self._kv_leaves
                if lf.tier in (None, tier.name)]

    @staticmethod
    def _two_sides(sides: tuple) -> tuple:
        """One entry a leaf as the programs' two arguments: the leaf and
        ``()``, the two leaves, or of pairs of leaves the first ones and
        the second ones."""
        if len(sides) <= 2:
            return sides + ((),) * (2 - len(sides))
        return sides[0::2], sides[1::2]

    # -- block-commitment admission (submit thread) ---------------------
    def _peak_blocks(self, tier: KVTier, n_tokens: int) -> int:
        """The most blocks of ``tier`` a request of ``n_tokens`` holds at
        once; the most rows one run adds to it is a chunk."""
        return tier.peak_blocks(n_tokens, self.block_size,
                                self._buckets.max_rows)

    def _worst_case_blocks(self, prompt_len: int,
                           max_new_tokens: int) -> List[int]:
        """The most blocks the request can hold at once, tier by tier."""
        n = min(int(prompt_len) + int(max_new_tokens), self.max_seq_len)
        return [self._peak_blocks(ts.tier, n) for ts in self._tiers]

    @property
    def _committed(self) -> int:
        """Blocks reserved over all tiers."""
        return sum(self._reserved)

    def _uncommit(self, need: Sequence[int]) -> None:
        with self._commit_lock:
            for k, n in enumerate(need):
                self._reserved[k] -= int(n)

    def submit(self, prompt, max_new_tokens: int = 16,
               **kw) -> GenerationHandle:
        """:meth:`GenerativeServer.submit` plus block-pool admission:
        the request's WORST-CASE block footprint (prompt + full token
        budget) is reserved against pool capacity up front, in every
        tier, so a placed request can never fail a block allocation
        mid-decode. A request the pool cannot ever hold alongside the
        committed load sheds typed — :class:`PoolExhaustedError` with a
        ``retry_after_s`` backoff hint — instead of crashing a worker
        later. The reservation is released exactly once, whenever the
        request's future resolves (success, failure, timeout, shed,
        cancel, or a second-crash fail — every resolution path sets the
        future).

        Validation runs BEFORE the commitment: a request that could
        never run (empty/over-long/out-of-vocab prompt, zero token
        budget) raises its permanent ValueError even when the pool is
        fully committed, instead of masquerading as a retryable
        overload shed."""
        p = self._validate_submit(prompt, max_new_tokens)
        need = self._worst_case_blocks(p.size, max_new_tokens)
        with self._commit_lock:
            for ts, have, n in zip(self._tiers, self._reserved, need):
                if have + n <= ts.pool.capacity:
                    continue
                self.metrics.inc("requests_submitted")
                self.metrics.inc("requests_shed")
                hint = (self.admission.retry_hint_s(
                            self._queue.pending() + 1)
                        if self.admission is not None else 0.25)
                raise PoolExhaustedError(
                    f"KV block pool cannot hold the request: needs "
                    f"{n} blocks worst-case, {have} of "
                    f"{ts.pool.capacity} already committed — shed at "
                    f"admission", retry_after_s=hint)
            for k, n in enumerate(need):
                self._reserved[k] += n
        try:
            handle = super().submit(p, max_new_tokens, **kw)
        except BaseException:
            self._uncommit(need)
            raise
        handle._req.future.add_done_callback(
            lambda _f, n=need: self._uncommit(n))
        return handle

    def _can_place(self, req: GenerationRequest) -> bool:
        """Step-boundary gate: hold a queued request at the FRONT until
        its prefill's blocks are actually free in every tier (free list +
        evictable cached blocks). The submit-side commitment makes this
        eventually true without failing anything."""
        n = int(req.prefix().size)
        return all(ts.pool.usable_free_count()
                   >= self._peak_blocks(ts.tier, n) for ts in self._tiers)

    # -- worker: prefill / decode / retire ------------------------------
    def _consume_prefix_flush(self) -> None:
        """Hot-reload fence, worker side: update_model() swapped the
        weights, so every cached block addresses K/V the OLD model
        computed. Consumed on the worker thread (which owns the pool)
        at every step boundary AND immediately before each prefill's
        cache lookup — the lookup check matters because ``_admit``
        blocks on the queue *inside* a step, so a request submitted
        after the reload can reach prefill before the next boundary.
        In-flight holders keep their refcounts and finish (the same
        accepted in-flight staleness as the dense update_model)."""
        if self._prefix_flush_pending.is_set():
            self._prefix_flush_pending.clear()
            self.pool.flush_cache()
            self.metrics.inc("prefix_cache_flushes")

    def _step(self, slot) -> bool:
        self._consume_prefix_flush()
        return super()._step(slot)

    def _prefill_runs(self, s: int, prefix: np.ndarray, L: int):
        """Slot ``s``'s table starts with what the prefix cache holds of
        the prefix; the rest runs through the program in chunks of the
        largest bucket, cut besides where a window that tumbles ends (a
        run's rows then lie in one window)."""
        BS = self.block_size
        self._hashes = []
        hit: List[int] = []
        if self.prefix_cache_enabled:
            self._consume_prefix_flush()
            self._hashes = prefix_block_hashes(prefix, BS)
            # reuse is capped one block short of the full prefix: at
            # least one suffix token must run through prefill (the
            # logits at the LAST prompt position produce the first
            # generated token)
            hit = self.pool.lookup(self._hashes, max_blocks=(L - 1) // BS)
            self.metrics.observe_prefix(True, len(hit))
            self._tables[s, :len(hit)] = hit
            self._nblocks[s] = len(hit)
        runs, a = [], len(hit) * BS
        while a < L:
            b = min([a + self._buckets.max_rows, L]
                    + [w * (a // w + 1) for w in self._tumbling])
            runs.append((a, b))
            a = b
        return runs

    def _prefill_io(self, s: int, prefix: np.ndarray, L: int,
                    start: int, stop: int):
        """Fresh blocks for the rows ``[start, stop)`` of the prefix in
        every tier, and what the program is given to run them. A
        failed allocation leaves what it took on the slot's books, which
        the retirement that follows gives back."""
        BS = self.block_size
        fresh = sum(ts.grow(s, ts.tier.blocks(stop, BS))
                    for ts in self._tiers)
        self.metrics.observe_blocks(allocated=fresh)
        bucket, padded = self._pad_to_bucket(prefix[start:stop])
        io = {"tokens": padded, "length": np.int32(stop - start),
              "hist": np.int32(start)}
        for ts in self._tiers:
            t = ts.tier
            io[t.key("table")] = ts.tables[s].copy()
            if t.row_tokens > 1:
                # one block a row the run can complete, the null block
                # for the rows it does not (a run starts on a row)
                r0, r1 = t.rows(start), t.rows(stop)
                wb = np.full(blocks_for_tokens(bucket, t.row_tokens),
                             NULL_BLOCK, np.int32)
                wb[:r1 - r0] = [ts.block_at(s, r // BS)
                                for r in range(r0, r1)]
                io[t.key("write_block")] = wb
                self.metrics.inc("summary_rows_written", r1 - r0)
            elif t.name:
                # a window tier's ring still holds what the run reads,
                # so where the run's own rows go comes beside the table
                # (the unnamed tier's program finds it in the table)
                u0 = start // BS
                per_block = np.asarray(
                    [ts.block_at(s, u)
                     for u in range(u0, blocks_for_tokens(stop, BS))],
                    np.int32)
                wb = np.full(bucket, NULL_BLOCK, np.int32)
                wb[:stop - start] = per_block[
                    np.arange(start, stop) // BS - u0]
                io[t.key("write_block")] = wb

        def filled():
            # content-address the freshly FILLED full blocks (the
            # trailing partial block is still being appended to and
            # never registers); give back what the next run's window
            # no longer reaches
            for u in range(start // BS, min(len(self._hashes), stop // BS)):
                self.pool.register(self._hashes[u], int(self._tables[s, u]))
            self._advance(s, stop)

        return io, {"bucket": bucket, "hist": start}, filled

    def _advance(self, s: int, position: int) -> int:
        """Slot ``s``'s next query is at ``position``: its window tiers
        move on. Returns how many windows were given back whole."""
        gone = turns = 0
        for ts in self._window_tiers:
            n = ts.advance(s, position)
            gone += n
            turns += bool(n and ts.tier.tumbles)
        if gone:
            self.metrics.observe_blocks(released=gone, behind_window=gone,
                                        turns=turns)
        return turns

    def _decode_io(self, lead: int = 0) -> Optional[dict]:
        BS = self.block_size
        # at the step boundary a window tier takes the last step's block
        # into its table and gives back what the lane's next query no
        # longer reads, and a lane whose next write position crosses
        # into an unallocated block gets one in every tier (the tiers'
        # block counts run together). The submit-side commitment
        # guarantees this cannot fail for a placed request; the typed
        # retire is the defensive belt. With the step before still in
        # the air (``lead`` 1) the books move as they would after it: the
        # device runs programs in the order they were launched, so a
        # block given back here is read by that step before any later
        # program writes it
        self._turns = 0
        for s in np.flatnonzero(self._active):
            s = int(s)
            pos = int(self._positions[s]) + lead
            if self._window_tiers:
                self._turns += self._advance(s, pos)
            for ts in self._tiers:
                # the blocks of the rows written once this step's is (a
                # tier of one row a token: the block of ``pos``)
                need = ts.tier.blocks(pos + 1, BS)
                if need > ts.stop[s]:
                    try:
                        grown = ts.grow(s, need)
                    except PoolExhaustedError as e:   # pragma: no cover
                        self._retire(s, error=e)
                        break
                    self.metrics.observe_blocks(allocated=grown)
        if not self._active.any():
            return None
        act = self._active.copy()
        positions = self._positions.copy()
        positions[act] += lead
        wo = positions % BS
        wo[~act] = 0
        io = {"tokens": self._tokens.copy(), "positions": positions,
              "active": act, "write_off": wo}
        n_act = int(act.sum())
        written = positions + 1
        held = gathered = sent = 0
        for ts in self._tiers:
            t = ts.tier
            # the tier's own rung: the narrowest width that holds every
            # active lane's live blocks, this boundary's growth and turn
            # included (an active lane's position lies in a block it
            # holds, so the program's mask never reaches past the width)
            width = ts.decode_width(act)
            # the block of the row this step writes: the last of the rows
            # written once it has (an idle lane's table is all null
            # blocks, wherever it points)
            rows = t.rows(written)
            u = (rows - 1) // BS
            wb = ts.tables[self._lane_ids, u % ts.entries]
            if t.window is not None:
                for s in np.flatnonzero(act):
                    if ts.pending[s]:
                        wb[s] = ts.pending[s].get(int(u[s]), wb[s])
            if t.row_tokens > 1:
                # a row of several tokens is written by its last one
                done = act & (rows > t.rows(positions))
                wb[~done] = NULL_BLOCK
                self.metrics.inc("summary_rows_written", int(done.sum()))
            io[t.key("tables")] = ts.tables[:, :width].copy()
            io[t.key("write_block")] = wb
            held += len(t.layers) * int(
                (rows[act] - ts.first[act] * BS).sum())
            gathered += len(t.layers) * width
            if ts.on_ladder:
                sent += width
        self.metrics.observe_tables(sent, self._ladder_capacity)
        self.metrics.observe_rows(
            held, self._kv_layers * int(written[act].sum()),
            gathered * BS * n_act)
        return io

    def _decode_span_args(self, io: dict) -> dict:
        # the widths sent, tier by tier, and their sum (the one unnamed
        # tier's field IS the sum)
        sent = {ts.tier.key("table_entries"):
                io[ts.tier.key("tables")].shape[1]
                for ts in self._ladder_tiers}
        args = {"table_entries": sum(sent.values()), **sent}
        if self._turns:
            # lanes whose window tumbled at this step's boundary
            args["turns"] = self._turns
        return args

    def _sample_pool(self) -> None:
        windows = [ts.pool for ts in self._window_tiers]
        self.metrics.observe_pool(
            sum(ts.pool.held_count() for ts in self._tiers),
            stats=self.pool.stats(),
            window_held=sum(p.held_count() for p in windows),
            window_capacity=sum(p.capacity for p in windows))

    def _check_leaks(self) -> None:
        if self.debug_leaks:
            for ts in self._tiers:
                ts.pool.check_invariant(tables=[
                    ts.blocks(s) for s in range(self.max_slots)
                    if self._slot_reqs[s] is not None])

    # -- speculative decoding over the paged tier -----------------------
    def _spec_ready(self) -> bool:
        """Paged readiness additionally grows every active lane's block
        table UP FRONT to cover the verify window's live rows (those
        within the lane's remaining token budget — rows the submit-side
        worst-case commitment already reserved blocks for). If the pool
        defensively cannot (commitment math should make this
        impossible), the round falls back to plain single-step decode,
        whose one-block-at-a-time growth path handles it."""
        if not super()._spec_ready():
            return False
        BS = self.block_size
        W = self.speculate_k
        for s in np.flatnonzero(self._active):
            s = int(s)
            req = self._slot_reqs[s]
            rem = (req.max_new_tokens - len(req.generated)
                   if req is not None else 0)
            usable = min(W, max(rem, 0))
            if usable < 1:
                continue
            last = int(self._positions[s]) + usable - 1
            need = last // BS + 1
            while int(self._nblocks[s]) < need:
                try:
                    b = self.pool.alloc()
                except PoolExhaustedError:    # pragma: no cover
                    return False
                self._tables[s, int(self._nblocks[s])] = b
                self._nblocks[s] = int(self._nblocks[s]) + 1
                self.metrics.observe_blocks(allocated=1)
        return True

    def _verify_io(self, window: np.ndarray, positions: np.ndarray,
                   active: np.ndarray) -> dict:
        """Window write coordinates for the paged verify program:
        per-slot [S, W] (block, offset) pairs. Window rows beyond a
        lane's remaining token budget — writes no future step can ever
        read, because the lane retires exactly at its budget — are
        dumped to the null block, so speculation never writes a block
        the submit-side commitment didn't reserve. A rejected tail
        needs no rollback: the block-table cursor (``_nblocks``) only
        ever grew to committed rows, and positions simply do not
        advance over rejected columns."""
        BS = self.block_size
        S, W = window.shape
        wb = np.full((S, W), NULL_BLOCK, np.int32)
        wo = np.zeros((S, W), np.int32)
        for s in np.flatnonzero(active):
            s = int(s)
            req = self._slot_reqs[s]
            rem = (req.max_new_tokens - len(req.generated)
                   if req is not None else 0)
            usable = min(W, max(rem, 0))
            for j in range(usable):
                p = int(positions[s]) + j
                wb[s, j] = self._tables[s, p // BS]
                wo[s, j] = p % BS
        return {"tokens": window, "positions": positions.copy(),
                "active": active.copy(), "tables": self._tables.copy(),
                "write_block": wb, "write_off": wo}

    def _retire(self, s: int, error: Optional[BaseException] = None,
                timed_out: bool = False, cancelled: bool = False) -> None:
        """Release slot ``s``'s blocks (decrementing shared prefix
        refcounts) exactly once, then the base retirement. Exactness
        rides the same free-list discipline as slots: a second release
        of any block raises in the pool."""
        req = self._slot_reqs[s]
        if req is not None:
            if (error is None and not cancelled
                    and self.prefix_cache_enabled and req.generated):
                self._register_generated(s, req)
            n = sum(ts.clear(s) for ts in self._tiers)
            self.metrics.observe_blocks(released=n)
            self.metrics.observe_request_blocks(n)
        super()._retire(s, error=error, timed_out=timed_out,
                        cancelled=cancelled)

    def _register_generated(self, s: int, req) -> None:
        """Content-address the GENERATED span's full blocks at clean
        retirement, not just the prompt's (the prefill path already
        registered those): a resume-from-emitted-prefix continuation
        (fleet failover / journal replay) prefills ``prompt + emitted``
        and now hits cache over the whole already-decoded span. Must
        run BEFORE the release loop — registration requires the block
        held. Only blocks whose every position was written to KV
        qualify: the written region is ``[0, positions[s])`` (the final
        emitted token is never written back — the slot retires before
        its decode step), so exactly ``positions // block_size`` blocks
        are full. Blocks already registered (a prefill cache hit, or a
        concurrent fill of the same prefix) are left as-is."""
        BS = self.block_size
        n_full = min(int(self._positions[s]) // BS,
                     int(self._nblocks[s]))
        if n_full <= 0:
            return
        hashes = prefix_block_hashes(req.prefix(), BS, n_blocks=n_full)
        for u, h in enumerate(hashes):
            self.pool.register(h, int(self._tables[s, u]))

    def _reset_state(self) -> None:
        """Crash-recovery respawn: fresh leaves, a hard pool reset
        (every held block released ONCE, the prefix cache dropped — it
        content-addresses pool rows that are now garbage), clean
        tables. The requeued requests keep their submit-side block
        commitment (their futures are unresolved) and re-enter at
        prefill."""
        self._kc, self._vc = self._fresh_leaves()
        self._reset_draft_slabs()
        for ts in self._tiers:
            ts.reset()
        # the wholesale reset already dropped the prefix cache — a
        # pending hot-reload flush is thereby satisfied
        self._prefix_flush_pending.clear()
        self._reset_slots()

    # -- AOT warmup -----------------------------------------------------
    def _warmup(self, buckets: Optional[Sequence[int]]) -> dict:
        """Paged analogue of :meth:`GenerativeServer.warmup`'s body: one
        decode shape per combination of the tiers' table widths + one
        prefill shape per bucket, lowered with the
        mesh shardings when ``tp > 1`` so the AOT executables match the
        live sharded arguments (a mismatch would silently fall back to
        lazy jit — the AOTDispatch ValueError path)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.compilecache import install_compile_watcher
        from deeplearning4j_tpu.environment import environment
        from deeplearning4j_tpu.monitor import memstats
        environment().apply_compilation_cache()
        install_compile_watcher()
        bucket_list = sorted({int(b) for b in buckets}) \
            if buckets is not None else list(self._buckets.buckets)

        def _abs(shape, dtype, sharding=None):
            if sharding is not None:
                return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                            sharding=sharding)
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        io_sh = self._io_sharding if self.tp > 1 else None
        params_abs = {
            n: _abs(np.shape(a), a.dtype,
                    self._strategy.param_sharding(n, np.ndim(a))
                    if self._strategy is not None else None)
            for n, a in self._params.items()}
        kv_abs = self._two_sides(tuple(
            tuple(_abs(shape, self._kv_dtype, self._kv_sharding)
                  for shape in side) for side in self._kv_leaf_shapes))
        S, MAXB = self.max_slots, self._maxb

        def _tier_io(table_key, lead, rows, widths=None):
            """The tiers' part of a program's io: a table a request
            (``lead`` requests; whole, or for decode of the ``widths``
            given tier by tier) and, where the program is told (decode;
            a named tier's prefill), the block of each of its ``rows``
            fresh rows."""
            out = {}
            for ts, width in zip(self._tiers, widths or
                                 [ts.entries for ts in self._tiers]):
                t = ts.tier
                out[t.key(table_key)] = _abs(lead + (width,),
                                             jnp.int32, io_sh)
                if lead:
                    out[t.key("write_block")] = _abs((rows,), jnp.int32,
                                                     io_sh)
                elif t.name or t.row_tokens > 1:
                    out[t.key("write_block")] = _abs(
                        (blocks_for_tokens(rows, t.row_tokens),),
                        jnp.int32, io_sh)
            return out

        mark = COMPILE_STATS.mark()
        programs: List[dict] = []

        def _build(disp, io_abs, label, params_abs=params_abs,
                   kv_abs=kv_abs, role="target"):
            sig = ph_shape_sig(io_abs)
            with self._exec_lock:
                if sig not in disp.aot:
                    at = COMPILE_STATS.mark()
                    with COMPILE_STATS.precompile(label):
                        disp.aot[sig] = disp.lower(
                            params_abs, *kv_abs, io_abs).compile()
                    memstats.capture_plan(label, sig,
                                          compiled=disp.aot[sig])
                    programs.append(COMPILE_STATS.program_row(label, at))
                if (role, sig) not in self._shapes_seen:
                    self._shapes_seen.add((role, sig))
                    self.metrics.inc("warmup_compiles")

        # one decode program a combination of the tiers' widths: each
        # tier takes a rung of its own (_decode_io), so the product of
        # their distinct widths (a ring that slides has one)
        for k, widths in enumerate(product(
                *(sorted(set(ts.widths)) for ts in self._tiers))):
            _build(self._decode_disp,
                   {"tokens": _abs((S,), jnp.int32, io_sh),
                    "positions": _abs((S,), jnp.int32, io_sh),
                    "active": _abs((S,), jnp.bool_, io_sh),
                    "write_off": _abs((S,), jnp.int32, io_sh),
                    **_tier_io("tables", (S,), S, widths)},
                   f"paged_decode_s{S}r{k}")
        if self._cut_tokens is not None:
            at, label = COMPILE_STATS.mark(), f"paged_cut_tokens_s{S}"
            with COMPILE_STATS.precompile(label):
                self._cut_tokens = self._cut_program().lower(_abs(
                    (S + len(self._program_counters),),
                    jnp.int32)).compile()
            programs.append(COMPILE_STATS.program_row(label, at))
        if self.tp > 1:
            # the step launched ahead hands the program its own next
            # tokens back: only if they come out laid over the mesh as
            # the warmed program takes its io
            self._feed_on_device = all(
                c.output_shardings[2].is_equivalent_to(self._io_sharding, 1)
                for c in self._decode_disp.aot.values())
        for b in bucket_list:
            _build(self._prefill_disp,
                   {"tokens": _abs((int(b),), jnp.int32, io_sh),
                    "length": _abs((), jnp.int32, io_sh),
                    "hist": _abs((), jnp.int32, io_sh),
                    **_tier_io("table", (), int(b))},
                   f"paged_prefill_b{int(b)}")
        if self.draft_spec is not None:
            W = self.speculate_k
            _build(self._verify_disp,
                   {"tokens": _abs((S, W), jnp.int32, io_sh),
                    "positions": _abs((S,), jnp.int32, io_sh),
                    "active": _abs((S,), jnp.bool_, io_sh),
                    "tables": _abs((S, MAXB), jnp.int32, io_sh),
                    "write_block": _abs((S, W), jnp.int32, io_sh),
                    "write_off": _abs((S, W), jnp.int32, io_sh)},
                   f"paged_verify_s{S}w{W}")
            # the draft runs DENSE and unsharded, whatever the target's
            # layout — its abstract args carry no mesh shardings
            dparams_abs = {
                n: _abs(np.shape(a), np.asarray(a).dtype)
                for n, a in self._draft_params.items()}
            dkv_abs = (_abs(self._dkc.shape, self._dkc.dtype),) * 2
            _build(self._draft_decode_disp,
                   {"tokens": _abs((S,), jnp.int32),
                    "positions": _abs((S,), jnp.int32),
                    "active": _abs((S,), jnp.bool_)},
                   f"draft_decode_s{S}", params_abs=dparams_abs,
                   kv_abs=dkv_abs, role="draft")
            for b in bucket_list:
                _build(self._draft_prefill_disp,
                       {"tokens": _abs((int(b),), jnp.int32),
                        "length": _abs((), jnp.int32),
                        "slot": _abs((), jnp.int32)},
                       f"draft_prefill_b{int(b)}", params_abs=dparams_abs,
                       kv_abs=dkv_abs, role="draft")
        return {
            "decode_slots": S,
            "decode_table_widths": {
                ts.tier.name or "all": sorted(set(ts.widths))
                for ts in self._tiers},
            "prefill_buckets": bucket_list,
            "speculative": self.draft_spec is not None,
            "programs": programs,
            **{k: v for k, v in COMPILE_STATS.delta(mark).items()
               if k in ("backend_compiles", "cache_hits",
                        "cache_misses")}}

    def update_model(self) -> None:
        """Re-pull trained parameters; under ``tp > 1`` the fresh
        arrays are re-placed onto the mesh with the same shardings.

        Also fences the prefix cache: cached blocks are
        content-addressed by token ids alone, but their K/V were
        computed with the weights being replaced — reusing them would
        silently mix old-model keys/values with the new model for
        every repeated prefix. The pool is worker-thread-owned, so the
        flush is flagged here and consumed at the next step boundary
        (:meth:`_step`): evictable cached blocks return to the free
        list, held shared blocks just lose their registration so
        in-flight requests finish (dense's accepted staleness
        window)."""
        fresh = dict(self.spec.params())
        if self._strategy is not None:
            import jax
            fresh = {n: jax.device_put(
                         a, self._strategy.param_sharding(n, np.ndim(a)))
                     for n, a in fresh.items()}
        with self._exec_lock:
            self._params = fresh
        self._refresh_draft_params()
        self._prefix_flush_pending.set()

    def restore_params(self, params: dict) -> None:
        """Fleet-deploy rollback: install a ``params_snapshot()`` and
        fence the prefix cache exactly as :meth:`update_model` does —
        cached K/V were computed with the weights being replaced in
        EITHER direction of a swap."""
        super().restore_params(params)
        self._prefix_flush_pending.set()

    # -- observability --------------------------------------------------
    def _telemetry_load(self, depth: int, active: int) -> dict:
        load = super()._telemetry_load(depth, active)
        # capacity on the paged path is blocks held, not slots filled —
        # a router balancing on occupancy must see pool pressure
        load["pool_occupancy"] = round(safe_ratio(
            sum(ts.pool.held_count() for ts in self._tiers),
            sum(ts.pool.capacity for ts in self._tiers)), 4)
        load["blocks_committed"] = self._committed
        return load

    def memory_report(self) -> dict:
        """Pool accounting for /memory + capacity planning — block
        granularity instead of the dense per-slot rows."""
        st = self.pool.stats()
        for ts in self._tiers[1:]:
            for k, v in ts.pool.stats().items():
                st[k] += v
        return {"kv_slab_bytes": self.kv_slab_bytes,
                "kv_slab_shape": [len(self._kv_leaf_shapes[0]),
                                  *self._kv_leaf_shapes[0][0]],
                "kv_leaves": {lf.name: lf.width for lf in self._kv_leaves},
                "kv_bytes_per_token": self.kv_bytes_per_token,
                # what the model fills of that (a leaf may lay its row
                # out wider than it is: KVLeaf.filled)
                "kv_leaves_filled": {lf.name: lf.filled or lf.width
                                     for lf in self._kv_leaves},
                "kv_bytes_per_token_filled":
                    self._kv_bytes_per_token_filled,
                "kv_tiers": {ts.tier.name or "all": {
                    "layers": len(ts.tier.layers),
                    "leaves": [lf.name
                               for lf in self._tier_leaves(ts.tier)],
                    "window": ts.tier.window,
                    "tumbles": ts.tier.tumbles,
                    "row_tokens": ts.tier.row_tokens,
                    "table_entries": ts.entries,
                    "num_blocks": ts.pool.capacity,
                    "blocks_held": ts.pool.held_count()}
                    for ts in self._tiers},
                "kv_bytes_per_block": self.bytes_per_block,
                "block_size": self.block_size,
                "num_blocks": st["capacity"],
                "blocks_free": st["free"],
                "blocks_held": st["held"],
                "blocks_evictable": st["evictable"],
                "blocks_cached": st["cached"],
                "blocks_committed": self._committed,
                "pool_evictions": st["evictions"],
                "tensor_parallel": self.tp,
                "max_slots": self.max_slots,
                "max_seq_len": self.max_seq_len,
                "active_slots": self._n_active()}


__all__ = ["PagedGenerativeSpec", "PagedGenerativeServer", "PagedMetrics",
           "PrefixCacheUnsupportedError"]
