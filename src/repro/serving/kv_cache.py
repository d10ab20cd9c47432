"""Paged KV-cache: a shared pool of fixed-size blocks + per-request tables.

The dense engine allocated a ``(slots, max_seq)`` KV cache per layer, so
HBM scales with the *worst-case* sequence length times the slot count —
the paper's "batch mode" datacenter scenario (many users, short typical
prompts) wastes most of it.  Here KV lives in a pool of LANE-aligned
fixed-size blocks; each request owns only the blocks its tokens actually
fill, tracked by a block table (logical block -> physical block id).

Block id 0 is reserved as the **null block**: table entries past a
request's used length point at it, padded prefill tokens are written to
it, and inactive decode slots scatter into it — reads are masked by the
valid-length anyway, so it absorbs all don't-care traffic without
branching inside jit.

Device-side helpers (:func:`scatter_prefill_pages`,
:func:`scatter_prefill_dense`) copy a freshly prefiled batch=1 cache into
the shared pool / the dense slot cache; the engine jits them per bucket.
"""
from __future__ import annotations

import math
import zlib
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

LANE = 128

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# host-side accounting
# ---------------------------------------------------------------------------

def bucket_for(n: int, max_seq: int, min_bucket: int = 16) -> int:
    """Pad a prompt length to its power-of-two prefill bucket.

    The prefill jit re-traces per *shape*, so padding to pow2 buckets
    bounds the trace count by O(log2 max_seq) instead of one per
    distinct prompt length.
    """
    if n > max_seq:
        raise ValueError(f"prompt length {n} exceeds max_seq {max_seq}")
    b = max(min_bucket, 1)
    while b < n:
        b *= 2
    return min(b, max_seq)


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Number of KV blocks needed to hold ``n_tokens``."""
    return max(1, math.ceil(n_tokens / block_size))


def per_rank_block_bytes(n_layers: int, kv_heads_per_rank: int,
                         d_head: int, block_size: int,
                         dtype_bytes: int = 2,
                         scale_bytes: int = 0) -> int:
    """Bytes ONE pool block occupies on ONE ring rank (K and V).

    Under tensor parallelism the pool's stored-head dim is sharded over
    the model ring, so each rank holds ``kv_heads_per_rank`` of every
    block — pool HBM divides by tp, which is what lets a tp-wide ring
    serve proportionally longer contexts at a fixed per-chip budget.

    ``scale_bytes`` is the per-(row, head) side-array cost of a
    quantized pool (``KVPrecision.scale_itemsize``), so budget sizing
    (``--kv-budget-mb``) stays honest about the scales it must co-locate
    — an int8 pool admits ~2x the fp16 blocks, not exactly 2x.
    """
    return 2 * n_layers * block_size * kv_heads_per_rank \
        * (d_head * dtype_bytes + scale_bytes)


def pool_blocks_for_budget(budget_bytes: int, block_bytes: int) -> int:
    """Largest pool (incl. the null block) fitting a per-rank HBM budget.

    ``block_bytes`` is the per-rank footprint from
    :func:`per_rank_block_bytes`.  Raises when the budget cannot hold the
    null block plus one allocatable block — a pool that small can never
    admit a request.
    """
    n = int(budget_bytes // max(block_bytes, 1))
    if n < 2:
        raise ValueError(
            f"KV budget {budget_bytes}B holds {n} blocks of "
            f"{block_bytes}B/rank; need >= 2 (null block + 1)")
    return n


class BlockPool:
    """Refcounting allocator over the shared block pool.

    Block 0 is reserved (null block) and never handed out.  ``alloc``
    returns None when the request cannot be satisfied — the scheduler
    turns that into queueing or preemption, never a partial grant.

    Blocks carry a **refcount** so a prefix-cache hit can map the same
    physical block into several block tables (:meth:`share`); ``free``
    decrements and only returns the block to circulation at zero.  A
    block *registered* in the prefix index (:meth:`mark_cached`) is not
    recycled eagerly at refcount zero — it parks in an LRU and its KV
    stays valid for future hits; ``alloc`` drains the plain free list
    first and only then evicts LRU-oldest cached blocks, firing
    ``on_evict`` so the index drops its entries (counted in
    ``evicted_blocks``).
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (one is the null block)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.ref: List[int] = [0] * num_blocks
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._cached: set = set()
        self.on_evict: Optional[Callable[[int], None]] = None
        self.evicted_blocks = 0

    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._lru)

    @property
    def num_used(self) -> int:
        return (self.num_blocks - 1) - self.num_free

    def can_alloc(self, n: int) -> bool:
        return n <= self.num_free

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > self.num_free:
            return None
        out: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # evict the least-recently-used cached block; its KV is
                # reusable only via the index, which on_evict invalidates
                b, _ = self._lru.popitem(last=False)
                self._cached.discard(b)
                self.evicted_blocks += 1
                if self.on_evict is not None:
                    self.on_evict(b)
            self.ref[b] = 1
            out.append(b)
        return out

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per listed block.

        Raises on block ids outside the pool, on the null block, and on
        blocks whose refcount is already zero (double free, or freeing a
        never-allocated id) — once blocks are shared between tables a
        silent bad free corrupts another request's KV.
        """
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
            if self.ref[b] <= 0:
                raise ValueError(
                    f"double free (or free of never-allocated) block {b}")
            self.ref[b] -= 1
            if self.ref[b] == 0:
                if b in self._cached:
                    self._lru[b] = None       # most-recently-used end
                else:
                    self._free.append(b)

    def share(self, blocks: Sequence[int]) -> None:
        """Take an extra reference on each block (a prefix-cache hit
        mapping cached blocks into a new table).  Blocks parked in the
        LRU (refcount 0, index-reachable) are revived; live blocks just
        gain a reference."""
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
            if self.ref[b] == 0:
                if b not in self._lru:
                    raise ValueError(
                        f"share of free, un-cached block {b}")
                del self._lru[b]
            self.ref[b] += 1

    def mark_cached(self, block: int) -> None:
        """Flag a live block as registered in the prefix index: at
        refcount zero it parks in the LRU instead of the free list."""
        if self.ref[block] <= 0:
            raise ValueError(f"mark_cached of free block {block}")
        self._cached.add(block)

    def touch(self, blocks: Sequence[int]) -> None:
        """Refresh LRU recency for cached blocks hit while parked."""
        for b in blocks:
            if b in self._lru:
                self._lru.move_to_end(b)

    def used_bytes(self, bytes_per_block: int) -> int:
        return self.num_used * bytes_per_block


def assert_pool_balanced(pool: BlockPool,
                         prefix: Optional["PrefixCache"] = None) -> None:
    """Refcount-balance invariant after a full drain (zero live requests).

    Every block's refcount must be zero (cached blocks park in the LRU
    at refcount zero, so this holds for them too) and the free + LRU
    lists must account for every non-null block.  With a prefix cache,
    every index entry must point at an LRU-parked block.  Raises
    ``AssertionError`` naming the leaked block ids — the gate the
    fault-tolerance tests and serving_bench's chaos row hold after a
    ring drain/rebuild cycle (a rebuild that leaked references would
    silently shrink the pool every failure).
    """
    leaked = [b for b in range(1, pool.num_blocks) if pool.ref[b] != 0]
    if leaked:
        raise AssertionError(
            f"leaked blocks (nonzero refcount after drain): {leaked}")
    if pool.num_used != 0:
        raise AssertionError(
            f"pool accounting imbalance: {pool.num_used} blocks used "
            "after drain (free + LRU lists lost track of them)")
    if prefix is not None:
        stray = [b for b in prefix._by_block if b not in pool._lru]
        if stray:
            raise AssertionError(
                f"prefix index entries for non-parked blocks: {stray}")


# ---------------------------------------------------------------------------
# prefix cache: block-aligned hash index over token prefixes
# ---------------------------------------------------------------------------

def _chain_hash(prev: int, tokens: Sequence[int]) -> int:
    """crc32-chained hash of one block's tokens, keyed by the hash of
    everything before it.  crc32 (not ``hash``) so the index is
    deterministic across processes — the tp=2 parity tests replay the
    same trace in subprocesses."""
    data = prev.to_bytes(4, "little") + \
        b"".join(int(t).to_bytes(4, "little", signed=True) for t in tokens)
    return zlib.crc32(data)


class PrefixCache:
    """Block-aligned prefix index over the pool.

    Maps the chained hash of each *full* block of prompt tokens to the
    physical block holding its KV.  Consulted at admission: the longest
    chain of consecutive full-block hits is mapped (refcounted) into
    the new request's table and only the tail is prefilled.  One index
    entry per physical block; eviction from the pool's LRU invalidates
    the entry via ``pool.on_evict``.

    The index never has to invalidate on writes: a registered block's
    contents are immutable — any KV write into a block with refcount > 1
    goes through copy-on-write, and a sole owner appending into its
    registered tail block would first diverge from the hashed token
    string only at positions past the hashed span (full blocks hash all
    ``block_size`` tokens, so appends always land in later blocks).
    """

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.block_size = pool.block_size
        self._by_hash: Dict[int, int] = {}
        self._by_block: Dict[int, int] = {}
        self.lookups = 0
        self.hits = 0
        self.hit_blocks = 0
        self.tokens_saved = 0
        pool.on_evict = self._evict

    def _evict(self, block: int) -> None:
        h = self._by_block.pop(block, None)
        if h is not None and self._by_hash.get(h) == block:
            del self._by_hash[h]

    def match(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens``.

        Returns ``(shared_blocks, cached_tokens)``.  ``cached_tokens``
        is capped at ``len(tokens) - 1`` so at least one tail token is
        always prefilled — prefill produces the logits row the first
        sampled token comes from, and a fully resident prompt would
        leave nothing to run.  When that cap lands mid-block, the final
        shared block is the one a later divergent append copy-on-writes.
        """
        self.lookups += 1
        bs = self.block_size
        n = len(tokens)
        hit: List[int] = []
        h = 0
        for i in range(n // bs):
            h = _chain_hash(h, tokens[i * bs:(i + 1) * bs])
            b = self._by_hash.get(h)
            if b is None:
                break
            hit.append(b)
        cached = min(len(hit) * bs, n - 1)
        if cached <= 0:
            return [], 0
        shared = hit[:blocks_for(cached, bs)]
        self.pool.touch(shared)
        return shared, cached

    def peek(self, tokens: Sequence[int]) -> int:
        """Stats-neutral probe: prefix tokens of ``tokens`` this index
        could map right now, with :meth:`match`'s same ``n - 1`` cap.

        Used by the fleet's prefix-affinity router to ask every ring
        "how much of this prompt do you already own?" BEFORE choosing
        one — so it must not count as a lookup (hit-rate telemetry
        stays an admission-path property) and must not ``touch`` the
        LRU (probing all rings would otherwise rejuvenate blocks on
        rings the request never lands on).
        """
        bs = self.block_size
        n = len(tokens)
        hit = 0
        h = 0
        for i in range(n // bs):
            h = _chain_hash(h, tokens[i * bs:(i + 1) * bs])
            if self._by_hash.get(h) is None:
                break
            hit += 1
        return max(min(hit * bs, n - 1), 0)

    def note_hit(self, shared: Sequence[int], cached: int) -> None:
        """Count a hit that actually admitted (the scheduler calls this
        after the tail allocation succeeds, so a request that waits and
        retries is not double-counted)."""
        self.hits += 1
        self.hit_blocks += len(shared)
        self.tokens_saved += cached

    def register(self, tokens: Sequence[int], blocks: Sequence[int]) -> None:
        """Index every full block of a freshly prefilled prompt.

        First-wins on hash collision at the index level; the physical
        block keeps exactly one index entry (re-registering a shared
        block that already carries its own hash is a no-op)."""
        bs = self.block_size
        h = 0
        for i in range(len(tokens) // bs):
            h = _chain_hash(h, tokens[i * bs:(i + 1) * bs])
            if i >= len(blocks):
                break
            b = blocks[i]
            if self._by_hash.get(h) == b:
                continue                       # already indexed (shared hit)
            if h in self._by_hash or b in self._by_block:
                continue                       # first-wins; keep 1:1 mapping
            self._by_hash[h] = b
            self._by_block[b] = h
            self.pool.mark_cached(b)


def copy_pool_block(cache: Params, src: jax.Array, dst: jax.Array) -> Params:
    """Copy one physical block's KV across the whole pool pytree
    (copy-on-write: a shared block is duplicated before the writer's
    next scatter).  ``src``/``dst`` are int32 scalars so one jitted
    trace serves every copy."""
    return jax.tree.map(lambda pg: pg.at[:, dst].set(pg[:, src]), cache)


# ---------------------------------------------------------------------------
# quantized storage: absmax row quantization + the pool's scale side-arrays
# ---------------------------------------------------------------------------

def qmax_for_dtype(dtype) -> float:
    """Symmetric clip bound of a quantized pool leaf dtype."""
    d = jnp.dtype(dtype)
    if d == jnp.int8:
        return 127.0
    if d.name == "float8_e4m3fn":
        return 448.0
    raise ValueError(f"not a quantized KV storage dtype: {d.name}")


def quantize_kv_rows(rows: jax.Array, store_dtype,
                     scale_dtype) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax quantization of KV rows along the head dim.

    rows: (..., dh) float K (or V) rows.  Returns ``(q, scales)`` with
    ``q`` shaped like ``rows`` in ``store_dtype`` and ``scales`` shaped
    ``rows.shape[:-1]`` in ``scale_dtype`` — one scale per stored token
    row per kv head, the side array the pool carries next to the values.
    All-zero rows get scale 0 (dequantizes to exact zeros, the null
    block's contract); the divisor is made safe so they never NaN.  The
    values are divided by the scale as stored (rounded to
    ``scale_dtype``), so dequantization inverts exactly what was stored.
    """
    qmax = qmax_for_dtype(store_dtype)
    x = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = (amax / qmax).astype(scale_dtype).astype(jnp.float32)
    y = x / jnp.where(scale > 0, scale, 1.0)[..., None]
    if jnp.dtype(store_dtype) == jnp.int8:
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(store_dtype)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(store_dtype)
    return q, scale.astype(scale_dtype)


def dequantize_kv(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv_rows` (fp32 out)."""
    return q.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# device-side pool plumbing (pure functions; the engine jits them)
# ---------------------------------------------------------------------------

def cache_bytes(cache: Params) -> int:
    """Total bytes of a KV cache pytree (dense slot cache or block pool).

    Scale side-arrays of a quantized pool are ordinary pytree leaves, so
    the reported bytes include them — pool accounting stays honest."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def scatter_prefill_pages(cache: Params, prefill_cache: Params,
                          table: jax.Array) -> Params:
    """Copy a batch=1 prefill cache into the shared block pool.

    cache:         {lj: {"k": (n_sb, N, gp, bs, dh), "v": ...}}
    prefill_cache: {lj: {"k": (n_sb, 1, S, gp, dh), "v": ...}} with S a
                   multiple of bs
    table:         (S // bs,) physical block ids; pad entries point at the
                   null block 0, which absorbs the padded tokens' KV.

    A quantized pool carries ``k_scale``/``v_scale`` side-array leaves
    ((n_sb, N, gp, bs)); prefill stays full-precision in its bucket
    cache and quantization happens HERE, at pool-write time, so the
    quantized path shares one numerical contract with the chunked /
    speculative row scatters.
    """
    out: Params = {}
    for lj, c in cache.items():
        pc = prefill_cache[lj]
        layer: Params = {}
        for key in ("k", "v"):
            pg, dn = c[key], pc[key]
            n_sb, bs = pg.shape[0], pg.shape[3]
            S = dn.shape[2]
            nb = S // bs
            # seq-major (S, gp, dh) rows -> head-major (nb, gp, bs, dh)
            chunks = jnp.swapaxes(
                dn[:, 0].reshape((n_sb, nb, bs) + dn.shape[3:]), 2, 3)
            skey = key + "_scale"
            if skey in c:
                spg = c[skey]
                q, s = quantize_kv_rows(chunks, pg.dtype, spg.dtype)
                layer[key] = pg.at[:, table].set(q)
                layer[skey] = spg.at[:, table].set(s)
            else:
                layer[key] = pg.at[:, table].set(chunks.astype(pg.dtype))
        out[lj] = layer
    return out


def scatter_chunk_rows(pages: jax.Array, rows: jax.Array,
                       block_table: jax.Array, positions: jax.Array,
                       valid: jax.Array) -> jax.Array:
    """Positionwise scatter of ONE prefill chunk into the block pool.

    The monolithic prefill path materializes a whole batch=1 cache and
    copies it block-aligned (:func:`scatter_prefill_pages`); a *chunk*
    of a partially-prefilled prompt instead lands token by token — its
    start offset is arbitrary (chunks need not align to block
    boundaries), so each row resolves its own (physical block, offset)
    through the request's table, exactly like the decode path's
    one-token scatter.

    pages:       (N, G, bs, dh) one layer of the head-major pool
    rows:        (C, G, dh) the chunk's freshly computed K (or V)
    block_table: (T,) the request's physical block ids
    positions:   (C,) absolute token positions of the chunk rows
    valid:       (C,) bool; padded rows are routed to the null block 0
                 (absorbed don't-care traffic, masked on read).

    Shapes generalize over the trailing dims: a quantized pool's scale
    side-array ((N, G, bs) pages, (C, G) rows) scatters through the
    SAME function, so values and scales stay row-consistent by
    construction.
    """
    bs = pages.shape[2]
    T = block_table.shape[0]
    idx = jnp.clip(positions // bs, 0, T - 1)
    blk = jnp.where(valid, block_table[idx], 0)
    off = positions % bs
    return pages.at[blk, :, off].set(rows.astype(pages.dtype))


def scatter_spec_rows(pages: jax.Array, rows: jax.Array,
                      block_tables: jax.Array, positions: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Per-query scatter for a speculative verify window.

    The verify pass flattens every slot's (k drafts + 1 last token) into
    a batch of single-token queries, each with its OWN block table — the
    per-query generalization of :func:`scatter_chunk_rows` (whose rows
    all share one request's table).  Rejected drafts are never
    un-written: their rows sit past the slot's resident length, so every
    later read masks them out and the next window overwrites them
    idempotently (logical rollback, zero device work).

    pages:        (N, G, bs, dh) one layer of the head-major pool
                  (or its (N, G, bs) scale side-array)
    rows:         (Q, G, dh) the verify queries' freshly computed K (V)
    block_tables: (Q, T) each query's physical block ids
    positions:    (Q,) absolute token positions
    valid:        (Q,) bool; idle-slot rows route to the null block 0.
    """
    bs = pages.shape[2]
    T = block_tables.shape[1]
    idx = jnp.clip(positions // bs, 0, T - 1)
    blk = jnp.where(valid,
                    jnp.take_along_axis(block_tables, idx[:, None],
                                        1)[:, 0], 0)
    off = positions % bs
    return pages.at[blk, :, off].set(rows.astype(pages.dtype))


def scatter_prefill_dense(cache: Params, prefill_cache: Params,
                          slot: jax.Array) -> Params:
    """Copy a batch=1 prefill cache into one slot of the dense cache.

    KV leaves ("k"/"v") scatter along the sequence prefix of the slot;
    recurrent-state leaves (mamba conv/ssm, rwkv shift/wkv) replace the
    slot's state wholesale.
    """
    out: Params = {}
    for lj, c in cache.items():
        pc = prefill_cache[lj]
        layer: Params = {}
        for key, tgt in c.items():
            dn = pc[key]
            if key in ("k", "v"):
                layer[key] = jax.lax.dynamic_update_slice(
                    tgt, dn.astype(tgt.dtype)[:, 0:1],
                    (0, slot, 0) + (0,) * (tgt.ndim - 3))
            else:
                layer[key] = jax.lax.dynamic_update_slice(
                    tgt, dn.astype(tgt.dtype)[:, 0:1],
                    (0, slot) + (0,) * (tgt.ndim - 2))
        out[lj] = layer
    return out
