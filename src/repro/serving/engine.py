"""HyperDex-style runtime layer: continuous-batching serving engine.

``LPUEngine`` mirrors the paper's runtime API surface on top and a paged
KV-cache serving stack below; with a mesh it becomes the paper's
*multi-LPU* configuration — the whole prefill/decode path runs inside
one ``shard_map`` over the ``model`` ring so the ESL collectives (C2)
and the paged pool compose:

* **API** — the HF-like blocking ``generate(prompts, ...)`` plus a
  non-blocking ``submit(request) / step() / drain()`` interface for
  continuous serving (the paper's "batch mode" datacenter direction).
* **Scheduler** — a fixed decode batch of B slots; queued requests are
  admitted at step boundaries by :class:`repro.serving.scheduler.
  Scheduler`, finished sequences release their slot (and blocks)
  mid-flight.
* **KV cache** — paged by default for attention-only stacks: a shared
  pool of fixed-size blocks with per-request block tables
  (:mod:`repro.serving.kv_cache`), so the *persistent* cache scales
  with resident tokens instead of slots x max_seq.  Decode **streams**
  KV tiles straight from the pool through the scalar-prefetched paged
  Pallas kernel (``paged_kernel="stream"``, the default wherever the
  stored GQA layout allows): no contiguous per-request view is ever
  materialized.  ``paged_kernel="gather"`` keeps the old
  copy-then-attend path as the reference oracle (bit-trustworthy, 3x
  the KV bytes moved — see :meth:`LPUEngine.kv_bytes_moved_per_step`).
  The dense per-slot cache remains the contiguous fast path
  (``paged=False``, and the automatic fallback for recurrent-state
  families).
* **Prefill** — per-request at batch 1, padded to power-of-two length
  buckets so the prefill jit traces O(log2 max_seq) times instead of
  once per distinct prompt length; the resulting KV is scattered into
  the pool (or the slot's dense region).  With ``prefill_chunk=C`` the
  monolith is replaced by CHUNKED prefill: the prompt becomes resident
  C tokens per ``step()``, each chunk's KV written incrementally into
  the pool while the same step still dispatches a decode window — so a
  long prompt no longer freezes every in-flight stream for its whole
  bucketed prefill (``EngineStats.decode_stalls`` measures exactly
  that, and is structurally zero in chunked mode).  Token streams are
  bit-identical to monolithic prefill for greedy decoding.
* **Preemption** — when the pool is exhausted, the newest sequence is
  evicted and re-prefiled later (recompute), protecting old requests.
* **Fused sampling (C1)** — by default the sampler runs INSIDE the
  jitted decode program (the paper's VXE "sampling with sort"):
  per-slot SamplingParams ride as device arrays, the rng chain is
  device state, and only int32 token ids cross to the host — O(slots)
  bytes per token instead of the O(slots x vocab) logits row
  (``EngineStats.host_syncs`` / ``bytes_to_host`` measure it).
  ``steps_per_sync=S`` further runs S decode steps as one ``lax.scan``
  window with on-device stop masking (host reconciles overrun tokens
  after readback), and ``pipeline=True`` double-buffers window k+1's
  dispatch before blocking on window k.  ``sampling="host"`` keeps the
  pre-fusion loop as the parity oracle; token streams are identical in
  both modes (greedy bit-for-bit; stochastic for a fixed rng).

**Ring parallelism (C2)** — ``LPUEngine(model, params, mesh=...)`` with
a plan built for the mesh shards weights AND the KV pool over the
``model`` axis (stored kv heads split 1/tp per rank: same block ids on
every rank, 1/tp of the bytes).  Decode and prefill are jitted
``shard_map`` programs whose matmuls stream partial products around the
ICI ring (:mod:`repro.core.esl` ``ag_matmul``/``rs_matmul``); the
engine's host loop — admission, block tables, sampling — is unchanged,
because tables and sampled tokens are replicated ring-wide.  The token
stream matches the single-device engine (tests/test_serving.py).

**Sub-rings (C3)** — :class:`MultiRingEngine` carves the model axis
into ``RingConfig`` sub-rings (:mod:`repro.core.rings`) and runs one
independent ``LPUEngine`` per sub-mesh: disjoint device groups, so no
collective of one tenant can touch another's ring.  Requests are
admitted per-ring by :class:`repro.serving.scheduler.RingRouter`
(least outstanding tokens).

Monitoring hooks expose tokens/s, slot occupancy, prefill trace count,
preemptions and KV bytes (total and per rank) — the datacenter-level
statistics HyperDex exposes from its driver.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import (Callable, Dict, List, Optional, Sequence, Set, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compiler.plan import resolve_kv_precision
from repro.core.compat import shard_map
from repro.core.dist import make_axis_env
from repro.core.rings import reconfigure, submeshes
from repro.kernels import interpret_mode
from repro.kernels.decode_attention.ops import (plan_block_s,
                                                resolve_paged_kernel)
from repro.serving.kv_cache import (LANE, BlockPool, PrefixCache,
                                    assert_pool_balanced,
                                    cache_bytes, copy_pool_block,
                                    per_rank_block_bytes,
                                    pool_blocks_for_budget,
                                    scatter_prefill_dense,
                                    scatter_prefill_pages)
from repro.serving.config import EngineConfig, resolve_engine_config
from repro.serving.drafter import make_drafter
from repro.serving.ft import (Event, FailureInjector, HeartbeatTracker,
                              ManualClock, RingFailure, StragglerMonitor,
                              parse_chaos)
from repro.serving.sampler import (SamplingParams, sample_batched,
                                   sample_local, sample_sharded_batched,
                                   spec_verify_rows,
                                   speculative_verify_sharded,
                                   split_spec_rng_chain)
from repro.serving.scheduler import RingRouter, Scheduler, SeqSlot
from repro.serving.tracker import NO_SPANS

StreamCB = Callable[[int, int], None]   # (request_id, token)


def _program(name: str, fn: Callable, **jit_kw) -> Callable:
    """``jax.jit`` of ``fn`` under ``name``: the program lowers as
    ``module @jit_<name>``, which is how the profiler's trace names it
    (a bound method or a ``partial`` would come out ``jit__unknown``)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kw)


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    params: SamplingParams = SamplingParams()
    out: List[int] = field(default_factory=list)
    done: bool = False
    stream_cb: Optional[StreamCB] = None
    failed: bool = False          # structured failure (rejection, or
                                  # migration retries exhausted) — the
                                  # request is done but its stream is
                                  # incomplete; ``error`` says why
    error: Optional[str] = None
    cancelled: bool = False       # caller-initiated abort (frontend
                                  # cancellation): done, not failed —
                                  # the partial stream is intentional

    def resume_tokens(self) -> List[int]:
        """Tokens whose KV must be resident before decoding continues.

        Fresh request: the prompt.  After preemption the generated tokens
        ride along — all but the last (which has been sampled, not yet
        fed through the model) are re-prefiled.
        """
        if not self.out:
            return list(self.prompt)
        return list(self.prompt) + list(self.out[:-1])


@dataclass
class EngineStats:
    steps: int = 0
    tokens: int = 0
    busy_slot_steps: int = 0
    slot_steps: int = 0
    wall: float = 0.0
    preemptions: int = 0
    prefill_traces: int = 0       # distinct prefill buckets traced
    prefills: int = 0             # total prefill launches (incl. resume)
    peak_pool_blocks: int = 0     # high-water block-pool occupancy
    host_syncs: int = 0           # blocking device->host readbacks
    prefill_syncs: int = 0        # ...of which sample a prefill row
    bytes_to_host: int = 0        # payload bytes of those readbacks
    overrun_tokens: int = 0       # sampled in a window, discarded by host
    prefill_chunks: int = 0       # chunk launches (chunked prefill mode)
    decode_stalls: int = 0        # monolithic prefills run while decode
                                  # streams were in flight: each one
                                  # froze every stream for a full
                                  # bucketed prefill (chunked mode: 0 —
                                  # a decode window dispatches in the
                                  # same step as each chunk)
    prefix_lookups: int = 0       # admissions that consulted the prefix
                                  # index (prefix_cache=True only)
    prefix_hits: int = 0          # ...that admitted with shared blocks
    prefix_hit_blocks: int = 0    # pool blocks mapped from the index
                                  # instead of freshly prefilled
    prefill_tokens_saved: int = 0 # prompt tokens NOT re-prefilled thanks
                                  # to prefix hits (the TTFT win)
    evicted_blocks: int = 0       # cached refcount-0 blocks recycled by
                                  # the pool's LRU under pressure
    cow_blocks: int = 0           # copy-on-write splits: a shared block
                                  # copied before a divergent KV write
    spec_rounds: int = 0          # speculative verify rounds dispatched
    draft_tokens: int = 0         # drafter-proposed tokens verified
    accepted_tokens: int = 0      # ...accepted by rejection sampling
    ring_failures: int = 0        # drain/rebuild cycles this engine went
                                  # through (detected or injected faults)
    migrated_requests: int = 0    # in-flight requests this engine took
                                  # over from a failed ring (recompute
                                  # resume via Request.resume_tokens)
    retries: int = 0              # recovery resubmissions admitted here
                                  # (every migration, incl. back onto
                                  # the rebuilt ring when it is alone)
    rejected_requests: int = 0    # admissions rejected with a structured
                                  # per-request failure instead of a
                                  # scheduler RuntimeError (livelock fix)
    cancelled_requests: int = 0   # caller-aborted via cancel(): slot and
                                  # blocks released mid-stream

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.wall if self.wall else 0.0

    @property
    def occupancy(self) -> float:
        return self.busy_slot_steps / max(self.slot_steps, 1)

    @property
    def bytes_to_host_per_token(self) -> float:
        """Device->host payload per decode token: O(slots * vocab) for
        the host-sampled path, O(slots) once sampling is fused in-jit."""
        return self.bytes_to_host / max(self.tokens, 1)

    @property
    def syncs_per_token(self) -> float:
        return self.host_syncs / max(self.tokens, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefix-index consultations that mapped at least
        one shared block into the admitted table."""
        return self.prefix_hits / max(self.prefix_lookups, 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafter-proposed tokens the verify pass accepted
        (counts only REAL proposals, never the padding of slots the
        drafter had nothing for)."""
        return self.accepted_tokens / max(self.draft_tokens, 1)

    @property
    def accepted_per_window(self) -> float:
        """Mean accepted draft tokens per speculative round — the
        latency win: each accepted token is one decode step the engine
        did not have to run."""
        return self.accepted_tokens / max(self.spec_rounds, 1)


class LPUEngine:
    """Slot-based continuous-batching decode engine (single host).

    ``mesh=None`` is the single-device smoke configuration.  With a
    1-axis ``model`` mesh (and a plan built for it) the engine runs its
    jitted steps inside ``shard_map`` over the ring — weights, the KV
    pool and the prefill caches are placed with the mapper's
    PartitionSpecs; block tables, positions and sampled tokens stay
    replicated host state, identical to the single-device loop.

    Construction: ``LPUEngine(model, params, config=EngineConfig(...))``
    with runtime objects (``mesh``, ``rng``, ``drafter``,
    ``draft_model``/``draft_params``) as direct keyword arguments.
    Loose scalar kwargs (``slots=8, paged=True, ...``) still work via
    the deprecation shim in :mod:`repro.serving.config` — they fold
    into an identical ``EngineConfig`` and warn once per process.
    """

    def __init__(self, model, params,
                 config: Optional[EngineConfig] = None, *,
                 mesh=None, rng: Optional[jax.Array] = None,
                 drafter=None, draft_model=None, draft_params=None,
                 **legacy_kwargs):
        c = resolve_engine_config(config, legacy_kwargs)
        self.config = c
        slots, max_seq, eos_id = c.slots, c.max_seq, c.eos_id
        paged, block_size, num_blocks = c.paged, c.block_size, c.num_blocks
        min_bucket, kv_budget_bytes = c.min_bucket, c.kv_budget_bytes
        paged_kernel, sampling = c.paged_kernel, c.sampling
        steps_per_sync, pipeline = c.steps_per_sync, c.pipeline
        block_s, prefill_chunk = c.block_s, c.prefill_chunk
        prefix_cache, speculate = c.prefix_cache, c.speculate
        draft_k = c.draft_k
        self.model = model
        self.cfg = model.cfg
        self.plan = model.plan
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.mesh = mesh
        if mesh is not None:
            assert self.plan.mesh_axes is not None and \
                tuple(mesh.axis_names) == tuple(self.plan.mesh_axes) and \
                tuple(mesh.devices.shape) == tuple(self.plan.mesh_shape), \
                (f"plan built for {self.plan.mesh_axes}"
                 f"{self.plan.mesh_shape} but engine mesh is "
                 f"{mesh.axis_names}{mesh.devices.shape}")
        self.tp = self.plan.tp if mesh is not None else 1
        self.env = make_axis_env(self.plan, batch=slots)
        self.env1 = make_axis_env(self.plan, batch=1)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)

        if paged is None:
            paged = model.supports_paged_kv()
        self.paged = paged
        # KV storage precision: "auto" stores at the plan's cache dtype
        # (bit-identical to the historical engine); an explicit fp dtype
        # restores the pool at that width; int8/fp8 adds per-(row, kv
        # head) absmax scale side-arrays and in-kernel dequantization.
        self.kv_prec = resolve_kv_precision(c.kv_dtype,
                                            self.plan.cache_dtype)
        if self.kv_prec.quantized:
            if not paged:
                raise ValueError(
                    f"kv_dtype={c.kv_dtype!r} needs the paged KV pool: "
                    "quantization is a pool-storage contract (scales "
                    "live beside pool blocks); dense caches store fp")
        self.kv_dtype = self.kv_prec.store_dtype
        # w_dtype is the streamed-weight precision of the gemv decode
        # chain (core/streamline.decode_layer + kernels/gemv); the
        # engine's full-model decode keeps fp weights.  It is carried
        # here so the config round-trips and serving telemetry (bench
        # rows, the serve banner) reports the precision pair the
        # deployment requested.
        self.w_dtype = c.w_dtype
        if paged_kernel not in ("auto", "stream", "gather"):
            raise ValueError(f"paged_kernel={paged_kernel!r} not in "
                             "('auto', 'stream', 'gather')")
        # sampling="fused" (default) runs the sampler INSIDE the jitted
        # decode program — the paper's VXE "sampling with sort" (C1):
        # only the sampled token ids ever cross to the host.  "host" is
        # the pre-fusion baseline (full logits row to host, per-slot
        # python sampling), kept as the parity oracle and the synced
        # side of serving_bench's synced-vs-fused rows.
        if sampling not in ("fused", "host"):
            raise ValueError(f"sampling={sampling!r} not in "
                             "('fused', 'host')")
        if steps_per_sync < 1:
            raise ValueError(f"steps_per_sync={steps_per_sync} must be >= 1")
        if steps_per_sync > 1 and sampling != "fused":
            raise ValueError("steps_per_sync > 1 needs fused sampling: "
                             "the host path must read logits every step")
        self.sampling = sampling
        self.steps_per_sync = int(steps_per_sync)
        self.pipeline = bool(pipeline)
        self.block_s = int(block_s)
        # pow2 prefill buckets pad the prompt with token 0; attention
        # masks padded KV by valid length, but recurrent state (mamba /
        # rwkv) folds every position in — those families prefill at the
        # exact prompt length (one trace per distinct length, as before)
        self.bucketed = model.supports_paged_kv()
        if paged:
            self.block_size = block_size or min(LANE, max_seq)
            assert max_seq % self.block_size == 0, \
                (max_seq, self.block_size)
            self.table_len = max_seq // self.block_size
            if not num_blocks and kv_budget_bytes:
                # size the pool from the per-rank HBM budget: heads are
                # sharded over the ring, so a tp-ring stretches the same
                # budget to tp x the resident tokens — and a quantized
                # pool (block bytes ~halved, plus the scale side-array)
                # admits correspondingly more blocks under the SAME
                # budget: the memory half of the tentpole's claim
                a = self.plan.attn
                num_blocks = pool_blocks_for_budget(
                    kv_budget_bytes,
                    per_rank_block_bytes(
                        self.cfg.n_layers, a.kv_per_rank, a.d_head,
                        self.block_size, self.kv_prec.itemsize,
                        self.kv_prec.scale_itemsize))
            # default pool: dense-equivalent capacity + the null block
            self.num_blocks = num_blocks or (slots * self.table_len + 1)
        else:
            self.block_size = max_seq
            self.table_len = 1
            self.num_blocks = slots
        pool = self._init_kv_state()
        # paged decode dataflow: "stream" runs the Pallas paged kernel
        # straight off the pool (scalar-prefetched block table, no
        # contiguous per-request copy); "gather" keeps the materialized
        # (B, T*bs) view as the reference oracle; "auto" streams
        # whenever the stored GQA layout allows it.  The choice — and the
        # kv_bytes_moved accounting keyed off it — is what the decode
        # program will actually execute.
        self.paged_kernel = (resolve_paged_kernel(self.plan, paged_kernel)
                             if self.paged else None)
        if self.paged_kernel == "stream" and not interpret_mode() and \
                self.kv_dtype == "float16":
            raise ValueError(
                "kv_dtype='float16' cannot stream on this TPU: Mosaic has "
                "no fp16 vector loads for the paged kernel's pool tiles; "
                "use kv_dtype='bfloat16'")
        if self.block_s and self.paged_kernel == "stream" and \
                self.block_s != self.block_size:
            raise ValueError(
                "the streamed paged kernel's KV tile IS the pool "
                f"block_size ({self.block_size}); block_s="
                f"{self.block_s} conflicts (use block_size, or the "
                "gather/dense paths where block_s sets the flash chunk)")
        # chunked prefill (--prefill-chunk): prompts become resident C
        # tokens per step, interleaved with decode windows, instead of
        # one monolithic bucketed prefill that stalls every in-flight
        # stream.  Needs the paged pool: chunk KV scatters incrementally
        # through the block table (recurrent-state families fold every
        # position into per-slot state and must prefill whole).
        if prefill_chunk < 0:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be >= 0")
        if prefill_chunk and not self.paged:
            raise ValueError(
                "prefill_chunk needs the paged KV pool (attention-only "
                "stacks); dense / recurrent-state caches prefill "
                "monolithically")
        self.prefill_chunk = int(prefill_chunk)
        # prefix caching (--prefix-cache): a block-aligned hash index
        # over prompt prefixes lets a new request map already-resident
        # blocks (refcounted) into its table and prefill only the tail;
        # shared blocks split copy-on-write at the first divergent KV
        # write, and refcount-0 cached blocks are recycled LRU-first.
        # Needs the paged pool: sharing is per-block by construction.
        if prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache needs the paged KV pool (attention-only "
                "stacks); the dense per-slot cache cannot share blocks")
        self.prefix_cache = bool(prefix_cache)
        self.prefix = PrefixCache(pool) if (self.paged and prefix_cache) \
            else None
        # speculative decoding (--speculate): a cheap drafter proposes up
        # to draft_k tokens per slot; ONE chunk-as-batch verify pass
        # scores all k+1 positions against the pool and on-device
        # rejection sampling accepts a prefix — stochastic streams draw
        # from exactly the target distribution and greedy streams are
        # bit-identical to the plain engine (repro.serving.sampler.
        # _verify_rows).  ``drafter=`` injects a custom proposer (tests
        # use adversarial / oracle drafters); otherwise ``speculate``
        # picks the built-in n-gram or small-model drafter.
        if speculate not in ("off", "ngram", "model"):
            raise ValueError(f"speculate={speculate!r} not in "
                             "('off', 'ngram', 'model')")
        if draft_k < 1:
            raise ValueError(f"draft_k={draft_k} must be >= 1")
        self.drafter = drafter if drafter is not None else make_drafter(
            speculate, draft_model=draft_model, draft_params=draft_params,
            max_seq=max_seq)
        if self.drafter is not None and not self.paged:
            raise ValueError(
                "speculate needs the paged KV pool: the verify pass "
                "scatters draft KV per-query through block tables")
        self.speculate = ("off" if self.drafter is None
                          else (speculate if speculate != "off"
                                else "custom"))
        self.draft_k = int(draft_k)
        self._verify_jits: Dict[tuple, Callable] = {}
        # fault tolerance: deterministic chaos + the detection seams.
        # ``ring_id`` is stamped by MultiRingEngine; a standalone engine
        # is ring 0.  The injector's fired-set lives OUTSIDE the state
        # reset() rebuilds, so a chaos event fires exactly once per
        # process even across drain/rebuild cycles.
        self.ring_id = 0
        self.events: List[Event] = []
        self._step_no = 0
        self._stalled = False
        self._poison_next = False
        if c.chaos:
            chaos_events = parse_chaos(c.chaos)
            if self.drafter is not None:
                raise ValueError(
                    "chaos injection does not compose with speculative "
                    "decoding yet: the verify path has no finite-logits "
                    "guard, so a NaN fault could commit tokens")
            if not self.paged and \
                    any(e.kind == "corrupt" for e in chaos_events):
                raise ValueError(
                    "chaos kind 'corrupt' poisons a KV pool block and "
                    "needs the paged pool (dense caches have no blocks)")
            self.injector: Optional[FailureInjector] = \
                FailureInjector(chaos=chaos_events)
        else:
            self.injector = None
        self.sched = Scheduler(slots, max_seq, pool, min_bucket,
                               prefix=self.prefix)
        self.stats = EngineStats()
        # spans at the layer boundaries of step(): off until a
        # SpanRecorder attaches (serving/tracker.py)
        self.spans = NO_SPANS
        # cumulative bases for the stats fields ASSIGNED (not
        # incremented) from subsystem counters — scheduler preemptions,
        # pool evictions, prefix index counters.  reset() rebuilds those
        # subsystems from zero; folding the pre-reset totals in here
        # keeps EngineStats monotone across drain/rebuild cycles, so
        # per-window telemetry deltas (serving/tracker.py) never go
        # negative after a migration.
        self._ctr_base = dict(preemptions=0, evicted_blocks=0,
                              prefix_lookups=0, prefix_hits=0,
                              prefix_hit_blocks=0, prefill_tokens_saved=0)
        self._results: Dict[int, List[int]] = {}
        self._rid = 0
        self._chunk_rr = -1           # admit_seq of the last chunk served
        self._buckets_traced: Set[int] = set()
        self._window_jits: Dict[int, Callable] = {}
        self._sample_one = _program("lpu_sample_first", self._sample_one_fn)
        if mesh is None:
            self._decode = _program("lpu_decode", self._decode_fn)
            self._prefill = _program("lpu_prefill", self._prefill_fn)
            self._prefill_chunk_fn = _program("lpu_chunk", self._chunk_fn)
            self._write_pages = _program("lpu_write_pages",
                                         scatter_prefill_pages)
            self._write_dense = _program("lpu_write_dense",
                                         scatter_prefill_dense)
            self._copy_block = _program("lpu_copy_block", copy_pool_block)
        else:
            self._build_mesh_fns()

    def _init_kv_state(self) -> Optional[BlockPool]:
        """(Re)build the device KV state and its host mirrors from the
        engine's fixed geometry: a fresh zeroed cache (pool or dense),
        fresh block tables, and — paged — a fresh :class:`BlockPool`.
        Called at construction and by :meth:`reset` (ring rebuild)."""
        store = (None if self.kv_prec.requested == "auto"
                 else jnp.dtype(self.kv_prec.store_dtype))
        if self.paged:
            pool = BlockPool(self.num_blocks, self.block_size)
            scale_dt = (jnp.dtype(self.kv_prec.scale_dtype)
                        if self.kv_prec.quantized else None)
            self.cache = self.model.init_cache(
                self.slots, self.max_seq, paged=True,
                num_blocks=self.num_blocks, block_size=self.block_size,
                dtype=store, scale_dtype=scale_dt)
            self.block_tables = np.zeros((self.slots, self.table_len),
                                         np.int32)
        else:
            pool = None
            self.cache = self.model.init_cache(self.slots, self.max_seq,
                                               dtype=store)
            self.block_tables = None
        return pool

    def reset(self) -> List[Request]:
        """Drain this ring: drop every KV block, table and scheduling
        structure and rebuild them empty — the rebuild half of a ring
        drain/rebuild cycle.  Returns the orphaned in-flight requests
        (active sequences first, in admission order, then the queue) for
        the supervisor to migrate via the recompute-resume path
        (:meth:`Request.resume_tokens`).

        Finished results, stats, traced jits and the chaos injector's
        fired-set all survive: a rebuilt ring re-enters rotation without
        retracing a single program and without replaying chaos events.
        """
        orphans = [s.req for s in
                   sorted((s for s in self.sched.active if s is not None),
                          key=lambda s: s.admit_seq)]
        orphans += list(self.sched.queue)
        # the rebuilt scheduler/pool/prefix restart their counters at
        # zero: bank the cumulative totals so the ASSIGNED stats fields
        # stay monotone (telemetry deltas must never regress — the
        # tracker seam diffs consecutive snapshots)
        self._ctr_base["preemptions"] = self.stats.preemptions
        self._ctr_base["evicted_blocks"] = self.stats.evicted_blocks
        self._ctr_base["prefix_lookups"] = self.stats.prefix_lookups
        self._ctr_base["prefix_hits"] = self.stats.prefix_hits
        self._ctr_base["prefix_hit_blocks"] = self.stats.prefix_hit_blocks
        self._ctr_base["prefill_tokens_saved"] = \
            self.stats.prefill_tokens_saved
        pool = self._init_kv_state()
        if self.mesh is not None:
            self.cache = jax.device_put(self.cache, self._cache_named)
        self.prefix = (PrefixCache(pool)
                       if (self.paged and self.prefix is not None)
                       else None)
        self.sched = Scheduler(self.slots, self.max_seq, pool,
                               self.config.min_bucket, prefix=self.prefix)
        self._chunk_rr = -1
        self._stalled = False
        self._poison_next = False
        return orphans

    def check_pool_balanced(self) -> None:
        """Raise unless every pool block's refcount balances to zero
        (post-drain invariant; see
        :func:`repro.serving.kv_cache.assert_pool_balanced`)."""
        if self.sched.pool is not None:
            assert_pool_balanced(self.sched.pool, self.prefix)

    # -- chaos injection + detection (serving fault tolerance) ---------

    def _chaos_tick(self) -> None:
        """Fire this step's configured chaos events (exactly once each).

        ``ring`` raises :class:`RingFailure` outright; ``stall`` wedges
        the engine (no progress until the supervisor's heartbeat timeout
        drains it); ``nan`` poisons the next decode program's logits on
        device; ``corrupt`` overwrites a resident pool block with NaN —
        both of the latter are then *detected* by the finite-logits
        guard, never trusted to be benign.
        """
        if self.injector is None:
            return
        for ev in self.injector.fire(self._step_no, self.ring_id):
            self.events.append(Event("chaos", self._step_no,
                                     {"kind": ev.kind,
                                      "ring": self.ring_id}))
            if ev.kind == "ring":
                raise RingFailure("injected_ring_failure", self._step_no,
                                  self.ring_id)
            if ev.kind == "stall":
                self._stalled = True
            elif ev.kind == "nan":
                self._poison_next = True
            elif ev.kind == "corrupt":
                self._corrupt_pool_block()

    def _corrupt_pool_block(self) -> None:
        """Overwrite the first decode-ready sequence's first resident
        block with NaN across every floating cache leaf (a quantized
        pool is poisoned through its scale side-arrays).  The fault then
        surfaces exactly the way a real memory fault would: the next
        decode program's logits go non-finite and the guard fires."""
        blk = None
        for seq in self.sched.active:
            if seq is not None and not seq.prefilling and seq.blocks:
                blk = seq.blocks[0]
                break
        if blk is None:
            return                   # nothing resident: fault lands on air
        bad = jnp.int32(blk)

        def poison(pg):
            if jnp.issubdtype(pg.dtype, jnp.floating):
                return pg.at[:, bad].set(jnp.nan)
            return pg
        self.cache = jax.tree.map(poison, self.cache)
        if self.mesh is not None:
            self.cache = jax.device_put(self.cache, self._cache_named)

    # -- jitted steps --------------------------------------------------

    def _decode_fn(self, params, cache, tokens, positions, tables):
        logits, new_cache, _ = self.model.forward(
            params, tokens, env=self.env, mode="decode",
            positions=positions, cache=cache, block_tables=tables,
            paged_kernel=self.paged_kernel or "gather",
            block_s=self.block_s)
        return logits[:, -1], new_cache

    def _window_fn(self, S, params, cache, tables, last, pos, n_out,
                   alive, rng, temps, top_ks, top_ps, max_new, poison):
        """``S`` fused decode steps in ONE jitted program (lax.scan).

        Each scan step runs the forward, samples every slot in-jit
        (:func:`sample_batched`; the vocab-sharded
        :func:`sample_sharded_batched` under ring tp, so the full
        logits row never leaves the ranks), and applies the engine's
        finish rules ON DEVICE: a slot that hits eos / its token budget
        / max_seq drops out of ``alive`` and is FROZEN — its
        (last, pos) stop advancing, so subsequent steps rewrite the
        same KV entry with the same value (idempotent don't-care work,
        like the null-block writes of idle slots).  The host reads back
        only the (S, slots) int32 token matrix plus an (S, slots) bool
        **finite-logits flag** per sampled row (the fault-tolerance NaN
        guard: O(slots) extra bytes, never the vocab row) and discards
        the frozen slots' overrun tokens during reconciliation.

        ``poison`` is the chaos seam: a traced bool that overwrites the
        sampled-from logits rows with NaN, so the guard is exercised by
        a fault that genuinely happens on device.
        """
        eos = jnp.int32(-1 if self.eos_id is None else self.eos_id)
        axis, tp = self.env.model, self.tp

        def one(carry, _):
            cache, last, pos, n_out, alive, rng = carry
            logits, cache, _ = self.model.forward(
                params, last[:, None], env=self.env, mode="decode",
                positions=pos, cache=cache, block_tables=tables,
                paged_kernel=self.paged_kernel or "gather",
                block_s=self.block_s)
            row = logits[:, -1]
            row = jnp.where(poison, jnp.full_like(row, jnp.nan), row)
            # NaN guard: each rank checks its vocab shard; under tp the
            # verdict must agree ring-wide, so AND via psum
            ok = jnp.isfinite(row).all(axis=-1)
            if tp > 1:
                ok = lax.psum(ok.astype(jnp.int32), axis) == tp
            toks, rng = sample_sharded_batched(
                row, rng, temps, top_ks, top_ps, alive, axis,
                tp)
            live = alive.astype(jnp.int32)
            n_out = n_out + live
            pos = pos + live
            fin = (n_out >= max_new) | (toks == eos) | \
                (pos >= self.max_seq - 1)
            last = jnp.where(alive, toks, last)
            alive = alive & ~fin
            return (cache, last, pos, n_out, alive, rng), (toks, ok)

        (cache, last, pos, n_out, alive, rng), (tok_mat, ok_mat) = \
            lax.scan(one, (cache, last, pos, n_out, alive, rng), None,
                     length=S)
        return tok_mat, ok_mat, cache, last, pos, n_out, alive, rng

    def _window(self, S: int) -> Callable:
        """The jitted ``S``-step fused window (one trace per S)."""
        fn = self._window_jits.get(S)
        if fn is None:
            fn = (_program("lpu_decode_window", partial(self._window_fn, S))
                  if self.mesh is None else self._build_mesh_window(S))
            self._window_jits[S] = fn
        return fn

    def _sample_one_fn(self, row, rng, temp, top_k, top_p):
        """Fused sampling of ONE prefill logits row; rng stays on device."""
        toks, rng = sample_batched(row[None], rng, temp[None],
                                   top_k[None], top_p[None])
        return toks[0], rng

    def _prefill_fn(self, params, tokens, true_len):
        """Batch-1 prefill of a bucket-padded prompt.

        Traced once per bucket size (``tokens.shape[1]``); ``true_len``
        is dynamic so distinct prompt lengths inside one bucket share
        the trace.  Returns (last-valid-token logits row, filled cache).
        """
        B, S = tokens.shape
        cache = self.model.init_cache(1, S)
        positions = jnp.broadcast_to(jnp.arange(S), (1, S))
        logits, new_cache, _ = self.model.forward(
            params, tokens, env=self.env1, mode="prefill", cache=cache,
            positions=positions)
        row = lax.dynamic_index_in_dim(logits[0], true_len - 1, 0,
                                       keepdims=False)
        return row, new_cache

    def _chunk_fn(self, params, cache, tokens, table, start, n_valid):
        """ONE prefill chunk of a partially-resident prompt.

        Unlike :meth:`_prefill_fn` this runs straight against the
        shared pool: the chunk's KV scatters incrementally through the
        request's block ``table`` and its queries attend to the full
        resident history (earlier chunks / recomputed tokens) via the
        same paged dataflow as decode — see
        :func:`repro.models.attention.chunk_prefill_attention`.

        tokens: (1, C) with C static — ONE trace total for any prompt
        mix (vs O(log2 max_seq) pow2 buckets); ``start`` (chunk offset)
        and ``n_valid`` (valid rows; the tail chunk is padded) are
        dynamic.  Returns (logits row of the chunk's last valid token —
        meaningful only for the final chunk — and the updated pool).
        """
        C = tokens.shape[1]
        positions = start + jnp.arange(C, dtype=jnp.int32)[None]
        logits, new_cache, _ = self.model.forward(
            params, tokens, env=self.env1, mode="chunk_prefill",
            positions=positions, cache=cache, block_tables=table[None],
            paged_kernel=self.paged_kernel or "gather",
            kv_valid_len=start + n_valid)
        row = lax.dynamic_index_in_dim(logits[0], n_valid - 1, 0,
                                       keepdims=False)
        return row, new_cache

    def _verify_fwd(self, params, cache, tokens, positions, tables, lens):
        """Forward of ONE speculative verify window.

        Every decode slot's (committed token + K drafts) ride flattened
        as a (1, B*(K+1)) batch of single-token queries with PER-QUERY
        block tables and valid lengths (``mode="verify"`` — see
        :func:`repro.models.attention.verify_attention`): query i of a
        slot attends its resident history plus the drafts before it, so
        one program scores all K+1 positions of every slot.  Returns
        the (B*(K+1), V) logits and the pool with the draft KV
        scattered in; rows past the accepted prefix are STALE but
        harmless — ``seq.pos`` never advances over them, so they are
        masked by valid length and overwritten by the next round's
        writes (logical rollback, zero device work)."""
        logits, new_cache, _ = self.model.forward(
            params, tokens, env=self.env1, mode="verify",
            positions=positions, cache=cache, block_tables=tables,
            paged_kernel=self.paged_kernel or "gather",
            kv_valid_len=lens)
        return logits[0], new_cache

    def _verify_fused_fn(self, K, params, cache, tokens, positions,
                         tables, lens, draft, rng, temps, top_ks,
                         top_ps, alive):
        """Verify forward + in-jit rejection sampling (C1 composed with
        speculation): only (out, n_acc) int32 vectors cross to the host,
        never the (B, K+1, V) verify logits."""
        rows, cache = self._verify_fwd(params, cache, tokens, positions,
                                       tables, lens)
        rows = rows.reshape(self.slots, K + 1, -1)
        out, n_acc, rng = speculative_verify_sharded(
            rows, draft, rng, temps, top_ks, top_ps, alive,
            self.env.model, self.tp)
        return out, n_acc, cache, rng

    def _verify(self, K: int) -> Callable:
        """The jitted verify program for draft length ``K`` (one trace
        per distinct K; rounds cap K near the end of a sequence, so
        only a handful of values ever trace)."""
        key = (K, self.sampling == "fused")
        fn = self._verify_jits.get(key)
        if fn is None:
            if self.mesh is not None:
                fn = self._build_mesh_verify(K)
            elif self.sampling == "fused":
                fn = _program("lpu_verify", partial(self._verify_fused_fn, K))
            else:
                fn = _program("lpu_verify", self._verify_fwd)
            self._verify_jits[key] = fn
        return fn

    # -- ring-parallel (shard_map) step construction -------------------

    def _named(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            spec_tree, is_leaf=lambda x: isinstance(x, P))

    def _build_mesh_fns(self) -> None:
        """shard_map-wrapped decode/prefill over the model ring.

        Everything the host loop touches stays replicated (tokens,
        positions, block tables in; the sampled-from logits row out, so
        sampling happens once, ring-consistent).  Weights, the KV pool
        and prefill caches live sharded per the mapper's specs; inside
        the program every matmul is an ESL collective matmul.
        """
        mesh, m = self.mesh, self.plan.tp_axis
        specs, _ = self.model.param_specs()
        self.params = jax.device_put(self.params, self._named(specs))
        cspecs = self.model.cache_specs(self.env, paged=self.paged,
                                        kv_quant=self.kv_prec.quantized)
        self._mesh_specs = (specs, cspecs)
        cspecs_named = self._named(cspecs)
        self._cache_named = cspecs_named     # reset() re-places with this
        self.cache = jax.device_put(self.cache, cspecs_named)
        pf_cspecs = self.model.cache_specs(self.env1)
        self._pf_named = self._named(pf_cspecs)
        self._pf_zero: Dict[int, object] = {}   # bucket -> zeroed cache

        if self.paged:
            def lpu_decode(params, cache, tokens, positions, tables):
                return self._decode_fn(params, cache, tokens, positions,
                                       tables)
            dec_sm = jax.jit(shard_map(
                lpu_decode, mesh=mesh,
                in_specs=(specs, cspecs, P(None, None), P(None),
                          P(None, None)),
                out_specs=(P(None, m), cspecs), check_vma=False))
            self._decode = dec_sm
        else:
            def lpu_decode(params, cache, tokens, positions):
                return self._decode_fn(params, cache, tokens, positions,
                                       None)
            dec_sm = jax.jit(shard_map(
                lpu_decode, mesh=mesh,
                in_specs=(specs, cspecs, P(None, None), P(None)),
                out_specs=(P(None, m), cspecs), check_vma=False))
            self._decode = lambda p, c, t, pos, tables: dec_sm(p, c, t, pos)

        def lpu_prefill(params, cache0, tokens, true_len):
            B, S = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(S), (1, S))
            logits, new_cache, _ = self.model.forward(
                params, tokens, env=self.env1, mode="prefill",
                cache=cache0, positions=positions)
            row = lax.dynamic_index_in_dim(logits[0], true_len - 1, 0,
                                           keepdims=False)
            return row, new_cache

        pre_sm = jax.jit(shard_map(
            lpu_prefill, mesh=mesh,
            in_specs=(specs, pf_cspecs, P(None, None), P()),
            out_specs=(P(m), pf_cspecs), check_vma=False))

        def prefill(params, tokens, true_len):
            # the bucket cache is an INPUT here (the single-device path
            # allocates it inside the jit): shard_map needs it placed
            # with the mapper's specs, and prefill overwrites the whole
            # [0:S) prefix, so one zeroed buffer per bucket is reusable
            S = int(tokens.shape[1])
            if S not in self._pf_zero:
                self._pf_zero[S] = jax.device_put(
                    self.model.init_cache(1, S), self._pf_named)
            return pre_sm(params, self._pf_zero[S], tokens, true_len)

        self._prefill = prefill
        if self.paged:
            # chunked prefill against the ring-sharded pool: the pool
            # rides in/out with the mapper's specs (head dim 1/tp per
            # rank, same block ids everywhere), tokens/table/offsets are
            # replicated host state and the logits row comes out
            # vocab-sharded exactly like the monolithic prefill's.
            def lpu_chunk(params, cache, tokens, table, start, n_valid):
                return self._chunk_fn(params, cache, tokens, table,
                                      start, n_valid)
            self._prefill_chunk_fn = jax.jit(shard_map(
                lpu_chunk, mesh=mesh,
                in_specs=(specs, cspecs, P(None, None), P(None), P(),
                          P()),
                out_specs=(P(m), cspecs), check_vma=False))
        self._write_pages = _program("lpu_write_pages", scatter_prefill_pages,
                                     out_shardings=cspecs_named)
        self._write_dense = _program("lpu_write_dense", scatter_prefill_dense,
                                     out_shardings=cspecs_named)
        self._copy_block = _program("lpu_copy_block", copy_pool_block,
                                    out_shardings=cspecs_named)

    def _build_mesh_window(self, S: int) -> Callable:
        """shard_map-wrapped fused window over the model ring.

        Token ids come out REPLICATED: every rank runs the identical
        rng chain and samples from the same all-gathered (tp x k)
        candidate set (:func:`sample_sharded_batched`), so no broadcast
        is needed and the full vocab row never leaves the ranks — the
        multi-LPU form of the paper's on-chip sampling.
        """
        mesh = self.mesh
        specs, cspecs = self._mesh_specs
        rep = P(None)
        # tok_mat + the NaN-guard ok_mat come out replicated (the psum
        # inside _window_fn makes the per-rank verdicts agree)
        out_specs = (P(None, None), P(None, None), cspecs) + (rep,) * 5

        if self.paged:
            def lpu_decode_window(params, cache, tables, last, pos, n_out,
                                  alive, rng, temps, top_ks, top_ps,
                                  max_new, poison):
                return self._window_fn(S, params, cache, tables, last,
                                       pos, n_out, alive, rng, temps,
                                       top_ks, top_ps, max_new, poison)
            return jax.jit(shard_map(
                lpu_decode_window, mesh=mesh,
                in_specs=(specs, cspecs, P(None, None)) + (rep,) * 9
                + (P(),),
                out_specs=out_specs, check_vma=False))

        def lpu_decode_window(params, cache, last, pos, n_out, alive, rng,
                              temps, top_ks, top_ps, max_new, poison):
            return self._window_fn(S, params, cache, None, last, pos,
                                   n_out, alive, rng, temps, top_ks,
                                   top_ps, max_new, poison)
        sm = jax.jit(shard_map(
            lpu_decode_window, mesh=mesh,
            in_specs=(specs, cspecs) + (rep,) * 9 + (P(),),
            out_specs=out_specs, check_vma=False))

        def drop_tables(params, cache, tables, *rest):
            return sm(params, cache, *rest)
        # keep .lower working (lower_decode_text / the bench's gate)
        drop_tables.lower = \
            lambda params, cache, tables, *rest: sm.lower(params, cache,
                                                          *rest)
        return drop_tables

    def _build_mesh_verify(self, K: int) -> Callable:
        """shard_map form of the verify program over the model ring.

        Tokens / positions / tables / drafts are replicated host state
        in, verified token ids come out replicated: every rank runs the
        identical rng chain over the all-gathered (tp x 64) candidate
        set (:func:`speculative_verify_sharded`), so the full verify
        logits never leave the ranks — same contract as the fused
        window.  The host-sampling variant returns the vocab-sharded
        logits rows instead (the parity oracle reads them back)."""
        mesh, m = self.mesh, self.plan.tp_axis
        specs, cspecs = self._mesh_specs
        rep1, rep2 = P(None), P(None, None)
        if self.sampling == "fused":
            def lpu_verify(params, cache, tokens, positions, tables, lens,
                           draft, rng, temps, top_ks, top_ps, alive):
                return self._verify_fused_fn(
                    K, params, cache, tokens, positions, tables, lens,
                    draft, rng, temps, top_ks, top_ps, alive)
            return jax.jit(shard_map(
                lpu_verify, mesh=mesh,
                in_specs=(specs, cspecs, rep2, rep2, rep2, rep1, rep2,
                          rep1, rep1, rep1, rep1, rep1),
                out_specs=(rep2, rep1, cspecs, rep1), check_vma=False))

        def lpu_verify(params, cache, tokens, positions, tables, lens):
            return self._verify_fwd(params, cache, tokens, positions,
                                    tables, lens)
        return jax.jit(shard_map(
            lpu_verify, mesh=mesh,
            in_specs=(specs, cspecs, rep2, rep2, rep2, rep1),
            out_specs=(P(None, m), cspecs), check_vma=False))

    # -- sampling ------------------------------------------------------

    def _sample(self, logits_np: np.ndarray, logits_dev,
                params: SamplingParams) -> int:
        """Host-path sampling (``sampling="host"``): per-slot python loop
        over a full logits row already copied to host — the pre-fusion
        baseline whose rng-split order the fused sampler reproduces."""
        if params.temperature <= 0.0:
            return int(np.argmax(logits_np))
        self.rng, sub = jax.random.split(self.rng)
        self.stats.host_syncs += 1
        self.stats.bytes_to_host += 4
        return int(sample_local(logits_dev[None], sub, params)[0])

    def _sample_first(self, row, params: SamplingParams) -> int:
        """Sample the prefill row per the engine's sampling mode.

        Fused: the row stays on device, only the token id (4 bytes)
        crosses; the rng chain advances on device exactly as the host
        loop would (greedy consumes nothing).
        """
        if self.sampling == "fused":
            tok, self.rng = self._sample_one(
                row, self.rng, np.float32(params.temperature),
                np.int32(params.top_k), np.float32(params.top_p))
            self.stats.host_syncs += 1
            self.stats.prefill_syncs += 1
            self.stats.bytes_to_host += 4
            with self.spans.span("lpu.wait", bytes=4):
                return int(tok)
        with self.spans.span("lpu.wait") as sp:
            row_np = np.asarray(row)
            sp.count(bytes=row_np.nbytes)
        self.stats.host_syncs += 1
        self.stats.bytes_to_host += row_np.nbytes
        before = self.stats.host_syncs
        tok = self._sample(row_np, row, params)
        # the row readback + any nested stochastic draw are both
        # prefill-attributed syncs (decode syncs = host_syncs - these)
        self.stats.prefill_syncs += 1 + self.stats.host_syncs - before
        return tok

    # -- prefill + admission -------------------------------------------

    def _refresh_tables(self) -> None:
        """Mirror decode-ready sequences' block lists into the replicated
        (slots, T) table the decode programs consume.  Slots that are
        empty OR still prefilling stay all-zero: their don't-care window
        writes land in the null block — a prefilling slot's REAL blocks
        are known only to the host and the per-chunk program, so decode
        can never clobber a partially-resident prompt.

        A FRESH array is allocated every refresh, never an in-place
        rewrite: ``jnp.asarray`` on CPU can alias an aligned numpy
        buffer zero-copy, so mutating the old array would race with a
        still-executing window that was dispatched against it (the
        pipelined h2 dispatch refreshes tables while h1 is in flight) —
        the transiently zeroed rows read as null-block garbage and
        corrupt the stream."""
        if not self.paged:
            return
        tables = np.zeros((self.slots, self.table_len), np.int32)
        for slot, seq in enumerate(self.sched.active):
            if seq is not None and seq.blocks and not seq.prefilling:
                tables[slot, :len(seq.blocks)] = seq.blocks
        self.block_tables = tables

    def _should_finish(self, seq: SeqSlot, tok: int) -> bool:
        req = seq.req
        return (len(req.out) >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id)
                or seq.pos >= self.max_seq - 1)

    def _finish(self, seq: SeqSlot) -> Request:
        req = seq.req
        req.done = True
        self._results[req.rid] = req.out
        self.sched.release(seq)
        return req

    def _do_prefill(self, seq: SeqSlot) -> Optional[Request]:
        """Run MONOLITHIC bucketed prefill for a just-admitted sequence.

        The whole prompt (pow2-padded) runs as one batch-1 program and
        its cache is block-copied into the pool (or the slot's dense
        region) afterwards.  While it runs, every in-flight decode
        stream is frozen — ``stats.decode_stalls`` counts exactly those
        launches (the tail-latency cliff ``prefill_chunk`` removes).
        Returns the request if it finished immediately (eos /
        max_new_tokens == 1).
        """
        req = seq.req
        tokens = req.resume_tokens()
        if self.sched.num_decoding() > 0:
            self.stats.decode_stalls += 1
        if seq.cached:
            return self._prefill_tail(seq, tokens)
        bucket = (self.sched.bucket(len(tokens)) if self.bucketed
                  else len(tokens))
        with self.spans.span("lpu.prefill", rid=req.rid, tokens=len(tokens),
                             bucket=bucket):
            buf = np.zeros((1, bucket), np.int32)
            buf[0, :len(tokens)] = tokens
            row, pc = self._prefill(self.params, jnp.asarray(buf),
                                    jnp.int32(len(tokens)))
            self._buckets_traced.add(bucket)
            self.stats.prefills += 1
            slot = self.sched.slot_of(seq)
            if self.paged:
                table = np.zeros((bucket // self.block_size,), np.int32)
                table[:len(seq.blocks)] = seq.blocks
                self.cache = self._write_pages(self.cache, pc,
                                               jnp.asarray(table))
            else:
                self.cache = self._write_dense(self.cache, pc,
                                               jnp.int32(slot))
            return self._finish_prefill(seq, row)

    def _prefill_tail(self, seq: SeqSlot, tokens: List[int]
                      ) -> Optional[Request]:
        """Prefill ONLY the un-cached tail of a prefix-cache hit.

        The first ``seq.cached`` tokens' KV is already resident in the
        shared blocks mapped at admission; the tail runs through the
        chunk-prefill program (its queries attend the resident history
        through the paged dataflow, its KV scatters through the table),
        pow2-bucketed so tail lengths share O(log2 max_seq) traces with
        the chunked-prefill path.  Shared blocks the tail writes into
        (a hit capped mid-block) are split copy-on-write first.
        """
        n = len(tokens)
        start = seq.cached
        C = self.sched.bucket(n - start)
        with self.spans.span("lpu.prefill", rid=seq.req.rid,
                             tokens=n - start, bucket=C):
            self._ensure_writable(seq, start, n)
            buf = np.zeros((1, C), np.int32)
            buf[0, :n - start] = tokens[start:n]
            table = np.zeros((self.table_len,), np.int32)
            table[:len(seq.blocks)] = seq.blocks
            row, self.cache = self._prefill_chunk_fn(
                self.params, self.cache, jnp.asarray(buf),
                jnp.asarray(table), jnp.int32(start), jnp.int32(n - start))
            self._buckets_traced.add(("chunk", C))
            self.stats.prefills += 1
            return self._finish_prefill(seq, row)

    def _ensure_writable(self, seq: SeqSlot, lo: int, hi: int,
                         allow_preempt: bool = True) -> bool:
        """Copy-on-write guard: before KV for positions ``[lo, hi)`` of
        ``seq`` is scattered, any block in that span referenced by MORE
        than one table is copied device-side into a fresh block, the
        fresh block swapped into ``seq``'s table, and the shared
        original released — so the write can never reach another
        request's (or the index's still-shared) resident KV.

        Returns False (nothing copied beyond what already succeeded)
        when a fresh block cannot be had without preemption and
        ``allow_preempt`` is False — the retry-capable chunk path waits
        for the next step.  Sole-owner blocks are written in place even
        when index-registered: the write carries the SAME token's KV
        (hits are capped at ``n - 1``, so the only in-span registered
        positions are re-computations of the hashed tokens), which
        keeps every index entry's content claim intact.
        """
        if self.sched.pool is None or hi <= lo:
            return True
        bs = self.block_size
        for li in range(lo // bs, (hi - 1) // bs + 1):
            if li >= len(seq.blocks):
                break
            old = seq.blocks[li]
            if self.sched.pool.ref[old] <= 1:
                continue
            new, victims = self.sched.cow_alloc(seq, allow_preempt)
            self._requeued(victims)
            if new is None:
                return False
            self.cache = self._copy_block(self.cache, jnp.int32(old),
                                          jnp.int32(new))
            seq.blocks[li] = new
            self.sched.pool.free([old])
            self.stats.cow_blocks += 1
        return True

    def _cow_pending(self, seq: SeqSlot, lo: int, hi: int) -> bool:
        """True while any block in the span is still multiply-referenced
        (i.e. :meth:`_ensure_writable` has not run / could not finish)."""
        pool = self.sched.pool
        if pool is None or hi <= lo:
            return False
        bs = self.block_size
        top = min((hi - 1) // bs + 1, len(seq.blocks))
        return any(pool.ref[seq.blocks[li]] > 1
                   for li in range(lo // bs, top))

    def _finish_prefill(self, seq: SeqSlot, row) -> Optional[Request]:
        """Shared tail of both prefill paths, once the prompt is fully
        resident: restore the last sampled token (preemption resume) or
        sample the first one from the final logits row, then apply the
        finish rules.  Returns the request if it finished immediately."""
        req = seq.req
        if self.prefix is not None:
            self.prefix.register(req.resume_tokens(), seq.blocks)
        if seq.resumed:
            seq.last_token = req.out[-1]
            return None
        tok = self._sample_first(row, req.params)
        req.out.append(tok)
        seq.last_token = tok
        if req.stream_cb:
            req.stream_cb(req.rid, tok)
        if self._should_finish(seq, tok):
            return self._finish(seq)
        return None

    def _run_prefill_chunk(self, seq: SeqSlot) -> Optional[Request]:
        """Make the next ``prefill_chunk`` prompt tokens of ``seq``
        resident (KV scattered incrementally into the pool through the
        request's table); on the final chunk, hand off to
        :meth:`_finish_prefill`.  The caller has already reserved the
        chunk's blocks (:meth:`Scheduler.chunk_reserve`).  Returns the
        request if it finished immediately."""
        req = seq.req
        tokens = req.resume_tokens()
        C = self.prefill_chunk
        start = seq.prefilled
        n_valid = min(C, len(tokens) - start)
        with self.spans.span("lpu.prefill", rid=req.rid, tokens=n_valid,
                             bucket=C):
            buf = np.zeros((1, C), np.int32)
            # table is built AFTER the CoW guard: a split swaps block ids
            assert not self._cow_pending(seq, start, start + n_valid)
            buf[0, :n_valid] = tokens[start:start + n_valid]
            table = np.zeros((self.table_len,), np.int32)
            table[:len(seq.blocks)] = seq.blocks
            row, self.cache = self._prefill_chunk_fn(
                self.params, self.cache, jnp.asarray(buf),
                jnp.asarray(table), jnp.int32(start), jnp.int32(n_valid))
            seq.prefilled = start + n_valid
            seq.pos = seq.prefilled
            self._buckets_traced.add(("chunk", C))
            self.stats.prefills += 1
            self.stats.prefill_chunks += 1
            if seq.prefilling:
                return None              # more chunks next step
            return self._finish_prefill(seq, row)

    def _admit_and_chunk(self) -> List[Request]:
        """Chunked-mode admission: admit while slots + first-chunk
        blocks allow, then run ONE prefill chunk — the per-step prefill
        budget — for one prefilling sequence.  The decode window
        dispatched later in the same :meth:`_step` is what makes the
        interleave: active streams keep producing a token per step
        while a long prompt trickles in, instead of standing still for
        its whole bucketed prefill.

        The chunk goes to prefilling sequences ROUND-ROBIN (by
        admission order, resuming after the last one served), not
        FIFO-to-completion: a 40-token prompt ahead of a 3-token prompt
        must not hold the short one's first token hostage for ten
        steps — exactly the head-of-line blocking chunking exists to
        remove."""
        with self.spans.span("lpu.schedule") as sp:
            admitted = 0
            while self._admit(self.prefill_chunk) is not None:
                admitted += 1
            seq = self._next_chunk()
            sp.count(admitted=admitted)
        if seq is None:
            return []
        done = self._run_prefill_chunk(seq)      # ONE chunk per step
        return [] if done is None else [done]

    def _next_chunk(self) -> Optional[SeqSlot]:
        """The prefilling sequence whose next chunk runs this step, its
        blocks reserved (and split copy-on-write); None when no chunk
        can run (pool pressure)."""
        cands = self.sched.prefilling()
        # rotate so the scan starts just after the last sequence served
        # (each candidate probed at most once per step)
        i = next((j for j, s in enumerate(cands)
                  if s.admit_seq > self._chunk_rr), 0)
        for seq in cands[i:] + cands[:i]:
            allow_preempt = self.sched.num_decoding() == 0
            got = self.sched.chunk_reserve(
                seq, self.prefill_chunk,
                allow_preempt=allow_preempt)
            if got is None:
                continue             # pool pressure: try the next seq
            self._requeued(got)
            nxt = min(seq.prefilled + self.prefill_chunk,
                      seq.prefill_target)
            if not self._ensure_writable(seq, seq.prefilled, nxt,
                                         allow_preempt=allow_preempt):
                continue             # no CoW block free: try the next seq
            self._chunk_rr = seq.admit_seq
            return seq
        return None

    def _admit(self, chunk: int = 0) -> Optional[SeqSlot]:
        """:meth:`Scheduler.admit_next`, ending the admitted request's
        queue span."""
        seq = self.sched.admit_next(chunk=chunk)
        if seq is not None:
            self.spans.admitted(seq.req.rid)
        return seq

    def _requeued(self, victims: Sequence[SeqSlot]) -> None:
        """Preempted sequences went back to the queue: their requests'
        queue spans open again."""
        for seq in victims:
            self.spans.queued(seq.req.rid)

    # -- public API ----------------------------------------------------

    def submit(self, prompt: Union[Request, Sequence[int]],
               max_new_tokens: int = 32,
               params: Optional[SamplingParams] = None,
               stream_cb: Optional[StreamCB] = None) -> int:
        """Enqueue a request (non-blocking).  Returns its request id."""
        if isinstance(prompt, Request):
            req = prompt
        else:
            req = Request(self._rid, list(prompt), max_new_tokens,
                          params or SamplingParams(0.0, 0, 1.0),
                          stream_cb=stream_cb)
        if not req.prompt:
            raise ValueError("empty prompt")
        if len(req.prompt) >= self.max_seq:
            raise ValueError(
                f"prompt length {len(req.prompt)} >= max_seq "
                f"{self.max_seq}: no room to decode")
        self._rid = max(self._rid, req.rid) + 1
        self.sched.submit(req)
        self.spans.queued(req.rid)
        return req.rid

    def has_work(self) -> bool:
        """True while the queue or any slot holds an unfinished request
        (same signal :meth:`drain` loops on; the async frontend's pump
        uses it to idle without busy-stepping an empty engine)."""
        return self.sched.has_work()

    def cancel(self, rid: int) -> Optional[Request]:
        """Abort one request between steps: pop it from the queue, or
        release its slot and free its pool blocks if already admitted
        (shared prefix blocks just drop a refcount — cached KV survives
        for future hits).  The partial stream is kept in the results
        buffer.  Returns the request, or None if ``rid`` is not in
        flight here (already finished, or routed to another ring).

        Host-side only and safe by construction: ``step()`` reconciles
        every dispatched window before returning, so no in-flight device
        program can still scatter into the freed blocks.
        """
        req = None
        for r in self.sched.queue:
            if r.rid == rid:
                self.sched.queue.remove(r)
                req = r
                break
        else:
            for seq in self.sched.active:
                if seq is not None and seq.req.rid == rid:
                    self.sched.release(seq)
                    req = seq.req
                    break
        if req is None:
            return None
        req.done = True
        req.cancelled = True
        self._results[rid] = req.out
        self.stats.cancelled_requests += 1
        self.events.append(Event("request_cancelled", self._step_no,
                                 {"rid": rid, "tokens": len(req.out)}))
        return req

    def set_step_knobs(self, prefill_chunk: Optional[int] = None,
                       steps_per_sync: Optional[int] = None) -> None:
        """Retune the per-step latency knobs between steps — the seam
        the SLO budget scheduler (serving/budget.py) drives.

        Cheap by design: decode windows are jitted per window size
        (``_window_jits[S]``) so a new ``steps_per_sync`` at worst adds
        one trace, and the chunk program retraces once per distinct
        chunk width (the budget scheduler quantizes to powers of two to
        bound that).  Validation mirrors construction; additionally a
        chunked engine can never drop back to ``prefill_chunk=0`` —
        mid-prefill sequences would starve (only ``_admit_and_chunk``
        feeds them), and monolithic prefill cannot resume a
        half-resident prompt.
        """
        if steps_per_sync is not None:
            s = int(steps_per_sync)
            if s < 1:
                raise ValueError(f"steps_per_sync={s} must be >= 1")
            if s > 1 and self.sampling != "fused":
                raise ValueError("steps_per_sync > 1 needs fused sampling")
            self.steps_per_sync = s
        if prefill_chunk is not None:
            c = int(prefill_chunk)
            if c < 0:
                raise ValueError(f"prefill_chunk={c} must be >= 0")
            if c and not self.paged:
                raise ValueError("prefill_chunk needs the paged KV pool")
            if c == 0 and self.prefill_chunk:
                raise ValueError(
                    "cannot leave chunked-prefill mode mid-serve: "
                    "admitted prompts may be partially resident")
            self.prefill_chunk = c

    def step(self) -> List[Request]:
        """One scheduler round: admit + prefill (monolithic, or ONE
        chunk in ``prefill_chunk`` mode), then one decode round for the
        whole slot batch — a fused window of up to ``steps_per_sync``
        device steps (pipelined one window ahead) in the default fused
        mode, or a single host-sampled step with ``sampling="host"``.
        In chunked mode the prefill chunk and the decode window share
        the step — that interleave is what keeps active streams
        producing while a long prompt admits.  Returns requests
        finished this round."""
        st = self.stats
        prefills, steps = st.prefills, st.steps
        t0 = time.perf_counter()
        with self.spans.span("lpu.step", ring=self.ring_id) as sp:
            try:
                return self._step()
            finally:
                st.wall += time.perf_counter() - t0
                sp.count(prefills=st.prefills - prefills,
                         steps=st.steps - steps)

    def _step(self) -> List[Request]:
        self._step_no += 1
        self._chaos_tick()
        if self._stalled:
            # injected stall: the ring makes no progress this step (and
            # every later one) — the fleet's heartbeat tracker is what
            # notices and drains it.
            return []
        finished: List[Request] = []
        if self.prefill_chunk:
            finished += self._admit_and_chunk()
        else:
            while True:
                with self.spans.span("lpu.schedule") as sp:
                    seq = self._admit()
                    sp.count(admitted=int(seq is not None))
                if seq is None:
                    break
                done = self._do_prefill(seq)
                if done is not None:
                    finished.append(done)
        with self.spans.span("lpu.schedule") as sp:
            preempted = self.stats.preemptions
            finished += self._harvest_rejections()
            # may preempt (recompute)
            self._requeued(self.sched.ensure_decode_capacity())
            self._mirror_counters()
            sp.count(preempted=self.stats.preemptions - preempted)
        live = self.sched.num_decoding()
        if live == 0:
            return finished
        with self.spans.span("lpu.decode", live=live) as sp:
            steps = self.stats.steps
            if self.drafter is not None:
                done, window = self._spec_decode_round()
            elif self.sampling == "fused":
                done, window = self._fused_decode_round()
            else:
                done, window = self._host_decode_step()
            sp.count(steps=self.stats.steps - steps, window=window)
        finished += done
        self.stats.prefill_traces = len(self._buckets_traced)
        return finished

    def _mirror_counters(self) -> None:
        """Copy the scheduler's, pool's and prefix index's counters into
        ``stats`` over the bases banked at :meth:`reset`."""
        base = self._ctr_base
        self.stats.preemptions = base["preemptions"] \
            + self.sched.preemptions
        if self.sched.pool is not None:
            self.stats.peak_pool_blocks = max(self.stats.peak_pool_blocks,
                                              self.sched.pool.num_used)
            self.stats.evicted_blocks = base["evicted_blocks"] \
                + self.sched.pool.evicted_blocks
        if self.prefix is not None:
            self.stats.prefix_lookups = base["prefix_lookups"] \
                + self.prefix.lookups
            self.stats.prefix_hits = base["prefix_hits"] + self.prefix.hits
            self.stats.prefix_hit_blocks = base["prefix_hit_blocks"] \
                + self.prefix.hit_blocks
            self.stats.prefill_tokens_saved = base["prefill_tokens_saved"] \
                + self.prefix.tokens_saved

    def _harvest_rejections(self) -> List[Request]:
        """Surface scheduler admission rejections (request can NEVER
        fit, e.g. needs more blocks than the whole pool) as structured
        per-request failures instead of the historical engine-crashing
        ``RuntimeError`` — see Scheduler.take_rejected()."""
        finished: List[Request] = []
        for req, why in self.sched.take_rejected():
            req.done = True
            req.failed = True
            req.error = why
            self._results[req.rid] = req.out
            self.stats.rejected_requests += 1
            self.events.append(Event("request_rejected", self._step_no,
                                     {"rid": req.rid, "why": why}))
            finished.append(req)
        return finished

    # -- host-sampled decode (the pre-fusion baseline) -----------------

    def _host_decode_step(self) -> Tuple[List[Request], int]:
        """One decode step, sampling on host: the full (slots, vocab)
        logits tensor crosses to the host every token — the
        serialization the fused path removes (kept as the parity oracle
        and the "synced" row of serving_bench).  Returns the requests
        it finished and its window (1)."""
        with self.spans.span("lpu.prepare"):
            self._refresh_tables()
            toks = np.zeros((self.slots, 1), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            for slot, seq in enumerate(self.sched.active):
                if seq is not None and not seq.prefilling:
                    toks[slot, 0] = seq.last_token
                    pos[slot] = seq.pos
        with self.spans.span("lpu.dispatch", window=1):
            tables = (jnp.asarray(self.block_tables) if self.paged
                      else None)
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos), tables)
        with self.spans.span("lpu.wait") as sp:
            logits_np = np.asarray(logits)
            sp.count(bytes=logits_np.nbytes)
        self.stats.host_syncs += 1
        self.stats.bytes_to_host += logits_np.nbytes
        if self._poison_next:
            # chaos "nan": corrupt the host copy (asarray may alias a
            # read-only device buffer) so the guard below trips.
            self._poison_next = False
            logits_np = np.array(logits_np)
            logits_np[:] = np.nan
        act = [slot for slot, seq in enumerate(self.sched.active)
               if seq is not None and not seq.prefilling]
        if act and not np.isfinite(logits_np[act]).all():
            bad = [slot for slot in act
                   if not np.isfinite(logits_np[slot]).all()]
            raise RingFailure("nan_logits", self._step_no, self.ring_id,
                              {"slots": bad})

        finished: List[Request] = []
        self.stats.steps += 1
        self.stats.slot_steps += self.slots
        with self.spans.span("lpu.reconcile") as sp:
            tokens = self.stats.tokens
            for slot, seq in enumerate(self.sched.active):
                if seq is None or seq.prefilling:
                    continue
                req = seq.req
                self.stats.busy_slot_steps += 1
                self.stats.tokens += 1
                tok = self._sample(logits_np[slot], logits[slot], req.params)
                req.out.append(tok)
                seq.pos += 1
                seq.last_token = tok
                if req.stream_cb:
                    req.stream_cb(req.rid, tok)
                if self._should_finish(seq, tok):
                    finished.append(self._finish(seq))
            sp.count(tokens=self.stats.tokens - tokens)
        return finished, 1

    # -- fused decode: multi-step windows + double-buffered dispatch ---

    def _slot_state(self) -> Tuple[tuple, tuple]:
        """Host slot state -> the window program's carry + per-slot
        sampling params (tiny O(slots) uploads).

        A slot is marked ``alive`` only when it holds a DECODE-READY
        sequence.  Empty slots and slots still chunk-prefilling stay
        dead (zeros): the window freezes them — their (last, pos) never
        advance and their KV writes target the null block (the table
        row is zeroed by :meth:`_refresh_tables`), so a window can
        safely run concurrently with a prompt that is only partially
        resident."""
        B = self.slots
        last = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        n_out = np.zeros((B,), np.int32)
        alive = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        max_new = np.zeros((B,), np.int32)
        for slot, seq in enumerate(self.sched.active):
            if seq is None or seq.prefilling:
                continue
            sp = seq.req.params
            last[slot] = seq.last_token
            pos[slot] = seq.pos
            n_out[slot] = len(seq.req.out)
            alive[slot] = True
            temps[slot] = sp.temperature
            top_ks[slot] = sp.top_k
            top_ps[slot] = sp.top_p
            max_new[slot] = seq.req.max_new_tokens
        return (last, pos, n_out, alive), (temps, top_ks, top_ps, max_new)

    def _admission_waiting(self) -> bool:
        """True when the baseline loop could admit next step: a queued
        request AND a free slot (pool pressure pending).  Multi-step
        windows shrink to a single step then, so admission latency
        stays at the single-step baseline's.  Note this is the
        *window-size* rule only — the full-prefill decode stall (every
        stream frozen while a long prompt prefills monolithically) is
        what ``prefill_chunk`` removes; see :meth:`_admit_and_chunk`."""
        return bool(self.sched.queue) and \
            any(s is None for s in self.sched.active)

    def _may_survive(self, steps: int) -> bool:
        """Could any decode-ready slot still be alive after ``steps``
        more tokens?  (Budget/length check only — eos can still end a
        window early; speculation past an eos is bounded waste, never
        wrong.  Prefilling slots sit windows out entirely.)"""
        for seq in self.sched.active:
            if seq is None or seq.prefilling:
                continue
            if (seq.req.max_new_tokens - len(seq.req.out)) > steps and \
                    (self.max_seq - 1 - seq.pos) > steps:
                return True
        return False

    def _dispatch_window(self, win: int, carry: tuple, samp: tuple):
        """Launch one fused window (non-blocking: jax dispatch is async).
        Returns ((win, token matrix, ok matrix, active snapshot),
        device carry).  Consumes a pending chaos ``nan`` event: the
        poison flag rides into the program as a traced bool, so the
        fault happens on device and only the guard can catch it."""
        poison = np.bool_(self._poison_next)
        self._poison_next = False
        with self.spans.span("lpu.dispatch", window=win):
            tables = (jnp.asarray(self.block_tables) if self.paged
                      else None)
            out = self._window(win)(self.params, self.cache, tables,
                                    *carry, self.rng, *samp, poison)
        tok_mat, ok_mat, self.cache, last, pos, n_out, alive, self.rng \
            = out
        snapshot = [s is not None and not s.prefilling
                    for s in self.sched.active]
        return (win, tok_mat, ok_mat, snapshot), (last, pos, n_out, alive)

    def _reconcile(self, handle) -> List[Request]:
        """Block on a window's token matrix (the ONE device->host sync
        per window) and replay the finish rules the device already
        applied: tokens of slots that finished earlier in the window —
        or in a previously reconciled window — are overrun and
        discarded; everything else appends exactly as the single-step
        loop would.

        The NaN guard runs per window step BEFORE that step's tokens
        commit: the first step whose sampled-from row went non-finite
        for any dispatched slot raises :class:`RingFailure` — tokens of
        earlier (finite) steps are already committed, tokens at or
        after the fault never reach a request, so a recovered stream
        can be bit-identical to a fault-free run."""
        win, tok_mat, ok_mat, dispatch_active = handle
        with self.spans.span("lpu.wait") as sp:
            toks = np.asarray(tok_mat)                 # (win, slots)
            oks = np.asarray(ok_mat)                   # (win, slots) bool
            sp.count(bytes=toks.nbytes + oks.nbytes)
        self.stats.host_syncs += 1
        self.stats.bytes_to_host += toks.nbytes + oks.nbytes
        with self.spans.span("lpu.reconcile") as sp:
            tokens = self.stats.tokens
            finished: List[Request] = []
            for s in range(win):
                bad = [slot for slot in range(self.slots)
                       if dispatch_active[slot] and not oks[s, slot]]
                if bad:
                    raise RingFailure(
                        "nan_logits", self._step_no, self.ring_id,
                        {"window_step": s, "slots": bad})
                if self.sched.num_decoding() == 0:
                    self.stats.overrun_tokens += \
                        (win - s) * sum(dispatch_active)
                    break
                self.stats.steps += 1
                self.stats.slot_steps += self.slots
                for slot, seq in enumerate(self.sched.active):
                    if seq is None or seq.prefilling:
                        if dispatch_active[slot]:
                            self.stats.overrun_tokens += 1
                        continue
                    req = seq.req
                    self.stats.busy_slot_steps += 1
                    self.stats.tokens += 1
                    tok = int(toks[s, slot])
                    req.out.append(tok)
                    seq.pos += 1
                    seq.last_token = tok
                    if req.stream_cb:
                        req.stream_cb(req.rid, tok)
                    if self._should_finish(seq, tok):
                        finished.append(self._finish(seq))
            sp.count(tokens=self.stats.tokens - tokens)
        return finished

    def _fused_decode_round(self) -> Tuple[List[Request], int]:
        """One fused decode round: up to two pipelined windows.

        Window size is ``steps_per_sync`` whenever no admission is
        waiting and the scheduler can reserve the whole window's blocks
        WITHOUT preemption (all-or-nothing, so speculative lookahead
        never evicts resident work); otherwise a single fused step.
        With ``pipeline=True`` and an empty queue, window k+1 is
        dispatched off window k's on-device carry BEFORE blocking on
        window k's tokens — the device-side finish masking makes the
        chained carry exact, so the speculation can waste compute
        (overrun tokens) but never produce wrong ones.  Returns the
        requests it finished and its window size.
        """
        S = self.steps_per_sync
        with self.spans.span("lpu.prepare"):
            win = S if (S > 1 and not self._admission_waiting()
                        and self.sched.reserve_lookahead(S)) else 1
            self._refresh_tables()
            carry, samp = self._slot_state()
        h1, dev_carry = self._dispatch_window(win, carry, samp)
        with self.spans.span("lpu.prepare"):
            ahead = self.pipeline and not self.sched.queue \
                and self._may_survive(win) \
                and self.sched.reserve_lookahead(2 * win)
            if ahead:
                self._refresh_tables()
        h2 = (self._dispatch_window(win, dev_carry, samp)[0] if ahead
              else None)
        finished = self._reconcile(h1)
        if h2 is not None:
            finished += self._reconcile(h2)
        return finished, win

    def _spec_decode_round(self) -> Tuple[List[Request], int]:
        """One draft-and-verify speculative round.

        Per decode-ready slot the drafter proposes up to ``draft_k``
        tokens from the request's visible token stream; ONE verify
        program scores all K+1 positions of every slot against the pool
        and rejection sampling accepts a per-slot prefix.  Slots the
        drafter had nothing for ride along with zero-padded drafts:
        rejection sampling is exact for ANY deterministic proposal, so
        correctness never depends on the drafter — only the acceptance
        counters (which track real proposals) do.  Rejected positions
        roll back logically (``seq.pos`` advances only over emitted
        tokens; stale KV is masked and overwritten next round), and the
        copy-on-write guard runs BEFORE the speculative write, so a
        rejected write can never have landed in a block another table
        still references.

        Rounds that cannot speculate — no proposal anywhere, no
        head-room before max_seq, or a lookahead-block shortfall — fall
        back to one plain round, so composition with admission,
        chunked prefill and preemption needs no special cases.  Returns
        the requests it finished and its window (K + 1 positions).
        """
        K = self.draft_k
        for seq in self.sched.active:
            if seq is not None and not seq.prefilling:
                K = min(K, self.max_seq - 1 - seq.pos)
        props: Dict[int, List[int]] = {}
        if K >= 1:
            for slot, seq in enumerate(self.sched.active):
                if seq is None or seq.prefilling:
                    continue
                p = self.drafter.propose(
                    list(seq.req.prompt) + list(seq.req.out), K)[:K]
                if p:
                    props[slot] = p
        if K < 1 or not props \
                or not self.sched.reserve_lookahead(1, draft_k=K):
            return (self._fused_decode_round()
                    if self.sampling == "fused"
                    else self._host_decode_step())
        B, K1 = self.slots, K + 1
        with self.spans.span("lpu.prepare"):
            for seq in self.sched.active:
                if seq is not None and not seq.prefilling:
                    self._ensure_writable(seq, seq.pos, seq.pos + K + 1)
            self._refresh_tables()
            (last, pos, _, alive), (temps, top_ks, top_ps, _) = \
                self._slot_state()
            toks = np.zeros((B, K1), np.int32)
            draft = np.zeros((B, K), np.int32)
            real = np.zeros((B,), np.int32)
            toks[:, 0] = last
            for slot, p in props.items():
                draft[slot, :len(p)] = p
                real[slot] = len(p)
            toks[:, 1:] = draft
            positions = pos[:, None] + np.arange(K1, dtype=np.int32)[None]
            positions = np.where(alive[:, None], positions, 0) \
                .astype(np.int32)
            lens = np.where(alive[:, None], positions + 1, 1) \
                .reshape(-1).astype(np.int32)
            tables = np.repeat(self.block_tables, K1, axis=0)
        if self.sampling == "fused":
            with self.spans.span("lpu.dispatch", window=K1):
                out, n_acc, self.cache, self.rng = self._verify(K)(
                    self.params, self.cache,
                    jnp.asarray(toks.reshape(1, B * K1)),
                    jnp.asarray(positions.reshape(1, B * K1)),
                    jnp.asarray(tables), jnp.asarray(lens),
                    jnp.asarray(draft), self.rng, jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(alive))
            with self.spans.span("lpu.wait") as sp:
                out = np.asarray(out)
                n_acc = np.asarray(n_acc)
                sp.count(bytes=out.nbytes + n_acc.nbytes)
            self.stats.host_syncs += 1
            self.stats.bytes_to_host += out.nbytes + n_acc.nbytes
        else:
            with self.spans.span("lpu.dispatch", window=K1):
                rows, self.cache = self._verify(K)(
                    self.params, self.cache,
                    jnp.asarray(toks.reshape(1, B * K1)),
                    jnp.asarray(positions.reshape(1, B * K1)),
                    jnp.asarray(tables), jnp.asarray(lens))
            with self.spans.span("lpu.wait") as sp:
                rows_np = np.asarray(rows).reshape(B, K1, -1)
                sp.count(bytes=rows_np.nbytes)
            self.stats.host_syncs += 1
            self.stats.bytes_to_host += rows_np.nbytes
            stoch = alive & (temps > 0.0)
            self.rng, keys = split_spec_rng_chain(
                self.rng, jnp.asarray(stoch), K1)
            out = np.zeros((B, K1), np.int32)
            n_acc = np.zeros((B,), np.int32)
            for slot in range(B):
                if not alive[slot]:
                    continue
                o, n = spec_verify_rows(
                    jnp.asarray(rows_np[slot]),
                    jnp.asarray(draft[slot]), keys[slot],
                    jnp.float32(temps[slot]), jnp.int32(top_ks[slot]),
                    jnp.float32(top_ps[slot]))
                out[slot] = np.asarray(o)
                n_acc[slot] = int(n)
        finished: List[Request] = []
        self.stats.steps += 1
        self.stats.spec_rounds += 1
        self.stats.slot_steps += self.slots
        with self.spans.span("lpu.reconcile") as sp:
            tokens = self.stats.tokens
            for slot, seq in enumerate(self.sched.active):
                if seq is None or seq.prefilling:
                    continue
                req = seq.req
                self.stats.busy_slot_steps += 1
                n = int(n_acc[slot])
                self.stats.draft_tokens += int(real[slot])
                self.stats.accepted_tokens += min(n, int(real[slot]))
                emit = [int(t) for t in out[slot, :n + 1]]
                for j, tok in enumerate(emit):
                    self.stats.tokens += 1
                    req.out.append(tok)
                    seq.pos += 1
                    seq.last_token = tok
                    if req.stream_cb:
                        req.stream_cb(req.rid, tok)
                    if self._should_finish(seq, tok):
                        self.stats.overrun_tokens += len(emit) - j - 1
                        finished.append(self._finish(seq))
                        break
            sp.count(tokens=self.stats.tokens - tokens)
        return finished, K1

    def drain(self) -> Dict[int, List[int]]:
        """Step until the queue and all slots are empty; returns
        {rid: generated tokens} finished since the last drain.

        Results are handed off exactly once (the buffer is cleared), so a
        long-running submit/step/drain server does not accumulate every
        request it ever served.
        """
        while self.sched.has_work():
            self.step()
        self.stats.prefill_traces = len(self._buckets_traced)
        out, self._results = self._results, {}
        return out

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 params: Optional[SamplingParams] = None,
                 stream_cb: Optional[StreamCB] = None) -> List[List[int]]:
        """HF-like entry point: batch of prompts -> generated ids."""
        rids = [self.submit(list(p), max_new_tokens, params,
                            stream_cb=stream_cb) for p in prompts]
        results = self.drain()
        return [results[r] for r in rids]

    # -- monitoring ----------------------------------------------------

    def kv_cache_bytes(self) -> int:
        """Bytes held by the KV cache (block pool or dense slot cache)."""
        return cache_bytes(self.cache)

    def per_rank_kv_bytes(self) -> int:
        """KV bytes resident on ONE ring rank (heads shard 1/tp)."""
        return self.kv_cache_bytes() // self.tp

    def pending_load(self) -> int:
        """Outstanding tokens (queued + active) — the router's signal."""
        return self.sched.pending_tokens()

    def kv_bytes_moved_per_step(self) -> int:
        """Analytic KV bytes MOVED through HBM per decode step (all ranks).

        * dense / streamed-paged: attention reads each resident KV tile
          exactly once (``V`` = the table-span view bytes); nothing is
          copied.
        * gather-paged: the contiguous per-request view is materialized
          first — read the pool span, write the view, then attention
          reads the view back: ``3 * V``.  This is the O(resident-tokens)
          copy per layer per token the streamed kernel removes.

        A quantized pool streams at the quantized byte width PLUS its
        scale side-array (one scale per (row, kv head)): per (position,
        head) that is ``d_head * store_itemsize + scale_itemsize``
        bytes instead of ``d_head * fp_itemsize`` — the bandwidth half
        of the tentpole's claim (the accuracy half is serving_bench's
        drift gate).
        """
        a = self.plan.attn
        row = self.kv_prec.bytes_per_row_head(a.d_head)
        v = 2 * self.cfg.n_layers * self.slots * self.table_len \
            * self.block_size * a.gp * row
        return 3 * v if self.paged_kernel == "gather" else v

    def dense_equiv_bytes(self) -> int:
        """Bytes a dense (slots, max_seq) cache of this model would take."""
        if not self.paged:
            return self.kv_cache_bytes()
        per_tok = self.kv_cache_bytes() // (self.num_blocks
                                            * self.block_size)
        return per_tok * self.slots * self.max_seq

    def decode_block_s(self) -> int:
        """KV stream tile of the decode program actually dispatched: the
        pool block size when streaming off the pool, else the flash
        chunk (the ``block_s`` override or its 2048 default, clamped to
        the resident span)."""
        if self.paged and self.paged_kernel == "stream":
            return self.block_size
        return min(self.block_s or 2048, self.max_seq)

    def planned_block_s(self) -> int:
        """What :func:`plan_block_s` recommends for this span — the
        reference a real-hardware ``--block-s`` sweep tunes against
        (ROADMAP: tune the streamed kernel's block size on TPU)."""
        a = self.plan.attn
        gs = max(a.hp // max(a.gp, 1), 1) if a is not None else 1
        dh = a.d_head if a is not None else LANE
        return plan_block_s(self.max_seq, dh, gs, self.kv_prec.itemsize)

    def lower_decode(self, sharding=None):
        """The decode program this engine will actually run (the fused
        1-step window, or the host-sampled logits step), lowered.

        ``sharding``: lower for that device instead, with every argument
        abstract (shapes and dtypes of the engine's own), e.g. a
        described TPU's device for an ahead-of-time compile."""
        tables = (jnp.asarray(self.block_tables) if self.paged else None)
        if self.sampling != "fused":
            fn = self._decode
            args = (self.params, self.cache,
                    jnp.zeros((self.slots, 1), jnp.int32),
                    jnp.zeros((self.slots,), jnp.int32), tables)
        else:
            carry, samp = self._slot_state()
            fn = self._window(1)
            args = (self.params, self.cache, tables, *carry, self.rng,
                    *samp, np.bool_(False))
        if sharding is not None:
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=sharding), args)
        return fn.lower(*args)

    def lower_decode_text(self) -> str:
        """MLIR of :meth:`lower_decode` — the bench's MEASURED no-copy
        gate greps this text for per-request view tensors instead of
        trusting the analytic byte formula."""
        return self.lower_decode().as_text()


class MultiRingEngine:
    """C3 multi-tenant serving: one :class:`LPUEngine` per ESL sub-ring.

    The paper's router splits an 8-LPU ring into 2x4 / 4x2 sub-rings so
    several request streams are served concurrently with no cross-ring
    interference.  Here the ``model`` axis of ``mesh`` is carved by
    :func:`repro.core.rings.submeshes` into ``total // ring_size``
    disjoint sub-meshes; each gets an independent ring-parallel engine
    (its own weight replica, KV pool and scheduler), so no collective of
    one ring can involve another ring's devices — the paper's isolation
    property by construction.

    ``model`` must be built with a plan for ONE sub-ring (mesh axes
    ``("model",)``, shape ``(ring_size,)``); the same plan serves every
    ring because the sub-meshes are congruent.  Admission is per-ring:
    :class:`repro.serving.scheduler.RingRouter` sends each request to
    the ring with the fewest outstanding tokens.

    Concurrency caveat: isolation is the paper's property reproduced
    here; *wall-clock* concurrency is not.  ``step()`` dispatches the
    rings sequentially from one host thread, and each engine's step
    blocks on its host-side sampling sync — a real deployment runs one
    driver per sub-ring.  Throughput accounting must therefore use
    total tokens over fleet wall time, never the sum of per-ring rates
    (see ``benchmarks/serving_bench.py``).

    Fault tolerance (docs/serving.md §Fault tolerance): ``step()``
    supervises the rings.  A :class:`repro.serving.ft.RingFailure`
    raised by any engine (chaos-injected or detected by the NaN guard)
    — or a ring that stops making progress past the heartbeat timeout —
    triggers the recovery cycle: drain the ring
    (:meth:`LPUEngine.reset` returns its orphaned requests and rebuilds
    the KV pool / prefix cache / scheduler from scratch), migrate the
    orphans to surviving rings through the recompute-resume path
    (``Request.resume_tokens``), and return the rebuilt ring to
    rotation (:meth:`HeartbeatTracker.revive`).  Migrations are bounded
    by ``EngineConfig.max_migrations``; a request that exhausts them
    surfaces ``failed=True`` + ``error`` instead of crashing the fleet.

    Host-fleet mode (``mesh=None, rings=N``) builds N single-device
    engines over the same host backend — no ring parallelism, but the
    full supervision/recovery machinery, which is how the chaos tests
    and serving_bench exercise it without a multi-device mesh.
    """

    def __init__(self, model, params, mesh=None, *, ring_size: int = 0,
                 rings: int = 0, config: Optional[EngineConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 step_prior_s: float = 0.0, **engine_kw):
        if mesh is not None:
            if ring_size < 1:
                raise ValueError("mesh fleets need ring_size >= 1")
            total = mesh.devices.shape[-1]
            self.ring_cfg = reconfigure(total, ring_size)
            assert self.ring_cfg.validate_disjoint()
            assert model.plan.tp == ring_size, \
                (f"model planned for tp={model.plan.tp}, "
                 f"ring_size={ring_size}")
            self.engines = [LPUEngine(model, params, config, mesh=sub,
                                      **engine_kw)
                            for sub in submeshes(mesh, ring_size)]
        else:
            if rings < 1:
                raise ValueError("host fleets need rings >= 1")
            if model.plan.mesh_axes is not None:
                raise ValueError(
                    "host-fleet mode needs a mesh-free plan "
                    f"(got mesh_axes={model.plan.mesh_axes})")
            self.ring_cfg = None
            self.engines = [LPUEngine(model, params, config, **engine_kw)
                            for _ in range(rings)]
        for i, eng in enumerate(self.engines):
            eng.ring_id = i
        self.router = RingRouter(len(self.engines))
        self.ring_of: Dict[int, int] = {}
        self._rid = 0
        # -- supervision state (see class docstring) -------------------
        c = self.engines[0].config
        self.max_migrations = c.max_migrations
        # "prefix": probe every ring's PrefixCache at submit and prefer
        # the deepest owner of the prompt's block chain (see RingRouter)
        self.affinity = c.affinity
        chaotic = any(e.injector is not None for e in self.engines)
        # chaos runs default to a virtual clock (1 fleet round = 1 s)
        # so heartbeat timeouts are step-deterministic, never wall time
        self._clock = clock or (ManualClock() if chaotic else time.time)
        self.round_dt = 1.0
        self.hb = HeartbeatTracker(len(self.engines),
                                   timeout_s=c.heartbeat_timeout_s,
                                   clock=self._clock)
        self.monitors = [StragglerMonitor(mu0=step_prior_s or None)
                         for _ in self.engines]
        self.ft_straggler_drain = c.ft_straggler_drain
        self.events: List[Event] = []
        self._migrations: Dict[int, int] = {}   # rid -> resubmit count
        self.failed: Dict[int, Request] = {}    # rid -> failed request
        self._round = 0

    @property
    def n_rings(self) -> int:
        return len(self.engines)

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               params: Optional[SamplingParams] = None,
               stream_cb: Optional[StreamCB] = None) -> int:
        """Route to the prefix-owning ring (``affinity="prefix"``) or
        the least-loaded sub-ring; returns a global rid."""
        prompt = list(prompt)
        aff = None
        if self.affinity == "prefix":
            aff = [e.prefix.peek(prompt) if e.prefix is not None else 0
                   for e in self.engines]
        ring = self.router.route(
            [e.pending_load() for e in self.engines], affinity=aff)
        req = Request(self._rid, prompt, max_new_tokens,
                      params or SamplingParams(0.0, 0, 1.0),
                      stream_cb=stream_cb)
        self._rid += 1
        self.engines[ring].submit(req)
        self.ring_of[req.rid] = ring
        return req.rid

    def cancel(self, rid: int) -> Optional[Request]:
        """Abort one in-flight request on whichever ring owns it (the
        ``ring_of`` map follows migrations).  Returns the request, or
        None if it already finished / terminally failed."""
        ring = self.ring_of.get(rid)
        if ring is None:
            return None
        return self.engines[ring].cancel(rid)

    def step(self) -> List[Request]:
        """One supervised round on every sub-ring that has work.

        Idle rings heartbeat for free; a working ring beats only when
        its round made progress (finished a step or a prefill, or ran
        out of work), so a wedged ring goes silent and the timeout
        check at the end of the round eventually drains it."""
        self._round += 1
        done: List[Request] = []
        alive: List[int] = []
        for i, eng in enumerate(self.engines):
            if not eng.sched.has_work():
                alive.append(i)
                continue
            before = eng.stats.steps + eng.stats.prefills
            t0 = time.perf_counter()
            try:
                done.extend(eng.step())
            except RingFailure as f:
                done.extend(
                    self._on_ring_failure(i, f.reason, dict(f.detail)))
                continue
            ev = self.monitors[i].record(self._round,
                                         time.perf_counter() - t0)
            if ev is not None:
                self.events.append(Event("straggler", self._round,
                                         {"ring": i, **ev.detail}))
                if self.ft_straggler_drain:
                    done.extend(self._on_ring_failure(
                        i, "straggler", dict(ev.detail)))
                    continue
            progressed = (eng.stats.steps + eng.stats.prefills) > before \
                or not eng.sched.has_work()
            if progressed:
                alive.append(i)
        # beat at the end of the round: one host thread steps the rings
        # in turn, so the time another ring's step takes (a cold compile
        # takes a minute on the chip) is not silence of this one
        now = self._clock()
        for i in alive:
            self.hb.beat(i, now)
        if isinstance(self._clock, ManualClock):
            self._clock.advance(self.round_dt)
        for i in self.hb.check():
            done.extend(self._on_ring_failure(
                i, "heartbeat_timeout",
                {"timeout_s": self.hb.timeout}))
        return done

    def _on_ring_failure(self, i: int, reason: str,
                         detail: dict) -> List[Request]:
        """Drain -> migrate -> rebuild one ring.  Returns the requests
        that exhausted their migration budget (terminally failed)."""
        eng = self.engines[i]
        eng.stats.ring_failures += 1
        self.events.append(Event("ring_failed", self._round,
                                 {"ring": i, "reason": reason, **detail}))
        orphans = eng.reset()
        self.hb.revive(i)
        self.events.append(Event("ring_rebuilt", self._round,
                                 {"ring": i, "orphans": len(orphans)}))
        failed: List[Request] = []
        for req in orphans:
            got = self._migrate(req, i)
            if got is not None:
                failed.append(got)
        return failed

    def _migrate(self, req: Request, source: int) -> Optional[Request]:
        """Resubmit one orphaned request through the recompute-resume
        path, preferring a surviving ring.  Returns the request if its
        retry budget is exhausted (now a structured failure), else
        None."""
        n = self._migrations.get(req.rid, 0)
        if n >= self.max_migrations:
            req.done = True
            req.failed = True
            req.error = (f"retries exhausted: migrated {n}x "
                         f"(max_migrations={self.max_migrations})")
            self.failed[req.rid] = req
            self.events.append(Event("request_failed", self._round,
                                     {"rid": req.rid, "migrations": n}))
            return req
        self._migrations[req.rid] = n + 1
        others = [j for j in range(len(self.engines)) if j != source]
        pool = others or [source]
        ring = min(pool, key=lambda j: (self.engines[j].pending_load(), j))
        tgt = self.engines[ring]
        tgt.submit(req)
        self.ring_of[req.rid] = ring
        tgt.stats.retries += 1
        if ring != source:
            tgt.stats.migrated_requests += 1
        return None

    def has_work(self) -> bool:
        return any(e.sched.has_work() for e in self.engines)

    def drain(self) -> Dict[int, List[int]]:
        while self.has_work():
            self.step()
        out: Dict[int, List[int]] = {}
        for eng in self.engines:
            out.update(eng.drain())
        # failed requests surface their partial streams, same contract
        # as per-engine rejection — callers check Request.failed/error
        # (the fleet keeps the Request itself in ``self.failed``)
        for rid, req in self.failed.items():
            out[rid] = req.out
        return out

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 params: Optional[SamplingParams] = None,
                 stream_cb: Optional[StreamCB] = None) -> List[List[int]]:
        rids = [self.submit(list(p), max_new_tokens, params,
                            stream_cb=stream_cb) for p in prompts]
        results = self.drain()
        return [results[r] for r in rids]

    def per_ring_stats(self) -> List[EngineStats]:
        return [e.stats for e in self.engines]

    def fleet_counters(self) -> Dict[str, int]:
        """Aggregate FT counters across the fleet (banner + bench)."""
        stats = self.per_ring_stats()
        return {
            "ring_failures": sum(s.ring_failures for s in stats),
            "migrated_requests": sum(s.migrated_requests for s in stats),
            "retries": sum(s.retries for s in stats),
            "rejected_requests": sum(s.rejected_requests for s in stats),
            "failed_requests": len(self.failed),
            "submitted": self._rid,
            "events": len(self.events)
                + sum(len(e.events) for e in self.engines),
        }
