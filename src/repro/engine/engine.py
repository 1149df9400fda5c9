"""Real JAX serving engine: executes mixed chunked-prefill + decode
batches on an actual model (runnable on CPU with small configs; the same
code path jit-lowers for the TPU meshes in the dry-run).

Three executor paths:

* **paged** (default for all-ATTN configs): KV lives in a physical
  block pool ([L, num_blocks, block_size, Hkv, Dh], flat token axis)
  addressed through per-slot int32 block tables
  (``repro.engine.paged``).  Every iteration — prefill chunks AND
  decode steps together — executes as ONE fused jit call: decode rows
  are packed as length-1 chunks next to the prefill rows, attention
  reads KV through the block tables (Pallas paged kernels when
  ``attention.use_kernels`` is on, jnp gather reference otherwise),
  sampling is fused, and only token ids cross the host boundary.
  Prefix reuse and migration become block-table pointer updates, and
  HBM admission is bounded by blocks actually referenced instead of
  ``n_slots x max_seq`` reserved rows.
* **batched dense** (fallback for families that cannot page: recurrent
  / windowed state, capacity-dropping MoE): packed T-padded prefill
  where safe, else exact-shape slot-indexed rows; full-slot-batch fused
  decode over the slot-contiguous dense cache.
* **row-wise reference** (``batched=False``): the original executor —
  per-request exact-shape prefill with host-side cache row
  gather/scatter and host-side sampling.  Kept as the token-exact
  oracle the paged and batched paths are tested against.

Decode on the dense paths always runs the full slot batch (inactive
rows are harmless — masks derive validity from each row's own position,
and recurrent state is zeroed at slot assignment); the paged path runs
exactly the scheduled rows.

Two serving-loop-facing mechanisms sit on top of the three paths:

* **multi-step decode horizon** — a decode-only iteration whose plan
  carries ``horizon == K > 1`` executes as ONE jitted ``lax.scan`` over
  K decode steps (paged and packed-dense paths): sampling stays on
  device between steps, per-row done-masks freeze rows that emit EOS or
  exhaust their per-row budget (their KV writes drop via ``valid_len``),
  and block tables are pre-grown to the end-of-horizon frontier so the
  in-loop write pointer advances through them.  One host sync then
  retires up to ``K x B`` tokens.
* **non-blocking ``step_async``** — every executor path dispatches its
  jit calls and returns a :class:`PendingStep` immediately (JAX async
  dispatch keeps the device busy); the single blocking ``np.asarray``
  readback happens at ``resolve()``, so the serving loop can ingest
  arrivals, schedule other instances, and stream the *previous*
  horizon's tokens while this one computes.  ``execute`` remains the
  synchronous wrapper (``step_async(plan).resolve()``).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.prefix_cache import PrefixCache
from repro.cache.prefix_tree import PrefixTree
from repro.engine import batching, migrate
from repro.engine.kvcache import SlotTable
from repro.engine.paged import PagedKVCache
from repro.engine.request import Request
from repro.models import transformer as tf
from repro.models.config import ATTN, ModelConfig


class MigrationFormatError(ValueError):
    """A migrated engine state's KV format (dense row vs. paged blocks)
    does not match the destination executor's format.  Dense<->paged
    cross-migration is unsupported — migrate between like engines."""


class PendingStep:
    """An in-flight executor iteration: the jit calls are dispatched, the
    host readback is deferred.

    ``resolve()`` performs the (single) blocking host sync, applies
    tokens/EOS to the step's requests through the executor-supplied
    closure, and returns the eos dict — the same contract as
    ``execute``.  ``ready()`` / ``prefetch()`` let an idle serving loop
    materialize the device results without blocking once the device has
    finished, so the later ``resolve()`` costs nothing.

    ``emitted`` maps rid -> tokens produced this step (populated at
    resolve; consumers fall back to the plan's per-row budgets when a
    rid is absent).  ``padded_t`` is the token width a packed mixed
    step was padded to (None elsewhere)."""

    padded_t: Optional[int] = None

    def __init__(self, executor, arrays, apply_fn, horizon: int = 1):
        self._ex = executor
        self._arrays = tuple(arrays)
        self._apply = apply_fn
        self.horizon = horizon
        self._np: Optional[list] = None
        self.eos: Optional[Dict[int, bool]] = None
        self.emitted: Dict[int, int] = {}
        self.resolved = False

    def ready(self) -> bool:
        """True once every dispatched array has landed (non-blocking)."""
        if self._np is not None:
            return True
        try:
            return all(a.is_ready() for a in self._arrays)
        except AttributeError:      # older jax: no readiness probe
            return False

    def prefetch(self):
        """Materialize the device results on the host.  Every
        materialization counts as a readback; it additionally counts as
        a blocking sync unless the arrays were already ready (the
        serving loop calls this from idle pacing gaps, where it is
        free)."""
        if self._np is None:
            if self._arrays:
                self._ex.host_readbacks += 1
                if not self.ready():
                    self._ex.host_syncs += 1
            self._np = [np.asarray(a) for a in self._arrays]
        return self._np

    def resolve(self) -> Dict[int, bool]:
        if not self.resolved:
            arrays = self.prefetch()
            self.eos = self._apply(arrays, self)
            self.resolved = True
            if self._ex is not None and self._ex._pending is self:
                self._ex._pending = None
        return self.eos


class ImmediateStep:
    """Trivial pending step for executors with nothing in flight (the
    simulator's token oracle, empty plans)."""

    horizon = 1

    def __init__(self, eos: Optional[Dict[int, bool]] = None):
        self.eos = dict(eos or {})
        self.emitted: Dict[int, int] = {}
        self.resolved = False

    def ready(self) -> bool:
        return True

    def prefetch(self):
        return []

    def resolve(self) -> Dict[int, bool]:
        self.resolved = True
        return self.eos


def _prefill_window(req: Request, start: int, take: int):
    """Map an instance-space prefill window to (token chunk, cache
    position).  Normally the identity on ``prompt_tokens``; after a
    preemption-by-recompute the request re-prefills from negative
    ``prefill_pos`` and the true stream is prompt + the output tokens
    generated before eviction, at position ``start + recompute_offset``
    (see Request.recompute_offset)."""
    off = req.recompute_offset
    if not off:
        return req.prompt_tokens[start:start + take], start
    pos = start + off
    stream = list(req.prompt_tokens) + list(req.output_tokens[:off])
    return stream[pos:pos + take], pos


def packable(cfg: ModelConfig) -> bool:
    """True if T-padded packed prefill is token-exact for this config:
    every layer is full-cache global attention (padding KV writes are
    dropped and padded positions are masked by causality).  Ring-buffer
    windows would be overwritten by padding slots, recurrent SSM state
    would advance through padding, and capacity-dropping MoE would route
    padding tokens into expert capacity."""
    return all(b == ATTN for seg in cfg.segments() for b in seg.pattern)


class JaxExecutor:
    """Implements the core.instance.Executor protocol with a real model."""

    #: decode-growth headroom (tokens) reserved beyond the known context
    #: at admission — mirrors Instance._admit_prefill
    HEADROOM = 64

    def __init__(self, cfg: ModelConfig, params, n_slots: int, max_seq: int,
                 eos_id: Optional[int] = None, greedy: bool = True,
                 seed: int = 0, batched: bool = True,
                 t_buckets: Optional[Sequence[int]] = None,
                 temperature: float = 1.0, prefix_cache: bool = False,
                 cache_block_size: int = 16,
                 paged: Optional[bool] = None,
                 hbm_blocks: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 kv_spill_blocks: int = 0):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.greedy = greedy
        self.temperature = temperature
        self.batched = batched
        self.packed = batched and packable(cfg)
        self.t_buckets = (batching.default_t_buckets(max_seq)
                          if t_buckets is None else tuple(sorted(t_buckets)))
        self.slots = SlotTable(n_slots)
        self.positions = np.zeros(n_slots, np.int32)
        self.last_token = np.zeros(n_slots, np.int32)
        self._rng = np.random.default_rng(seed)
        self._base_key = jax.random.PRNGKey(seed)
        self._step = 0
        # prefix-KV reuse: KV at position p depends only on tokens [0, p]
        # iff every layer is full-cache global attention — same gate as
        # T-padding (and as paging).
        self.prefix_cache_enabled = prefix_cache and packable(cfg)
        self.cache_block_size = cache_block_size
        self._donors = PrefixTree(cache_block_size)
        self._claimed: set = set()
        self._preadded: set = set()
        self._deferred_states: dict = {}
        self.prefix_adoptions = 0
        self.prefix_copies = 0
        # async-step pipeline state + observability (test hooks):
        # host_readbacks counts every host<->device result
        # materialization (the horizon acceptance bound is readbacks
        # per generated token <= 1/K); host_syncs counts only the
        # BLOCKING ones (device not yet done when the host asked)
        self._pending: Optional[PendingStep] = None
        self.host_readbacks = 0
        self.host_syncs = 0
        self.horizon_calls = 0
        self.horizon_tokens = 0
        # ---- paged physical cache (default wherever paging is exact) --
        self.paged = (batched and packable(cfg) if paged is None
                      else bool(paged) and batched and packable(cfg))
        if kv_quant not in (None, "int8"):
            raise ValueError(f"unsupported kv_quant: {kv_quant!r}")
        self.kv_quant = kv_quant if self.paged else None
        self.kv: Optional[PagedKVCache] = None
        self.prefix_cache_obj: Optional[PrefixCache] = None
        # True once an Instance drives allocate/extend/free on our
        # allocator (unified bookkeeping) — the executor then only READS
        # owned-block lists; False = executor self-manages (standalone /
        # legacy construction with a separate instance allocator).
        self._external_bookkeeping = False
        if self.paged:
            max_blocks = -(-max_seq // cache_block_size)
            # default pool: dense-equivalent capacity + per-slot growth
            # headroom (admission is still per-block by actual context;
            # benches pass a smaller pool to realize the memory win)
            nb = (hbm_blocks if hbm_blocks is not None else
                  n_slots * (max_blocks
                             + self.HEADROOM // cache_block_size))
            alloc = None
            if self.prefix_cache_enabled:
                self.prefix_cache_obj = PrefixCache(
                    nb, cache_block_size, spill_blocks=kv_spill_blocks)
                alloc = self.prefix_cache_obj.allocator
            self.kv = PagedKVCache(cfg, n_slots, max_seq, nb,
                                   cache_block_size, allocator=alloc,
                                   quant=kv_quant)
            if self.prefix_cache_obj is not None:
                self._bind_spill(self.prefix_cache_obj)
            self.cache = None            # no dense rows: that's the point
        else:
            self.cache = tf.init_cache(cfg, n_slots, max_seq)
        # only the paged path lands a migration by aliasing cached prefix
        # blocks; the dense path ships and scatters the full row, so its
        # transfers must be charged in full (cluster._start_transfer)
        self.prefix_aware_transfer = self.paged

        def _sample_on_device(logits, key):
            if self.greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits.astype(jnp.float32) / self.temperature,
                axis=-1).astype(jnp.int32)

        # ---- reference path (host-side sampling, logits cross) ----
        @jax.jit
        def _decode(params, cache, tokens, pos):
            logits, cache, _ = tf.forward(params, cfg, tokens, pos[:, None],
                                          cache)
            return logits[:, -1], cache

        self._decode = _decode

        @functools.partial(jax.jit, static_argnames=("T",))
        def _prefill_row(params, row_cache, tokens, start, T):
            del T
            positions = start[:, None] + jnp.arange(
                tokens.shape[1], dtype=jnp.int32)[None]
            logits, row_cache, _ = tf.forward(params, cfg, tokens, positions,
                                              row_cache)
            return logits[:, -1], row_cache

        self._prefill_row = _prefill_row

        # ---- batched path (fused sampling, tokens cross) ----
        @functools.partial(jax.jit, donate_argnames=("cache",))
        def _decode_fused(params, cache, tokens, pos, key):
            logits, cache, _ = tf.forward(params, cfg, tokens, pos[:, None],
                                          cache)
            return _sample_on_device(logits[:, -1], key), cache

        self._decode_fused = _decode_fused

        @functools.partial(jax.jit, donate_argnames=("cache",))
        def _prefill_packed(params, cache, tokens, start, valid, slots, key):
            # compile variants keyed on the bucketed (B, T) shape only
            T = tokens.shape[1]
            positions = jnp.minimum(
                start[:, None] + jnp.arange(T, dtype=jnp.int32)[None],
                max_seq - 1)                   # padding must not wrap slots
            rows = jax.tree.map(lambda a: a[:, slots], cache["segments"])
            hidden, new_rows, _ = tf.forward(
                params, cfg, tokens, positions, {"segments": rows},
                compute_logits=False, valid_len=valid)
            # pad rows carry slot == n_slots: scatter drops them on-device
            segs = jax.tree.map(
                lambda a, r: a.at[:, slots].set(r.astype(a.dtype),
                                                mode="drop"),
                cache["segments"], new_rows["segments"])
            last = jnp.take_along_axis(
                hidden, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum("bd,dv->bv", last, params["lm_head"])
            return _sample_on_device(logits, key), {"segments": segs}

        self._prefill_packed = _prefill_packed

        @functools.partial(jax.jit, donate_argnames=("cache",))
        def _prefill_slot(params, cache, tokens, start, slot, key):
            # exact-shape fallback for families where padding is unsafe;
            # the cache row is still gathered/scattered on-device.
            positions = start[:, None] + jnp.arange(
                tokens.shape[1], dtype=jnp.int32)[None]
            row = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                cache["segments"])
            hidden, new_row, _ = tf.forward(
                params, cfg, tokens, positions, {"segments": row},
                compute_logits=False)
            segs = jax.tree.map(
                lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                    a, r.astype(a.dtype), slot, axis=1),
                cache["segments"], new_row["segments"])
            logits = jnp.einsum("bd,dv->bv", hidden[:, -1],
                                params["lm_head"])
            return _sample_on_device(logits, key), {"segments": segs}

        self._prefill_slot = _prefill_slot

        # ---- paged path: ONE fused mixed prefill+decode call ----------
        block_size = cache_block_size

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def _mixed_fused(params, pool, tokens, start, valid, tables, key):
            # compile variants keyed on the bucketed (B, T, NB) shape
            T = tokens.shape[1]
            positions = jnp.minimum(
                start[:, None] + jnp.arange(T, dtype=jnp.int32)[None],
                max_seq - 1)               # padding must not leave range
            hidden, pool, _ = tf.forward(
                params, cfg, tokens, positions, pool,
                compute_logits=False, valid_len=valid,
                block_tables=(tables, block_size))
            last = jnp.take_along_axis(
                hidden, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum("bd,dv->bv", last, params["lm_head"])
            return _sample_on_device(logits, key), pool

        self._mixed_fused = _mixed_fused

        # ---- rowwise-path device sampler (only token ids cross) -------
        @jax.jit
        def _sample_batch(logits, key):
            return _sample_on_device(logits, key)

        self._sample_batch = _sample_batch

        # ---- multi-step decode horizon: K fused steps, one readback ---
        eos_id = self.eos_id

        @functools.partial(jax.jit, static_argnames=("K",),
                           donate_argnames=("pool",))
        def _horizon_paged(params, pool, tok0, pos0, budget, tables,
                           key, K):
            # One lax.scan over K decode steps: sampling feeds the next
            # step on device, rows freeze (done-mask) once they emit EOS
            # or exhaust their per-row budget — frozen rows' KV writes
            # drop (valid_len == 0) and their position/token hold still,
            # so the returned carries are exact per-row final states.
            B = tok0.shape[0]

            def body(carry, s):
                pool, last, pos, emitted, done = carry
                active = (~done) & (emitted < budget)
                step = active.astype(jnp.int32)
                p = jnp.minimum(pos, max_seq - 1)
                hidden, pool, _ = tf.forward(
                    params, cfg, last[:, None], p[:, None], pool,
                    compute_logits=False, valid_len=step,
                    block_tables=(tables, block_size))
                logits = jnp.einsum("bd,dv->bv", hidden[:, 0],
                                    params["lm_head"])
                tok = _sample_on_device(logits, jax.random.fold_in(key, s))
                tok = jnp.where(active, tok, last)
                if eos_id is not None:
                    done = done | (active & (tok == eos_id))
                return (pool, tok, pos + step, emitted + step, done), tok

            init = (pool, tok0, pos0, jnp.zeros_like(pos0),
                    jnp.zeros((B,), bool))
            (pool, last, pos, emitted, done), toks = jax.lax.scan(
                body, init, jnp.arange(K, dtype=jnp.int32))
            return toks, emitted, last, pos, done, pool

        self._horizon_paged = _horizon_paged

        @functools.partial(jax.jit, static_argnames=("K",),
                           donate_argnames=("cache",))
        def _horizon_dense(params, cache, tok0, pos0, budget, key, K):
            # Packed-dense variant over the full slot batch: rows with
            # budget 0 (unscheduled slots, padding) never write — unlike
            # the K=1 dense decode, whose harmless-garbage writes rely
            # on later overwrites that a K-step loop cannot guarantee.
            B = tok0.shape[0]

            def body(carry, s):
                cache, last, pos, emitted, done = carry
                active = (~done) & (emitted < budget)
                step = active.astype(jnp.int32)
                logits, cache, _ = tf.forward(
                    params, cfg, last[:, None], pos[:, None], cache,
                    valid_len=step)
                tok = _sample_on_device(logits[:, -1],
                                        jax.random.fold_in(key, s))
                tok = jnp.where(active, tok, last)
                if eos_id is not None:
                    done = done | (active & (tok == eos_id))
                return (cache, tok, pos + step, emitted + step, done), tok

            init = (cache, tok0, pos0, jnp.zeros_like(pos0),
                    jnp.zeros((B,), bool))
            (cache, last, pos, emitted, done), toks = jax.lax.scan(
                body, init, jnp.arange(K, dtype=jnp.int32))
            return toks, emitted, last, pos, done, cache

        self._horizon_dense = _horizon_dense

        # every jitted entry point, for the recompile gauge below
        self._jitted = [_decode, _prefill_row, _decode_fused,
                        _prefill_packed, _prefill_slot, _mixed_fused,
                        _sample_batch, _horizon_paged, _horizon_dense]

    @staticmethod
    def transient_pools(horizon: int) -> int:
        """Pool-sized temporaries the largest paged program holds beside
        the pool it updates, for sizing the pool: one for a migration's
        block scatter (a new pool is written before the old one is
        freed), which also covers a mixed step; three once the fused
        decode horizon runs (``horizon`` > 1), as XLA copies the pool
        ``_horizon_paged`` carries from one scanned step to the next
        (2.7 pools in ``compiled.memory_analysis()`` for a v5e, at any
        K >= 2; tests/test_tpu_compile.py holds both bounds)."""
        return 3 if horizon > 1 else 1

    def jit_compiles(self) -> int:
        """Total traced-and-compiled variants across this executor's
        jitted entry points (shape buckets x static args).  A steadily
        climbing value under a steady workload is a recompile storm —
        usually a shape-bucketing bug — and shows up here long before
        it shows up in latency percentiles."""
        return sum(fn._cache_size() for fn in self._jitted)

    @property
    def horizon_capable(self) -> bool:
        """True when this executor can fuse K>1 decode steps: the paged
        pool and the packed dense path freeze rows via ``valid_len``,
        which needs full-cache attention everywhere (same gate as
        T-padded packing) — other families stay at K=1."""
        return self.paged or self.packed

    # ------------------------------------------------------------------
    # unified bookkeeping surface (paged mode)
    # ------------------------------------------------------------------
    @property
    def allocator(self):
        """The block allocator whose ids index the physical pool (None on
        the dense paths) — an Instance adopts this so admission and the
        tensors share one source of truth."""
        return self.kv.allocator if self.paged else None

    def use_external_bookkeeping(self):
        """An Instance now drives allocate/extend/free on our allocator;
        the executor only reads owned-block lists from here on."""
        self._external_bookkeeping = True

    def adopt_prefix_cache(self, pc: PrefixCache) -> bool:
        """Bind an instance-owned PrefixCache: its allocator's block ids
        become the pool's physical indices and its radix tree becomes
        the donor index.  Returns False (no rebind) when incompatible —
        the executor then keeps self-managed physical bookkeeping."""
        if not self.paged or pc.block_size != self.cache_block_size:
            return False
        self.prefix_cache_obj = pc
        self.kv.rebind_allocator(pc.allocator)
        self._bind_spill(pc)
        self._external_bookkeeping = True
        return True

    def _bind_spill(self, pc: PrefixCache):
        """Give the prefix cache's host spill tier real tensor legs:
        eviction snapshots a block's pool slice to host RAM, promotion
        scatters it back into whatever block id the allocator hands
        out.  Without this binding the tier still runs (bookkeeping-only
        payloads), which is what the simulator uses."""
        if pc.spill is None:
            return
        pc.bind_tiers(
            fetch_block=lambda bid: jax.tree.map(
                np.asarray, self.kv.extract_blocks([bid])),
            load_block=lambda bid, payload: self.kv.insert_blocks(
                [bid], payload))

    def sync(self):
        """Block until all in-flight cache updates land (benchmarks)."""
        if self.paged:
            jax.block_until_ready(self.kv.pool["segments"])
        else:
            jax.block_until_ready(self.cache["segments"])

    def cache_bytes(self) -> int:
        """Device bytes held by the KV cache (pool or dense rows)."""
        if self.paged:
            return self.kv.pool_bytes()
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.cache["segments"]))

    # ------------------------------------------------------------------
    def _acquire_slot(self, rid: int) -> int:
        """Acquire a free slot, preferring rows that are NOT retained
        prefix donors; whatever row is reused stops being a donor."""
        avoid = set(self._donors.bids()) if self.prefix_cache_enabled else ()
        slot = self.slots.acquire(rid, avoid=avoid)
        self._donors.remove_bid(slot)
        return slot

    def claim_prefix(self, req: Request, max_tokens: int) -> int:
        """Reuse cached KV for the longest donor-resident prefix of
        ``req.prompt_tokens`` (capped at ``max_tokens``, full blocks).

        Adopts the donor row outright when it is free (a finished
        request's retained slot — zero copies), otherwise gathers the
        matched columns from the live donor's row into a fresh slot.
        Acquires the request's slot either way; ``add_request`` then
        skips its own acquisition.  Returns the claimed token count."""
        if not self.prefix_cache_enabled or not req.prompt_tokens:
            return 0
        if self.paged:
            return self._claim_prefix_paged(req, max_tokens)
        bs = self.cache_block_size
        cap = min(max_tokens, len(req.prompt_tokens) - 1,
                  self.max_seq - 1) // bs
        path = self._donors.match(req.prompt_tokens, cap) if cap > 0 else []
        if not path:
            return 0
        donor = path[-1].bid                  # deepest node's row holds
        h = len(path) * bs                    # the whole matched prefix
        if self.slots.is_free(donor):
            self.slots.acquire_slot(req.rid, donor)
            self._donors.remove_bid(donor)
            slot = donor
            self.prefix_adoptions += 1
        else:
            slot = self._acquire_slot(req.rid)
            self.cache = migrate.copy_prefix(self.cache, donor, slot, h)
            self.prefix_copies += 1
        # stale columns >= h are dead: masked by position until prefill/
        # decode overwrites them in order (same argument as zero_row).
        self.positions[slot] = h
        self.last_token[slot] = 0
        self._claimed.add(req.rid)
        return h

    def _claim_prefix_paged(self, req: Request, max_tokens: int) -> int:
        """Paged prefix hit = copy-on-write block-table aliasing: the new
        request takes REFERENCES on the matched blocks (no tensor
        gather, no row adoption special case — live and finished donors
        are identical because blocks, not slots, hold the KV)."""
        pc = self.prefix_cache_obj
        bs = self.cache_block_size
        cap = (min(max_tokens, len(req.prompt_tokens) - 1, self.max_seq - 1)
               // bs * bs)
        hit = min(pc.match_tokens(req.prompt_tokens), cap)
        if hit <= 0:
            return 0
        slot = self._acquire_slot(req.rid)
        if not self._external_bookkeeping:
            total = len(req.prompt_tokens) + self.HEADROOM
            if not pc.acquire(req.rid, req.prompt_tokens, hit, total):
                self.slots.release(req.rid)
                return 0
            self.kv.refresh_row(slot, req.rid)
        else:
            # the Instance's PrefixCache.acquire (same allocator) takes
            # the references; the table row is built at add_request
            self.kv.clear_row(slot)
        self.positions[slot] = hit
        self.last_token[slot] = 0
        self._claimed.add(req.rid)
        self.prefix_adoptions += 1
        return hit

    def add_request(self, req: Request):
        if req.rid in self._preadded:
            # state already inserted by a migration (insert_state)
            self._preadded.discard(req.rid)
            return
        if req.rid in self._deferred_states:
            # memory-full at inject time: the admission gate has now
            # cleared this request — land the stashed migrated blocks
            # (plain allocation; the prefix-aliasing fast path is only
            # taken when the pool had room at inject)
            state = self._deferred_states.pop(req.rid)
            slot = self._acquire_slot(req.rid)
            if not self._external_bookkeeping:
                self.kv.ensure(req.rid, state["pos"] + self.HEADROOM)
            self._land_blocks(req, state, slot)
            return
        if req.rid in self._claimed:
            # slot acquired + prefix KV inherited by claim_prefix;
            # zeroing / re-tabling would wipe it
            self._claimed.discard(req.rid)
            if self.paged:
                # unified bookkeeping: the Instance has taken the block
                # references by now — materialize the table row
                self.kv.refresh_row(self.slots.slot(req.rid), req.rid)
            return
        slot = self._acquire_slot(req.rid)
        if self.paged:
            if not self._external_bookkeeping:
                # recompute_offset: a preempted request re-prefills its
                # whole context (prompt + regenerated output), not just
                # the prompt
                self.kv.ensure(req.rid,
                               max(req.prompt_len + req.recompute_offset,
                                   1) + self.HEADROOM)
            self.kv.refresh_row(slot, req.rid)
        else:
            self.cache = migrate.zero_row(self.cache, slot)
        self.positions[slot] = 0
        if req.prompt_tokens is None:
            req.prompt_tokens = list(
                self._rng.integers(1, self.cfg.vocab_size,
                                   size=req.prompt_len))

    def release(self, req: Request):
        if self.paged:
            # retention is block-level: freeing decrefs, and registered
            # (committed) blocks are RETAINED in the allocator's LRU —
            # no slot-donor bookkeeping needed
            self._claimed.discard(req.rid)
            self._preadded.discard(req.rid)
            self._deferred_states.pop(req.rid, None)
            slot = self.slots.release(req.rid)
            if slot is not None:
                self.kv.clear_row(slot)
            if not self._external_bookkeeping:
                self.kv.allocator.free(req.rid)    # no-op if never held
            return
        # the freed row keeps its donor registration: its prompt KV
        # stays adoptable until the slot is reacquired
        if req.rid in self._claimed and self.slots.has(req.rid):
            # claim never consumed (admission unwound): the row's prefix
            # columns are valid KV — re-register it as a retained donor
            # instead of forfeiting what adoption deregistered
            slot = self.slots.slot(req.rid)
            h = int(self.positions[slot])
            n = h // self.cache_block_size
            if n > 0 and req.prompt_tokens:
                self._donors.insert(
                    req.prompt_tokens[:n * self.cache_block_size],
                    [slot] * n)
        self._claimed.discard(req.rid)
        self.slots.release(req.rid)

    def _register_donor(self, req: Request, slot: int):
        """Prefill complete (or migrated-in state landed): the row now
        holds valid KV for the whole prompt — publish its full blocks to
        the donor index."""
        if not self.prefix_cache_enabled or not req.prompt_tokens:
            return
        if self.paged:
            # blocks ARE the donor currency: publish + retain them in
            # the shared radix tree (idempotent — the Instance commits
            # through the same PrefixCache at prefill completion)
            if (self.prefix_cache_obj is not None
                    and self.kv.allocator.holds(req.rid)):
                self.prefix_cache_obj.commit(req.rid, req.prompt_tokens)
            return
        n = len(req.prompt_tokens) // self.cache_block_size
        if n > 0:
            self._donors.insert(
                req.prompt_tokens[:n * self.cache_block_size], [slot] * n)

    # ------------------------------------------------------------------
    def _row_cache(self, slot: int):
        return {"segments": jax.tree.map(
            lambda a: a[:, slot:slot + 1], self.cache["segments"])}

    def _write_row_cache(self, slot: int, row_cache):
        self.cache = {"segments": jax.tree.map(
            lambda a, r: a.at[:, slot:slot + 1].set(r),
            self.cache["segments"], row_cache["segments"])}

    def _next_key(self):
        key = jax.random.fold_in(self._base_key, self._step)
        self._step += 1
        return key

    # ------------------------------------------------------------------
    def execute(self, plan) -> Dict[int, bool]:
        """Synchronous wrapper: dispatch + immediately resolve."""
        return self.step_async(plan).resolve()

    def step_async(self, plan) -> PendingStep:
        """Dispatch one planned iteration WITHOUT waiting for device
        results.  Host-deterministic bookkeeping (prefill position
        advances, block-table growth) happens now so the serving loop
        may keep scheduling; token-dependent state (output tokens,
        ``last_token``, EOS, donor registration) lands at
        ``resolve()``.  At most one step may be in flight per
        executor."""
        if self._pending is not None and not self._pending.resolved:
            raise RuntimeError(
                "step_async: previous step not resolved — the pipeline "
                "must be flushed (commit the in-flight iteration) first")
        if self.paged:
            step = self._step_paged(plan)
        elif self.batched:
            step = self._step_batched(plan)
        else:
            step = self._step_reference(plan)
        if isinstance(step, PendingStep):
            self._pending = step
        return step

    def abort_step(self, pending=None):
        """Fault path: abandon an in-flight dispatched step without
        resolving it.  The device work is discarded — no tokens are
        applied, no donor registration happens — and the single-step
        pipeline guard is released so the instance can dispatch again
        after recovery."""
        step = pending if pending is not None else self._pending
        if step is not None:
            step.resolved = True
        if self._pending is step:
            self._pending = None

    def on_crash(self):
        """Total HBM loss: forget everything device-side that outlives
        individual requests — slot rows, donor registrations, deferred
        migration payloads.  Per-request frees happened via ``release``
        during evacuation; this drops the residue (and any rows whose
        requests already finished but stayed adoptable)."""
        self.abort_step()
        self._donors = PrefixTree(self.cache_block_size)
        self._claimed.clear()
        self._preadded.clear()
        self._deferred_states.clear()
        for rid in list(self.slots._slot_of):
            slot = self.slots.release(rid)
            if self.paged and slot is not None:
                self.kv.clear_row(slot)
            if self.paged and not self._external_bookkeeping \
                    and self.kv.allocator.holds(rid):
                self.kv.allocator.free(rid)

    # ---- paged hot path: one fused mixed-batch jit call ---------------
    def _step_paged(self, plan) -> PendingStep:
        """Dispatch a whole TaiChi iteration — every prefill chunk AND
        every decode step — as ONE jit call over the block pool.  Decode
        rows ride along as length-1 chunks (token = last sampled token,
        start = row position); per-row valid lengths and block tables
        make the geometry uniform.  Decode-only plans with ``horizon >
        1`` take the K-step fused loop instead."""
        K = getattr(plan, "horizon", 1)
        if K > 1 and not plan.prefill_items and plan.decode_reqs:
            return self._step_horizon_paged(plan, K)
        rows = []   # (req, slot, start, chunk, completes, is_decode)
        if plan.prefill_items:
            for req, start, take, completes in plan.prefill_rows():
                chunk, pos = _prefill_window(req, start, take)
                rows.append((req, self.slots.slot(req.rid), pos,
                             chunk, completes, False))
        for req in plan.decode_reqs:
            slot = self.slots.slot(req.rid)
            # clamp like the jit step does: contexts past max_seq keep
            # rewriting the last position (the dense ring would wrap)
            rows.append((req, slot,
                         min(int(self.positions[slot]), self.max_seq - 1),
                         [int(self.last_token[slot])], False, True))
        if not rows:
            return ImmediateStep()
        table_rows = [
            self.kv.grow_for(slot, req.rid,
                             min(start + len(chunk), self.max_seq),
                             self._external_bookkeeping)
            for req, slot, start, chunk, _, _ in rows]
        packed = batching.pack_mixed(
            [chunk for _, _, _, chunk, _, _ in rows],
            [start for _, _, start, _, _, _ in rows],
            table_rows, self.t_buckets, self.kv.max_blocks,
            self.cache_block_size)
        toks_dev, self.kv.pool = self._mixed_fused(
            self.params, self.kv.pool, jnp.asarray(packed.tokens),
            jnp.asarray(packed.start), jnp.asarray(packed.valid),
            jnp.asarray(packed.tables), self._next_key())
        # position advances are token-independent: land them at dispatch
        # so the next plan (and the decode rows' next dispatch) sees the
        # post-iteration frontier without waiting on the device
        for req, slot, start, chunk, _, is_dec in rows:
            if is_dec:
                self.positions[slot] += 1
            else:
                self.positions[slot] = start + len(chunk)

        def apply(arrays, handle) -> Dict[int, bool]:
            toks = arrays[0]
            eos: Dict[int, bool] = {}
            for i, (req, slot, start, chunk, completes, is_dec) in \
                    enumerate(rows):
                if is_dec:
                    tok = int(toks[i])
                    req.output_tokens.append(tok)
                    self.last_token[slot] = tok
                    handle.emitted[req.rid] = 1
                    if self.eos_id is not None and tok == self.eos_id:
                        eos[req.rid] = True
                    continue
                if completes:
                    tok = int(toks[i])
                    req.output_tokens.append(tok)
                    self.last_token[slot] = tok
                    self._register_donor(req, slot)
                    if self.eos_id is not None and tok == self.eos_id:
                        eos[req.rid] = True
            return eos

        step = PendingStep(self, (toks_dev,), apply)
        step.padded_t = packed.tokens.shape[1]
        return step

    def _step_horizon_paged(self, plan, K: int) -> PendingStep:
        """K fused decode steps over the block pool: grow every row's
        table to its end-of-horizon frontier, dispatch one scan, read
        back once."""
        budgets = plan.decode_budgets or [1] * len(plan.decode_reqs)
        rows = []   # (req, slot, pos, budget)
        for req, b in zip(plan.decode_reqs, budgets):
            slot = self.slots.slot(req.rid)
            pos = int(self.positions[slot])
            self.kv.grow_for(slot, req.rid, min(pos + b, self.max_seq),
                             self._external_bookkeeping)
            rows.append((req, slot, pos, b))
        packed = batching.pack_decode(
            [int(self.last_token[s]) for _, s, _, _ in rows],
            [p for _, _, p, _ in rows],
            [b for _, _, _, b in rows],
            [self.kv.tables[s] for _, s, _, _ in rows],
            self.kv.max_blocks, self.cache_block_size)
        toks, emitted, last, pos, done, self.kv.pool = self._horizon_paged(
            self.params, self.kv.pool, jnp.asarray(packed.tokens),
            jnp.asarray(packed.start), jnp.asarray(packed.budget),
            jnp.asarray(packed.tables), self._next_key(), K)
        self.horizon_calls += 1

        def apply(arrays, handle) -> Dict[int, bool]:
            toks_np, em_np, last_np, pos_np, done_np = arrays
            eos: Dict[int, bool] = {}
            for i, (req, slot, _, _) in enumerate(rows):
                n = int(em_np[i])
                handle.emitted[req.rid] = n
                req.output_tokens.extend(
                    int(t) for t in toks_np[:n, i])
                self.last_token[slot] = int(last_np[i])
                self.positions[slot] = int(pos_np[i])
                self.horizon_tokens += n
                if bool(done_np[i]):
                    eos[req.rid] = True
            return eos

        return PendingStep(self, (toks, emitted, last, pos, done),
                           apply, K)

    # ---- batched hot path --------------------------------------------
    def _step_batched(self, plan) -> PendingStep:
        K = getattr(plan, "horizon", 1)
        if K > 1 and self.packed and not plan.prefill_items \
                and plan.decode_reqs:
            return self._step_horizon_dense(plan, K)
        arrays: list = []
        appliers: list = []
        if plan.prefill_items:
            rows = plan.prefill_rows()
            if self.packed:
                self._dispatch_prefill_packed(rows, arrays, appliers)
            else:
                self._dispatch_prefill_slots(rows, arrays, appliers)
        if plan.decode_reqs:
            # the prefill dispatches above already advanced positions
            # for their rows, so this full-batch call's harmless writes
            # to non-decode slots land at post-chunk frontiers — exactly
            # where the synchronous path put them
            toks_dev, self.cache = self._decode_fused(
                self.params, self.cache,
                jnp.asarray(self.last_token[:, None]),
                jnp.asarray(self.positions), self._next_key())
            arrays.append(toks_dev)
            decode_reqs = list(plan.decode_reqs)
            for req in decode_reqs:
                self.positions[self.slots.slot(req.rid)] += 1

            def apply_decode(toks, handle, eos):
                for req in decode_reqs:
                    slot = self.slots.slot(req.rid)
                    tok = int(toks[slot])
                    req.output_tokens.append(tok)
                    self.last_token[slot] = tok
                    handle.emitted[req.rid] = 1
                    if self.eos_id is not None and tok == self.eos_id:
                        eos[req.rid] = True

            appliers.append(apply_decode)
        if not arrays:
            return ImmediateStep()

        def apply(np_arrays, handle) -> Dict[int, bool]:
            eos: Dict[int, bool] = {}
            for arr, fn in zip(np_arrays, appliers):
                fn(arr, handle, eos)
            return eos

        return PendingStep(self, arrays, apply)

    def _step_horizon_dense(self, plan, K: int) -> PendingStep:
        """K fused decode steps over the slot-contiguous dense cache:
        the full slot batch rides through the scan, with per-slot
        budgets freezing everything that is not a scheduled decode
        row."""
        budgets = plan.decode_budgets or [1] * len(plan.decode_reqs)
        slot_budget = np.zeros(self.n_slots, np.int32)
        rows = []   # (req, slot)
        for req, b in zip(plan.decode_reqs, budgets):
            slot = self.slots.slot(req.rid)
            slot_budget[slot] = b
            rows.append((req, slot))
        toks, emitted, last, pos, done, self.cache = self._horizon_dense(
            self.params, self.cache, jnp.asarray(self.last_token),
            jnp.asarray(self.positions), jnp.asarray(slot_budget),
            self._next_key(), K)
        self.horizon_calls += 1

        def apply(arrays, handle) -> Dict[int, bool]:
            toks_np, em_np, last_np, pos_np, done_np = arrays
            eos: Dict[int, bool] = {}
            # update ONLY the scheduled rows' slots: other slots may
            # have been written host-side (e.g. a migration landing)
            # while this step was in flight, and frozen rows carried
            # their inputs through unchanged anyway
            for req, slot in rows:
                n = int(em_np[slot])
                handle.emitted[req.rid] = n
                req.output_tokens.extend(
                    int(t) for t in toks_np[:n, slot])
                self.last_token[slot] = int(last_np[slot])
                self.positions[slot] = int(pos_np[slot])
                self.horizon_tokens += n
                if bool(done_np[slot]):
                    eos[req.rid] = True
            return eos

        return PendingStep(self, (toks, emitted, last, pos, done),
                           apply, K)

    def _dispatch_prefill_packed(self, rows, arrays, appliers):
        windows = [_prefill_window(req, start, take)
                   for req, start, take, _ in rows]
        chunks = [c for c, _ in windows]
        row_slots = self.slots.slots_of([req.rid for req, _, _, _ in rows])
        packed = batching.pack_prefill(
            chunks, [pos for _, pos in windows], row_slots,
            self.n_slots, self.t_buckets)
        toks_dev, self.cache = self._prefill_packed(
            self.params, self.cache, packed.tokens, packed.start,
            packed.valid, packed.slots, self._next_key())
        arrays.append(toks_dev)
        for i, (req, start, take, completes) in enumerate(rows):
            self.positions[row_slots[i]] = windows[i][1] + take

        def apply_prefill(toks, handle, eos):
            for i, (req, start, take, completes) in enumerate(rows):
                if not completes:
                    continue
                slot = row_slots[i]
                tok = int(toks[i])
                req.output_tokens.append(tok)
                self.last_token[slot] = tok
                self._register_donor(req, slot)
                if self.eos_id is not None and tok == self.eos_id:
                    eos[req.rid] = True

        appliers.append(apply_prefill)

    def _dispatch_prefill_slots(self, rows, arrays, appliers):
        for req, start, take, completes in rows:
            slot = self.slots.slot(req.rid)
            tokens, pos = _prefill_window(req, start, take)
            chunk = np.asarray(tokens, np.int32)[None]
            tok_dev, self.cache = self._prefill_slot(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.full((1,), pos, jnp.int32),
                jnp.int32(slot), self._next_key())
            self.positions[slot] = pos + take
            arrays.append(tok_dev)

            def apply_row(toks, handle, eos, req=req, slot=slot,
                          completes=completes):
                if not completes:
                    return
                tok = int(toks[0])
                req.output_tokens.append(tok)
                self.last_token[slot] = tok
                self._register_donor(req, slot)
                if self.eos_id is not None and tok == self.eos_id:
                    eos[req.rid] = True

            appliers.append(apply_row)

    # ---- row-wise reference path (token-exact oracle) ----------------
    def _step_reference(self, plan) -> PendingStep:
        """The oracle keeps its simple one-call-per-row structure; it is
        wrapped lazily so ``step_async`` has a uniform surface (compute
        runs at resolve — there is nothing worth overlapping here)."""
        return PendingStep(
            self, (), lambda arrays, handle: self._execute_reference(plan))

    def _execute_reference(self, plan) -> Dict[int, bool]:
        eos: Dict[int, bool] = {}
        # --- chunked prefill (row-wise, exact shapes) ---
        for req, take in plan.prefill_items:
            slot = self.slots.slot(req.rid)
            tokens, pos = _prefill_window(req, req.prefill_pos, take)
            chunk = np.asarray(tokens, np.int32)[None]
            start = jnp.full((1,), pos, jnp.int32)
            last, row_cache = self._prefill_row(
                self.params, self._row_cache(slot), jnp.asarray(chunk),
                start, T=take)
            self._write_row_cache(slot, row_cache)
            self.positions[slot] = pos + take
            if take == req.prefill_remaining:
                # the sampled first token is NOT yet in the cache; it is
                # written when fed to the next decode step at position
                # == prompt_len (positions[slot] already points there).
                # Sampling happens on device — only the token id crosses.
                tok_dev = self._sample_batch(last, self._next_key())
                self.host_readbacks += 1
                self.host_syncs += 1
                tok = int(np.asarray(tok_dev)[0])
                req.output_tokens.append(tok)
                self.last_token[slot] = tok
                self._register_donor(req, slot)
                if self.eos_id is not None and tok == self.eos_id:
                    eos[req.rid] = True
        # --- decode (full slot batch, one call) ---
        if plan.decode_reqs:
            tokens = jnp.asarray(self.last_token[:, None])
            pos = jnp.asarray(self.positions)
            logits, self.cache = self._decode(self.params, self.cache,
                                              tokens, pos)
            toks = np.asarray(self._sample_batch(logits, self._next_key()))
            self.host_readbacks += 1
            self.host_syncs += 1
            active = [(r, self.slots.slot(r.rid)) for r in plan.decode_reqs]
            for req, slot in active:
                tok = int(toks[slot])
                req.output_tokens.append(tok)
                self.last_token[slot] = tok
                self.positions[slot] += 1
                if self.eos_id is not None and tok == self.eos_id:
                    eos[req.rid] = True
        return eos

    # ------------------------------------------------------------------
    def extract_state(self, req: Request):
        if self._pending is not None and not self._pending.resolved:
            # an eject mid-horizon would read post-horizon tensors
            # against pre-horizon host bookkeeping — the scheduler must
            # commit (flush) the in-flight iteration before migrating
            raise RuntimeError(
                f"extract_state({req.rid}): an async step is in flight; "
                "resolve it (commit the iteration) before ejecting")
        slot = self.slots.slot(req.rid)
        if self.paged:
            # ship only the blocks actually covering the written context
            # (growth headroom stays home)
            ctx = int(self.positions[slot])
            n = self.kv.blocks_for(max(ctx, 1))
            bids = self.kv.allocator.owned(req.rid)[:n]
            return {"paged_blocks": self.kv.extract_blocks(bids),
                    "n_blocks": len(bids), "pos": ctx,
                    "last_token": int(self.last_token[slot]),
                    "prompt_tokens": list(req.prompt_tokens or ()),
                    "kv_format": self.kv_quant or "fp"}
        row = migrate.extract_row(self.cache, slot)
        return {"row": row, "pos": int(self.positions[slot]),
                "last_token": int(self.last_token[slot])}

    def insert_state(self, req: Request, state):
        if self.paged:
            if not isinstance(state, dict) or "paged_blocks" not in state:
                raise MigrationFormatError(
                    f"request {req.rid}: migrated state is in 'dense' "
                    "row format but the destination executor is 'paged' "
                    "— dense<->paged cross-migration is unsupported; "
                    "migrate between like engines")
            return self._insert_state_paged(req, state)
        if not isinstance(state, dict) or "row" not in state:
            raise MigrationFormatError(
                f"request {req.rid}: migrated state is in 'paged' block "
                "format but the destination executor is 'dense' — "
                "dense<->paged cross-migration is unsupported; migrate "
                "between like engines")
        slot = self._acquire_slot(req.rid)
        self.cache = migrate.insert_row(self.cache, state["row"], slot)
        self.positions[slot] = state["pos"]
        self.last_token[slot] = state["last_token"]
        # re-acquired below by add_request semantics: mark as pre-added
        self._preadded.add(req.rid)
        # donor re-registration after migration-in: the landed row holds
        # valid KV for the full prompt — make it adoptable here too
        self._register_donor(req, slot)

    def _insert_state_paged(self, req: Request, state):
        """Land migrated blocks: alias whatever prefix the destination
        already caches (those shipped blocks are discarded), scatter
        only the non-shared suffix, and republish the prompt blocks to
        this instance's donor index.

        When the pool is memory-full the landing is DEFERRED instead of
        raising: the state is stashed and materialized by add_request
        once the instance's admission gate (can_allocate in
        _try_admit_pending) lets the request through — same graceful
        queueing as the dense path's allocation-at-admission contract."""
        fmt = state.get("kv_format", "fp")
        want = self.kv_quant or "fp"
        if fmt != want:
            # int8 blocks carry scale leaves fp pools don't have (and
            # vice versa) — a blind scatter would silently misinterpret
            # the payload; migrate between like-quantized engines
            raise MigrationFormatError(
                f"request {req.rid}: migrated KV is {fmt!r} but the "
                f"destination pool is {want!r} — cross-format "
                "migration is unsupported")
        prompt = req.prompt_tokens or state.get("prompt_tokens") or []
        shared_bids: list = []
        if self.prefix_cache_enabled and self.prefix_cache_obj and prompt:
            pc = self.prefix_cache_obj
            hit = min(pc.match_tokens(prompt),
                      (state["n_blocks"] - 1) * self.cache_block_size)
            if hit > 0:
                shared_bids = pc.matched_bids(prompt, hit)
        alloc = self.kv.allocator
        total = state["pos"] + self.HEADROOM
        if not alloc.can_allocate(total, shared_bids):
            self._deferred_states[req.rid] = state
            return
        slot = self._acquire_slot(req.rid)
        alloc.allocate(req.rid, total, shared=shared_bids)
        self._land_blocks(req, state, slot, len(shared_bids))
        self._preadded.add(req.rid)

    def _land_blocks(self, req: Request, state, slot: int,
                     skip_blocks: int = 0):
        self.kv.refresh_row(slot, req.rid)
        self.kv.insert_blocks(
            self.kv.allocator.owned(req.rid)[:state["n_blocks"]],
            state["paged_blocks"], skip_blocks=skip_blocks)
        self.positions[slot] = state["pos"]
        self.last_token[slot] = state["last_token"]
        # donor re-registration after migration-in (open ROADMAP item):
        # republish the full prompt blocks so the migrated context is
        # adoptable on this instance
        self._register_donor(req, slot)

    def export_request_blocks(self, req: Request, indices):
        """Host copies of the blocks at the given indices of ``req``'s
        owned block run (warm-recovery checkpoint materialization).
        Side-effect free — no refcounts, no LRU touches, no slot state;
        each payload carries its quantization format so the restore
        path can refuse a mismatched destination pool.  None when this
        executor cannot export (dense path, request not held, or an
        async step still in flight — mid-flight tensors are torn)."""
        if not self.paged or not self.kv.allocator.holds(req.rid):
            return None
        if self._pending is not None and not self._pending.resolved:
            return None
        bids = self.kv.allocator.owned(req.rid)
        fmt = self.kv_quant or "fp"
        out = {}
        for i in indices:
            if 0 <= i < len(bids):
                out[i] = {"fmt": fmt, "kv": jax.tree.map(
                    np.asarray, self.kv.extract_blocks([bids[i]]))}
        return out

    # ------------------------------------------------------------------
    # hot-prefix replication (block-granular, no request attached)
    # ------------------------------------------------------------------
    def export_prefix_blocks(self, tokens: Sequence[int]):
        """Gather the cached pool blocks covering the longest resident
        full-block prefix of ``tokens``, for replication to a peer
        instance.  Side-effect free (no refcounts, no LRU touch) and
        deliberately NOT capped like match_tokens: a hot path's last
        full block is worth shipping even when a future request would
        still owe one prefill token."""
        pc = self.prefix_cache_obj
        if not self.paged or pc is None:
            return None
        n = len(tokens) // self.cache_block_size
        path = pc.tree.match(tokens, n, touch=False)
        if not path:
            return None
        bids = [nd.bid for nd in path]
        return {"paged_blocks": self.kv.extract_blocks(bids),
                "n_blocks": len(bids),
                "tokens": list(tokens[:len(bids) * self.cache_block_size]),
                "kv_format": self.kv_quant or "fp"}

    def import_prefix_blocks(self, state) -> int:
        """Land replicated prefix blocks into this pool and publish them
        to the donor tree.  Returns blocks newly admitted (0 when the
        prefix is already resident, nothing fit below the free
        watermark, or the payload carries no tensors — a bookkeeping-
        only payload must never alias garbage pool contents)."""
        pc = self.prefix_cache_obj
        if not self.paged or pc is None:
            return 0
        fmt = state.get("kv_format", "fp")
        want = self.kv_quant or "fp"
        if fmt != want:
            raise MigrationFormatError(
                f"replicated KV is {fmt!r} but the destination pool is "
                f"{want!r} — cross-format replication is unsupported")
        if state.get("paged_blocks") is None:
            return 0
        res = pc.admit_replica(state["tokens"], state["n_blocks"])
        if res is None:
            return 0
        skip, bids = res
        self.kv.insert_blocks(bids, state["paged_blocks"],
                              skip_blocks=skip)
        return len(bids) - skip

    def migration_bytes(self, req: Request) -> int:
        slot = self.slots.slot(req.rid)
        if self.paged:
            n = self.kv.blocks_for(max(int(self.positions[slot]), 1))
            return n * self.cache_block_size * self.kv.token_bytes()
        return migrate.row_bytes(migrate.extract_row(self.cache, slot))


class SimExecutor:
    """Token oracle for the event-driven simulator: no tensors, no
    compute.  EOS arrives when the request's hidden output length is
    reached (the instance observes it only as done())."""

    #: the simulator models the paper system, where migrations ship only
    #: the non-shared suffix when the destination caches the prefix
    prefix_aware_transfer = True

    #: no tensors exist: a warm restore needs only the allocator/slot
    #: bookkeeping (the Instance may resume a request at a checkpointed
    #: position without landing KV — on a real engine that would decode
    #: garbage, so the restore path gates on this attribute)
    bookkeeping_only = True

    def execute(self, plan) -> Dict[int, bool]:
        return {}

    def step_async(self, plan) -> ImmediateStep:
        """Nothing computes, so nothing is ever in flight — but exposing
        the async surface lets the serving loop run its dispatch/commit
        pipeline (and the horizon timing model) deterministically in
        simulation."""
        return ImmediateStep()

    def add_request(self, req: Request):
        pass

    def claim_prefix(self, req: Request, max_tokens: int) -> int:
        """No physical rows to gather — the instance-level block cache
        is the full model of HBM retention in simulation."""
        return max_tokens

    def release(self, req: Request):
        pass

    def extract_state(self, req: Request):
        return None

    def insert_state(self, req: Request, state):
        pass
