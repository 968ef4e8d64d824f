"""Continuous-batching serve engine with SmartConf-governed admission.

This is the framework's HB3813/HB6728 (paper §6.2, Fig. 6/8): two PerfConfs
share the hard ``hbm_bytes`` constraint —

  * ``serve.max_queue_tokens``  (indirect; deputy = tokens waiting in the
    admission queue) — a larger queue absorbs request bursts but queued
    prompts hold host/device memory;
  * ``serve.kv_block_budget``   (indirect; deputy = live KV blocks) — more
    resident sequences increase decode batch efficiency but eat HBM.

Both are ``super_hard`` on the same metric, so their controllers split the
error via the §5.4 interaction factor (N = 2).  A third, soft PerfConf
``serve.prefill_chunk_tokens`` bounds decode-latency interference from long
prefills (HB2149-style trade-off) by capping how many prompt tokens one
prefill call may process before decode runs again.

Hot path (one `tick`):
  admission -> scheduling (slot + KV allocation) -> model compute:
  **unified** (packed mode: ONE ``step_packed`` dispatch carrying prefill
  chunks AND every running slot's decode token as a length-1 segment) or
  **split** (bucketed/legacy: one prefill call + one fused decode step) ->
  completion/free -> controller updates.

Hot-path design (the serving-perf tentpole):
  * **Unified prefill+decode ticks** (``prefill_mode="packed"``, the
    default for every text arch) — each tick fills a single
    ``[1, width]`` ragged stream with prefill chunks from as many requests
    as fit under the ``serve.prefill_chunk_tokens`` budget PLUS one
    length-1 decode segment per running slot, all in admission order: the
    steady-state tick costs ONE compiled dispatch instead of two.
    (Decode-only ticks — the drain tail, where the split path never paid a
    second dispatch — route to the specialized decode program: still one
    dispatch, at that program's exact cost.)
    Per-token ``slot_id`` / ``position`` arrays plus per-slot segment
    boundaries carry the ragged structure; attention masks by segment id
    so no request sees another (a decode segment sees exactly its own
    history — the decode-attention predicate), and K/V scatter routes each
    token to its slot's dense ring row or paged block (``step_packed``).
    Sampling happens for every segment that completed a row this tick —
    prefill-finishers and decoders alike — with a ``_gen_buf`` scatter by
    slot.  Decode tokens are mandatory riders (the split path decodes
    every running slot each tick, so parity demands the same here); they
    count against the literal token budget, with prefill floored at one
    token per tick so it can never be fully starved.  The knob is
    therefore the *literal* per-tick token budget, the jit cache shrinks
    to one packed shape under saturated demand (drain-tail ticks bucket
    down, so worst case O(log cache_len) vs the bucketed path's
    per-(bucket, slot-count) spread), and ``pad_fraction`` — dead lanes
    per issued prefill lane — is observable per tick, so the SmartConf
    deputy for the knob tracks the work actually done.  Attention runs on
    the fused ``kernels/segment_attention`` family (online softmax over
    K/V tiles, predicate fused into the tile mask), so the packed stream
    never materializes the ``[P, B*N]`` score matrix that used to cap
    ``packed_width``.
  * **Length-bucketed prefill** (``prefill_mode="bucketed"``) — prompt
    chunks are padded to power-of-two buckets and batched across slots
    into a single ``prefill_chunk`` call at engine batch width, so the jit
    cache holds one entry per *bucket* instead of one per distinct prompt
    length.  Kept as the comparison baseline: its per-tick token cost is
    quantized to ``bucket x n_slots``, which is exactly the deputy drift
    packing removes.
  * **Real chunked prefill** — at most ``prefill_chunk`` prompt tokens are
    prefilled per tick; long prompts spread over several ticks interleaved
    with decode, so the SmartConf soft knob actuates observable behavior.
  * **Cache donation / in-place writes** — prefill and decode steps donate
    the fused KV cache (and the device-side token buffers), and chunked
    prefill scatters K/V straight into the donated cache; the legacy
    one-shot path merges per slot via ``dynamic_update_slice`` rather than
    copying the whole tree.
  * **Deferred host sync** — sampled tokens stay on device between ticks
    (token ring in ``_gen_buf``); the host reads a sequence back exactly
    once, at its completion boundary.

KV residency (the paged-KV tentpole):
  * **Paged KV cache** — for attention-only archs the per-slot dense
    ``[max_batch, cache_len]`` cache is replaced by per-layer physical
    block stores ``[capacity, Kv, T, D]`` addressed through per-sequence
    block tables (``serve/paging.py`` free-list allocator +
    ``kernels/paged_attention`` Pallas decode kernel).  Admission reserves
    table entries only — no cache-tree copy; ``serve.kv_block_budget``
    bounds the *physical* store, so budget cuts below occupancy preempt the
    lowest-priority sequence back to the queue (recompute on re-admission)
    and shrink the store arrays, actually releasing HBM rather than only
    moving the ledger.  Paged KV covers every arch whose blocks are all
    attention kinds — including MoE (only attention K/V is paged); archs
    with recurrent blocks (O(1) state, nothing to page) and the modality
    frontends keep the dense path (``kv_mode="auto"``).

Universal chunked prefill: every text-only family serves the packed (and
bucketed) path — attention kinds via position/segment masking, recurrent
kinds (rwkv6/rglru) by threading scan state across chunk boundaries through
the state-in/state-out kernel variants, and MoE via pad-aware router
capacity — so ``serve.prefill_chunk_tokens`` actuates uniformly across the
zoo.  Only the vision/encoder-decoder frontends (unpadded modality
prefixes) keep the exact one-shot path under ``prefill_mode="auto"``, and
that fallback warns loudly; requesting ``packed`` or ``bucketed`` for them
raises.  ``REPRO_PREFILL_MODE`` overrides what ``auto`` resolves to (the CI
matrix leg), and ``one_shot`` is accepted as an alias for ``legacy``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import os
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig
from repro.core import (ControllerModel, GoalSpec, Guardrails, HBMAccountant,
                        LatencySensor, SmartConfIndirect, SmartConf,
                        ThroughputSensor)
from repro.core.smartconf import ConfRegistry
from repro.core.telemetry import Telemetry, Tracer
from repro.distributed.fault_tolerance import PreemptionHandler
from repro.distributed.sharding import SERVE_TP_RULES, use_mesh
from repro.kernels.decode_attention import padded_cache_len
from repro.kernels.segment_attention.segment_attention import (
    live_block_ranges, live_blocks)
from repro.models import zoo
from repro.models.blocks import ATTN_KINDS
from .block_store import CacheShardingPlan, build_serve_mesh
from .kv_cache import KVBlockPool, QUEUE_TOKEN_BYTES
from .options import ServeOptions, SLOSpec
from .paging import PagedKVAllocator
from .prefix_cache import PrefixCache
from .speculation import NGramDrafter

__all__ = ["Admission", "Request", "RejectReason", "SLOSpec", "ServeEngine",
           "ServeOptions", "TICK_STATS_KEYS"]

_MIN_BUCKET = 16

# The frozen TickStats schema: every dict `tick()` / `_stats()` returns has
# exactly these keys, in exactly this order.  Telemetry, the open-loop
# driver's cost model, the benches, and the CI JSON gates all consume this
# dict — a key rename or reorder is a cross-layer breaking change, so the
# schema is explicit and regression-tested (tests/test_telemetry.py)
# instead of incidentally stable.  Add new keys at the end.
TICK_STATS_KEYS: tuple[str, ...] = (
    "tick",                     # engine tick ordinal (ticks_run at entry)
    "queued", "waiting", "running", "finished", "hbm", "tokens",
    "pad_fraction", "packed_segments", "dispatches",
    "prefill_tokens", "prefill_issued_tokens", "decode_tokens",
    "kv_used_blocks", "kv_budget_blocks", "kv_capacity_blocks",
    "kv_over_budget", "kv_frag_tokens",
    "preemptions", "admit_tier_max", "rejected", "draining",
    "slo_good_tokens", "slo_miss_tokens",
    # appended (prefix cache PR): reclaimed prefill tokens this tick, the
    # radix tree's held blocks, and the live cache share of the budget
    "prefix_hit_tokens", "prefix_cache_blocks", "kv_cache_share",
    # appended (speculative-decode PR): live draft depth, this tick's
    # accept rate, draft verify lanes issued (the stream width speculation
    # added), and decoding slots (the per-tick KV-read unit now that one
    # slot can emit several tokens per dispatch)
    "spec_depth", "accept_rate", "spec_lanes", "decode_slots",
    # appended (mesh-serving PR): model-axis shard count of this engine's
    # tick dispatch (1 = single-device) — lets the router and the CI gates
    # tell a TP tick from a plain one without poking engine internals
    "tp_shards",
    # appended (engine phase spans): seconds of the tick's serve.tick span
    # outside its serve.wait and serve.fetch spans — the host's part
    "host_s",
    # appended (live paged walk): KV blocks the packed tick's paged
    # segment attention visits per query head, mean over attention layers
    # (0 on ticks that do not run it)
    "attn_kv_blocks",
)

# phases whose span is the host waiting on the device: a tick's host_s
# leaves them out
_WAIT_PHASES = ("wait", "fetch")

# rejections in one tick at or past this count dump the flight recorder:
# a typed-rejection storm is exactly the "why did the engine shed all of
# that" moment the last-N-ticks sensor ring exists to answer
_REJECT_STORM_PER_TICK = 3


class RejectReason(str, enum.Enum):
    """Why the engine refused (or gave up on) a request — the typed reason
    the overload/robustness contract promises instead of a crash or a
    silent scheduler spin.  See serve/README.md for the full semantics."""

    EMPTY_PROMPT = "empty_prompt"          # nothing to prefill
    PROMPT_TOO_LONG = "prompt_too_long"    # prompt+new tokens exceed cache_len
    KV_FOOTPRINT = "kv_footprint"          # KV need exceeds the block budget
    DEADLINE_EXPIRED = "deadline_expired"  # deadline passed while waiting
    BROWNOUT_SHED = "brownout_shed"        # browned out past the TTFT SLO
    DRAINING = "draining"                  # worker preemption in progress

    def __str__(self) -> str:              # counters key on the short name
        return self.value


@dataclasses.dataclass(frozen=True)
class Admission:
    """Typed result of :meth:`ServeEngine.submit`.

    Callers used to null-check a bare ``RejectReason | None``; this carries
    the decision (``accepted`` — also the truth value), the typed
    ``reason`` when refused, and two advisory facts about the accepted
    request: ``prefix_hit_tokens`` (prompt tokens the radix cache could
    currently serve — the actual grant happens at schedule time, so this
    is a hint, not a promise) and ``footprint_blocks`` (KV blocks the
    request will need resident)."""

    accepted: bool
    reason: RejectReason | None = None
    prefix_hit_tokens: int = 0
    footprint_blocks: int = 0

    def __bool__(self) -> bool:
        return self.accepted


def _one_shot_reason(cfg: ArchConfig) -> str:
    """Why this arch cannot leave the one-shot prefill path (the only
    remaining families after universal chunked prefill are the modality
    frontends, whose unpadded prefixes have no chunk representation)."""
    if cfg.encoder_decoder:
        return "the encoder-decoder frontend"
    if cfg.frontend == "vision":
        return "the vision-prefix frontend"
    return f"block pattern {cfg.block_pattern}"


def _bucket(n: int) -> int:
    """Smallest power-of-two >= n (floored at _MIN_BUCKET): the padded
    prefill width, so the jit cache is keyed by O(log max_len) shapes."""
    return max(_MIN_BUCKET, 1 << (max(1, n) - 1).bit_length())


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int
    tier: int = 0               # priority tier; 0 = highest, shed last
    deadline_s: float | None = None  # completion deadline (from submit)
    prompt_bytes: int = 0
    submitted_t: float = 0.0
    queued_t: float | None = None    # first admission past the tier gate
    first_token_t: float | None = None
    done_t: float | None = None
    generated: list = dataclasses.field(default_factory=list)
    slot: int | None = None
    prefilled: int = 0          # prompt tokens already prefilled (chunking)
    prefill_chunks: int = 0     # chunk calls this request's prefill spanned
    gen_count: int = 0          # tokens generated (device-resident until done)
    admit_seq: int = 0          # scheduling order; highest = first preempted
    preempted: int = 0          # times this request was kicked back to queue
    reject_reason: RejectReason | None = None
    slo_ok: bool | None = None  # set at completion: counted toward goodput?
    lease: object | None = None  # KVLease/DenseKVLease while scheduled
    prefix_hit: int = 0         # prompt tokens served from the radix cache


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *,
                 options: ServeOptions | None = None,
                 registry: ConfRegistry | None = None,
                 preemption: PreemptionHandler | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 telemetry: Telemetry | None = None, **kwargs) -> None:
        # config lives in ServeOptions (the typed bag; resolve() is the one
        # env-reading point).  The legacy keyword surface still works: bare
        # kwargs build a ServeOptions here, so ServeEngine(cfg, params,
        # max_batch=8, kv_mode="paged") and ServeEngine(cfg, params,
        # options=ServeOptions(...)) are the same engine.
        if options is None:
            options = ServeOptions(**kwargs)
        elif kwargs:
            raise TypeError(
                "pass configuration via options=ServeOptions(...) OR bare "
                f"kwargs, not both (got {sorted(kwargs)})")
        opts = self.options = options.resolve()
        max_batch = opts.max_batch
        hbm_budget_bytes = opts.hbm_budget_bytes
        block_tokens = opts.block_tokens
        enable_smartconf = opts.enable_smartconf
        latency_goal_s = opts.latency_goal_s
        prefill_mode, kv_mode = opts.prefill_mode, opts.kv_mode
        slo, num_tiers = opts.slo, opts.num_tiers
        admit_tier_max = opts.admit_tier_max
        env_forced = opts.prefill_env_forced
        if telemetry is None:
            telemetry = opts.telemetry

        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        # dense decode tiles the KV axis by block_kv: a cache_len that is
        # not a tile multiple would re-pad K/V with jnp.pad on every decode
        # call, so round the allocation up once here instead
        self.cache_len = cache_len = padded_cache_len(opts.cache_len)
        self.clock = clock

        if prefill_mode not in ("auto", "packed", "bucketed", "legacy"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if (prefill_mode in ("packed", "bucketed")
                and not zoo.supports_chunked_prefill(cfg)):
            if not env_forced:
                raise ValueError(
                    f"{cfg.name}: {_one_shot_reason(cfg)} cannot serve "
                    f"{prefill_mode} (chunked) prefill; only "
                    "prefill_mode='legacy' (one-shot) is available for this "
                    "family")
            prefill_mode = "auto"
        if prefill_mode == "auto":
            if zoo.supports_chunked_prefill(cfg):
                prefill_mode = "packed"
            else:
                # every text-only family (attention, recurrent, MoE) serves
                # the fast path now; falling back is exceptional, so say it
                # loudly — the serve.prefill_chunk_tokens knob will NOT
                # actuate here
                warnings.warn(
                    f"{cfg.name}: {_one_shot_reason(cfg)} keeps the one-shot "
                    "legacy prefill path; serve.prefill_chunk_tokens will "
                    "not actuate for this engine", RuntimeWarning,
                    stacklevel=2)
                prefill_mode = "legacy"
        self.prefill_impl = prefill_mode
        self.fused_prefill = prefill_mode != "legacy"
        # the packed stream's width cap: under saturated demand every tick
        # issues this one shape; the live serve.prefill_chunk_tokens value
        # caps how many real tokens ride in it each tick
        self.packed_width = cache_len

        if kv_mode not in ("auto", "paged", "dense"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        if kv_mode == "paged" and not (zoo.supports_paged_kv(cfg)
                                       and self.fused_prefill):
            raise ValueError(
                f"{cfg.name}: paged KV requires an attention-only block "
                "pattern and chunked prefill (prefill_mode != 'legacy')")
        self.paged = kv_mode == "paged" or (
            kv_mode == "auto" and self.fused_prefill
            and zoo.supports_paged_kv(cfg))

        # ------------------------------------- self-speculative decode
        # rides the unified packed stream: each running slot's segment is
        # [pending token, draft...] and the SAME compiled dispatch that
        # prefills chunks verifies every draft position.  Engines without
        # the packed path cannot speculate; an explicit request raises, the
        # env-forced CI leg silently degrades to k=0.
        spec_depth = int(opts.spec_depth)
        if spec_depth > 0 and self.prefill_impl != "packed":
            if opts.spec_env_forced:
                spec_depth = 0
            else:
                raise ValueError(
                    f"{cfg.name}: speculative decode rides the packed "
                    f"stream; prefill_impl={self.prefill_impl!r} cannot "
                    "serve it")
        self.spec_depth_max = max(1, int(opts.spec_depth_max))
        self.spec_enabled = spec_depth > 0
        self.spec_depth = min(spec_depth, self.spec_depth_max) \
            if self.spec_enabled else 0
        self._spec_len_max = self.spec_depth_max + 1   # 1 pending + k drafts
        self._drafter = NGramDrafter() if self.spec_enabled else None
        self.spec_proposed = 0          # drafted tokens verified, lifetime
        self.spec_accepted = 0          # drafted tokens accepted, lifetime
        self._tick_spec_proposed = 0
        self._tick_spec_accepted = 0
        self._tick_spec_lanes = 0       # draft verify lanes issued
        self._tick_decode_slots = 0
        # windowed accept-rate: the sc_spec controller sensor (accepted,
        # proposed) pairs, token-weighted like the prefix-cache hit window
        self._accept_window: collections.deque[tuple[int, int]] = \
            collections.deque(maxlen=slo.window if slo is not None else 64)

        # --------------------------------- mesh serving (TP packed ticks)
        # the one compiled tick dispatch runs under shard_map on a
        # (data, model) host mesh: attention heads + the block stores' Kv
        # dim shard over `model`, everything else replicates (see
        # block_store.CacheShardingPlan + distributed/collectives TP
        # wrappers).  Infeasible explicit requests raise; env-forced ones
        # (REPRO_SERVE_MESH, the CI leg) degrade to single-device loudly.
        self.mesh = None
        self._cache_plan = None
        if opts.mesh is not None:
            self.mesh = build_serve_mesh(
                opts.mesh, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                prefill_impl=self.prefill_impl,
                env_forced=opts.mesh_env_forced)
        self.tp_shards = (int(self.mesh.shape["model"])
                          if self.mesh is not None else 1)
        if self.mesh is not None:
            # every device of the mesh holds the whole weight tree (only
            # attention heads shard, inside the tick); a tree that is
            # already placed so is not copied
            self.params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))

        self.accountant = HBMAccountant(budget_bytes=hbm_budget_bytes)
        weight_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                           for x in jax.tree.leaves(params))
        self.accountant.set("weights", int(weight_bytes))

        self.blocks_per_seq = -(-cache_len // block_tokens)
        if self.paged:
            # under an HBM goal the store starts at one sequence's worth and
            # grows on demand inside the accountant's headroom, so the ledger
            # (= physical store bytes) never front-runs the budget
            full = max_batch * self.blocks_per_seq
            tight = enable_smartconf and hbm_budget_bytes
            self.pool = PagedKVAllocator(
                cfg, block_tokens=block_tokens,
                max_blocks_per_seq=self.blocks_per_seq,
                capacity_blocks=self.blocks_per_seq if tight else full,
                budget_blocks=full, accountant=self.accountant)
        else:
            self.pool = KVBlockPool(cfg, block_tokens=block_tokens,
                                    max_blocks=2**30,
                                    accountant=self.accountant)
        self.registry = registry or ConfRegistry()

        # ------------------------------------------- radix prefix cache
        # opt-in; needs the refcounted paged allocator (leases + COW)
        if opts.prefix_cache and not self.paged:
            raise ValueError(
                f"{cfg.name}: prefix_cache requires paged KV "
                "(kv_mode='paged' on an attention-only arch)")
        self._prefix_cache = PrefixCache(self.pool) if opts.prefix_cache \
            else None
        if self._prefix_cache is not None:
            self.pool.remap_hook = self._prefix_cache.remap
        self.kv_cache_share = float(opts.kv_cache_share)
        self.prefix_hit_tokens_total = 0   # reclaimed prefill tokens
        self.cow_copied_blocks = 0
        self._tick_prefix_hit = 0
        # windowed token-weighted hit rate: the sc_cache controller sensor
        self._hit_window: collections.deque[tuple[int, int]] = \
            collections.deque(maxlen=slo.window if slo is not None else 64)
        # block-level sliding-window eviction: only when EVERY attention
        # layer is windowed (a single global layer needs the whole history
        # resident) and the prefix cache is off (trimmed blocks cannot be
        # shared — the two policies are mutually exclusive by construction)
        kinds = {k.split("+")[0] for k in cfg.block_pattern}
        self._window_evict = (self.paged and opts.window_evict
                              and self._prefix_cache is None
                              and kinds <= {"swa", "local"}
                              and bool(cfg.window))

        # engine state
        self.waiting: collections.deque[Request] = collections.deque()
        self.queued: collections.deque[Request] = collections.deque()
        self.queued_tokens = 0
        self.prefilling: dict[int, Request] = {}
        self.running: dict[int, Request] = {}
        self.finished: list[Request] = []
        self.rejected = 0
        self.shed: list[Request] = []   # typed-rejected requests, in order
        self.reject_counts: collections.Counter = collections.Counter()
        self.preemptions = 0
        self.recompute_tokens = 0       # prefilled work thrown away by
        #                                 preemption (bounded-recompute gauge)
        self._admit_counter = 0
        self._free_slots = collections.deque(range(max_batch))
        self.prefill_calls = 0
        self._prefill_shapes: set[int] = set()
        # model-dispatch accounting: every jitted model call (prefill,
        # decode, or unified step) counts one dispatch; the unified packed
        # path collapses the steady-state tick to exactly one
        self.model_dispatches = 0
        self._tick_dispatches = 0
        self._decode_dispatched = False
        # prefill padding telemetry (the serve.prefill_chunk_tokens deputy):
        # issued = token-positions the prefill calls computed, live = real
        # prompt tokens among them; pad_fraction = 1 - live/issued
        self.prefill_issued_tokens = 0
        self.prefill_live_tokens = 0
        self._tick_issued = 0
        self._tick_live = 0
        self._tick_packed_segments = 0
        self._tick_decode = 0
        self._tick_kv_blocks = 0.0
        # the window each attention layer's paged walk is bounded by, with
        # its layer count (models/blocks.py: swa/local kinds take cfg.window)
        self._attn_windows = collections.Counter(
            cfg.window if base in ("swa", "local") else 0
            for base in (cfg.block_pattern[i % len(cfg.block_pattern)]
                         .split("+")[0] for i in range(cfg.num_layers))
            if base in ATTN_KINDS)

        # device-resident hot state (one fused batch across slots); the
        # host only keeps positions/counters, never token values
        if self.paged:
            self.caches = zoo.init_paged_cache(cfg, self.pool.capacity,
                                               block_tokens)
            self._bt_np = np.full((max_batch, self.blocks_per_seq), -1,
                                  np.int32)
            self._bt_dev = jnp.asarray(self._bt_np)
            self._bt_dirty = False
        else:
            # windowed dense rings need headroom for in-flight draft K/V:
            # a rejected draft's stale entries must age out of the window
            # before they can alias a live position
            self.caches = zoo.init_cache(
                cfg, max_batch, cache_len,
                ring_margin=self.spec_depth_max if self.spec_enabled else 0)
        self.slot_pos = np.full((max_batch,), -1, np.int64)
        self._slot_tok = jnp.zeros((max_batch,), jnp.int32)
        self._gen_buf = jnp.zeros((max_batch, cache_len), jnp.int32)
        if self.mesh is not None:
            # pin the K/V planes on their Kv-dim model-axis placement once;
            # the step fns re-assert it on their (donated) cache outputs so
            # it survives every tick, and the eager resize paths re-place
            self._cache_plan = CacheShardingPlan(self.mesh, paged=self.paged)
            self.caches = self._cache_plan.place(self.caches)
        plan = self._cache_plan

        def _pin(c, tok, gbuf):
            # inside-jit epilogue: cache placement survives donation, and
            # the token rings stay replicated instead of drifting to
            # whatever layout XLA picked this compile
            if plan is None:
                return c, tok, gbuf
            return plan.constrain(c), plan.replicate(tok), \
                plan.replicate(gbuf)

        def decode_fn(p, c, tok, pos, active, gbuf, gidx, bt):
            logits, c = zoo.decode_step(cfg, p, c, tok, pos, active=active,
                                        block_tables=bt)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(active, nxt, tok)
            gbuf = gbuf.at[jnp.arange(tok.shape[0]), gidx].set(
                nxt, mode="drop")
            c, tok, gbuf = _pin(c, tok, gbuf)
            return tok, c, gbuf

        def prefill_chunk_fn(p, c, tokens, start, lengths, done, tok, gbuf,
                             bt):
            logits, c = zoo.prefill_chunk(cfg, p, c, tokens, start, lengths,
                                          block_tables=bt)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(done, first, tok)
            slot0 = jnp.where(done, 0, gbuf.shape[1])
            gbuf = gbuf.at[jnp.arange(tok.shape[0]), slot0].set(
                first, mode="drop")
            c, tok, gbuf = _pin(c, tok, gbuf)
            return c, tok, gbuf

        def step_unified_fn(p, c, tokens, slot_id, pos, start, seg_len,
                            is_dec, sample, gidx, tok, gbuf, bt):
            # decode segments carry placeholder tokens in the host-built
            # stream; fill them from the device-resident token ring so the
            # deferred-host-sync invariant survives unification
            safe = jnp.clip(slot_id, 0, max_batch - 1)
            tokens = jnp.where(is_dec[None, :], tok[safe][None, :], tokens)
            logits, c = zoo.step_packed(cfg, p, c, tokens, slot_id, pos,
                                        start, seg_len, block_tables=bt)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # sample every segment that completed a row this tick:
            # prefill-finishers (gidx == 0) and decoders (gidx == gen_count)
            tok = jnp.where(sample, nxt, tok)
            gbuf = gbuf.at[jnp.arange(tok.shape[0]), gidx].set(
                nxt, mode="drop")
            c, tok, gbuf = _pin(c, tok, gbuf)
            return c, tok, gbuf

        def step_spec_fn(p, c, tokens, slot_id, pos, start, seg_len, is_dec,
                         spec_rows, sample, gidx, spec_idx, draft_len, tok,
                         gbuf, bt):
            # the pending token of each spec segment (stream offset
            # spec_idx[:, 0]) is device-resident; drafts ride host-side
            safe = jnp.clip(slot_id, 0, max_batch - 1)
            tokens = jnp.where(is_dec[None, :], tok[safe][None, :], tokens)
            accept, toks, c = zoo.step_spec(cfg, p, c, tokens, slot_id, pos,
                                            start, seg_len, spec_rows,
                                            spec_idx, draft_len,
                                            block_tables=bt)
            # emit the accepted prefix plus the model's own next token:
            # toks[b, :accept[b]+1] lands at gidx[b]..gidx[b]+accept[b]
            rows = jnp.arange(max_batch)
            offs = jnp.arange(spec_idx.shape[1], dtype=jnp.int32)[None, :]
            write = (offs <= accept[:, None]) & sample[:, None]
            cols = jnp.where(write, gidx[:, None] + offs, gbuf.shape[1])
            gbuf = gbuf.at[rows[:, None], cols].set(toks, mode="drop")
            tok = jnp.where(sample, toks[rows, accept], tok)
            c, tok, gbuf = _pin(c, tok, gbuf)
            return c, tok, gbuf, accept, toks

        def merge_fn(full, one, slot):
            def merge(f, o):
                axis = None
                for i, (fs, os) in enumerate(zip(f.shape, o.shape)):
                    if os == 1 and fs == self.max_batch:
                        axis = i
                        break
                    if fs != os:
                        return f  # shape mismatch (e.g. enc_out cache len)
                if axis is None:
                    return f
                starts = tuple(slot if i == axis else 0
                               for i in range(f.ndim))
                return jax.lax.dynamic_update_slice(
                    f, o.astype(f.dtype), starts)
            return jax.tree.map(merge, full, one)

        # donated args: the fused cache + device token buffers are consumed
        # and returned every call, so XLA reuses their buffers in place
        self._decode = jax.jit(decode_fn, donate_argnums=(1, 2, 5))
        self._prefill_chunk = jax.jit(prefill_chunk_fn,
                                      donate_argnums=(1, 6, 7))
        self._step_unified = jax.jit(step_unified_fn,
                                     donate_argnums=(1, 10, 11))
        self._step_spec = jax.jit(step_spec_fn, donate_argnums=(1, 13, 14))
        self._prefill = jax.jit(
            lambda p, b: zoo.prefill(cfg, p, b, cache_len=cache_len))
        self._merge = jax.jit(merge_fn, donate_argnums=(0,))
        # COW resolution: whole-block device copies applied before a lease
        # writes into a block it shares with the prefix cache (pair lists
        # are padded to power-of-two lengths, so compiles stay O(log))
        def copy_blocks_fn(c, s, d):
            c = zoo.copy_paged_blocks(c, s, d)
            return c if plan is None else plan.constrain(c)

        self._copy_blocks = jax.jit(
            copy_blocks_fn, donate_argnums=(0,)) if self.paged else None

        # sensors (share the injected clock so tests can be deterministic).
        # tick_latency spans the WHOLE tick (admit + schedule + compute +
        # bookkeeping); decode_latency records only the model-compute span
        # of ticks that advanced at least one decoding slot — the latency a
        # decode token actually waited for, which is what the sc_chunk
        # controller must attribute to its own knob (a long prefill sharing
        # the tick inflates it; host-side admission work does not).
        # Under an SLO the latency windows shrink to slo.window so the
        # brownout controller reads the current load regime, not a stale
        # mix across a traffic shift.
        slo_window = slo.window if slo is not None else 512
        self.tick_latency = LatencySensor(clock=clock)
        self.decode_latency = LatencySensor(window=slo_window, clock=clock)
        self.ttft = LatencySensor(window=slo_window, clock=clock)
        # controller-facing TTFT, measured from ADMISSION ELIGIBILITY (the
        # tick the request first cleared the tier gate into the token
        # queue), not from submit().  The brownout gate's own parking delay
        # must never feed back into the signal that opens/closes the gate:
        # with submit-relative TTFT, every parked request re-admitted after
        # a burst carries a blown sample, p99 stays pinned above the goal,
        # and the gate latches shut (observed: goodput collapse).  True
        # client TTFT (self.ttft) still decides goodput.
        self.ttft_ctrl = LatencySensor(window=slo_window, clock=clock)
        self.throughput = ThroughputSensor(window_seconds=5.0, clock=clock)

        # SLO / multi-tenant overload state (serve/README.md): tiered
        # admission with graceful brownout, per-request deadlines, and
        # goodput-under-SLO accounting at completion
        self.slo = slo
        self.num_tiers = max(1, int(num_tiers))
        self.admit_tier_max = (self.num_tiers - 1 if admit_tier_max is None
                               else int(admit_tier_max))
        self.slo_good_requests = 0
        self.slo_miss_requests = 0
        self.slo_good_tokens = 0
        self.slo_miss_tokens = 0
        # chaos hook: every sensor reading the controllers consume passes
        # through the tap (fault injection corrupts here; the SmartConf
        # guardrails are what must absorb it)
        self.sensor_tap: Callable[[str, float], float] | None = \
            opts.sensor_tap
        # worker-preemption wiring (distributed.fault_tolerance): on
        # trigger the engine drains — requeues every in-flight request and
        # refuses new work with a typed reason — instead of crashing
        self.preemption = preemption if preemption is not None \
            else PreemptionHandler()
        self._draining = False
        self._closed = False

        # SmartConf PerfConfs
        self.enable_smartconf = enable_smartconf
        self.max_queue_tokens = 4 * cache_len
        self.prefill_chunk = cache_len
        self.sc_queue = None
        self.sc_kv = None
        self.sc_chunk = None
        self.sc_admit = None
        self.sc_cache = None
        self.sc_spec = None
        # the decode-latency goal is shared: sc_chunk targets it directly,
        # and the sc_spec knob is SUBORDINATE to it (accept-rate is a soft
        # goal; a blown decode p99 overrides and shrinks the draft depth)
        self._decode_goal = latency_goal_s if latency_goal_s is not None \
            else (slo.decode_s if slo is not None else None)
        # sensor-sanity guardrails for every serve controller: a dropped-out
        # or chaos-corrupted sensor (NaN, negative, physically impossible
        # spike) must never reach Eq. 2 — after 3 consecutive insane
        # readings the knob pins to its last-known-good value
        byte_rails = Guardrails(perf_lo=0.0, perf_hi=1e15)
        lat_rails = Guardrails(perf_lo=0.0, perf_hi=3600.0)
        if enable_smartconf and hbm_budget_bytes:
            goal = GoalSpec(float(hbm_budget_bytes), hard=True,
                            super_hard=True)
            self.sc_queue = SmartConfIndirect(
                "serve.max_queue_tokens", metric="hbm_bytes", goal=goal,
                initial=0.0, registry=self.registry, guardrails=byte_rails,
                model=ControllerModel(alpha=float(QUEUE_TOKEN_BYTES),
                                      lam=0.05, delta=1.15, conf_min=0.0,
                                      conf_max=1e9))
            # attention-free archs have block_bytes == 0 (O(1) state); floor
            # the gain so the controller degrades to a no-op instead of a
            # divide-by-zero
            self.sc_kv = SmartConfIndirect(
                "serve.kv_block_budget", metric="hbm_bytes", goal=goal,
                initial=1.0, registry=self.registry,
                guardrails=dataclasses.replace(byte_rails),
                model=ControllerModel(alpha=float(max(1, self.pool.block_bytes)),
                                      lam=0.05, delta=1.15, conf_min=1.0,
                                      conf_max=1e9))
            decode_goal = self._decode_goal
            if decode_goal is not None:
                # alpha: prefill seconds per token, measured lazily; start
                # 1e-4.  The slew clamp bounds one actuation to a quarter of
                # the knob range: a single insane error cannot slam the
                # chunk budget across its whole span in one interval.
                self.sc_chunk = SmartConf(
                    "serve.prefill_chunk_tokens", metric="decode_p99_s",
                    goal=GoalSpec(decode_goal, hard=False),
                    initial=float(cache_len), registry=self.registry,
                    guardrails=dataclasses.replace(
                        lat_rails, max_step=max(float(block_tokens),
                                                cache_len / 4.0)),
                    model=ControllerModel(alpha=1e-4, lam=0.1, delta=1.3,
                                          conf_min=float(block_tokens),
                                          conf_max=float(cache_len)))
        if enable_smartconf and slo is not None and admit_tier_max is None:
            # graceful-brownout controller: admit_tier_max is a direct
            # PerfConf on TTFT-p99 — overload pushes p99 past the (hard)
            # SLO goal, the two-pole controller sheds the lowest tiers
            # first (conf drops), and calm traffic re-opens them.  alpha =
            # one tier's worth of TTFT per step, in goal units: admitting
            # one more tier is modeled to add ~0.5 x the SLO bound to p99.
            self.sc_admit = SmartConf(
                "serve.admit_tier_max", metric="ttft_p99_s",
                goal=GoalSpec(float(slo.ttft_s), hard=True),
                initial=float(self.num_tiers - 1), registry=self.registry,
                guardrails=dataclasses.replace(lat_rails),
                model=ControllerModel(alpha=0.5 * float(slo.ttft_s),
                                      lam=0.1, delta=1.3, conf_min=0.0,
                                      conf_max=float(self.num_tiers - 1)))
        if enable_smartconf and self._prefix_cache is not None:
            # cache-share controller: serve.kv_cache_share is a direct
            # PerfConf on the windowed token-weighted prefix hit rate with
            # a LOWER-direction goal (the hit rate should stay above it).
            # alpha > 0: granting the cache a larger share of the block
            # budget retains more prefixes and raises the hit rate.  The
            # guardrails pin the sensor to [0, 1] (a rate) and slew-clamp
            # one actuation to a tenth of the knob span; the knob itself is
            # continuous (integer=False) in [0.05, 0.9] — the cache never
            # starves resident sequences entirely, and never vanishes so
            # abruptly the hit-rate sensor loses its signal.
            self.sc_cache = SmartConf(
                "serve.kv_cache_share", metric="prefix_hit_rate",
                goal=GoalSpec(float(opts.prefix_hit_rate_goal),
                              direction="lower"),
                initial=self.kv_cache_share, registry=self.registry,
                guardrails=Guardrails(perf_lo=0.0, perf_hi=1.0,
                                      max_step=0.1),
                model=ControllerModel(alpha=1.0, lam=0.05, delta=1.2,
                                      conf_min=0.05, conf_max=0.9,
                                      integer=False))
        if enable_smartconf and self.spec_enabled and opts.spec_adaptive:
            # draft-depth controller: serve.spec_depth is a direct PerfConf
            # on the windowed accept rate with a LOWER-direction soft goal
            # (the rate should stay above the setpoint).  alpha < 0 — the
            # sign-correct gain for an inversely-related pair: deepening the
            # draft DROPS the accept rate (late draft positions are less
            # predictable), so a rate above goal opens headroom to deepen
            # and a rate below it shallows.  The guardrails pin the sensor
            # to [0, 1] and slew-clamp one actuation to 2 depth steps; the
            # knob is integer in [1, spec_depth_max] — depth 0 is an
            # operator choice (spec off), never a controller state, so the
            # accept-rate sensor always keeps its signal.
            self.sc_spec = SmartConf(
                "serve.spec_depth", metric="accept_rate",
                goal=GoalSpec(float(opts.accept_rate_goal),
                              direction="lower"),
                initial=float(self.spec_depth), registry=self.registry,
                guardrails=Guardrails(perf_lo=0.0, perf_hi=1.0,
                                      max_step=2.0),
                model=ControllerModel(alpha=-0.08, lam=0.1, delta=1.3,
                                      conf_min=1.0,
                                      conf_max=float(self.spec_depth_max)))

        # ------------------------------------------------------- telemetry
        # Off by default, and free when off: a disabled (or absent) hub
        # collapses to self._tel = None, so the hot path pays exactly one
        # `is not None` test per instrumentation point — the disabled path
        # IS the pre-telemetry path (bench_overhead gates <1% in CI).
        # REPRO_TELEMETRY=1 force-enables it for the CI telemetry leg
        # without touching call sites (same pattern as REPRO_PREFILL_MODE).
        self.ticks_run = 0
        self._tick_wait_s = 0.0
        if telemetry is None and opts.telemetry_env:
            telemetry = Telemetry(enabled=True, clock=clock)
        self._tel = telemetry if (telemetry is not None
                                  and telemetry.enabled) else None
        self._tick_readings: dict[str, tuple[float, float]] = {}
        if self._tel is not None:
            # pre-create the hot-path instruments so ticks never take the
            # registry's get-or-create branch
            m = self._tel.metrics
            self._tel_h_tick = m.histogram("serve.tick_latency_s")
            self._tel_h_decode = m.histogram("serve.decode_latency_s")
            self._tel_h_ttft = m.histogram("serve.ttft_s")
            self._tel_c_ticks = m.counter("serve.ticks")
            self._tel_c_tokens = m.counter("serve.tokens")
            self._tel_c_spec_prop = m.counter("serve.spec.proposed")
            self._tel_c_spec_acc = m.counter("serve.spec.accepted")
            self._tel_h_spec = m.histogram("serve.spec.accepted_len")
            for reason in RejectReason:
                m.counter(f"serve.reject.{reason}")
            self._tick_rejects0 = 0
            self._tel_faults_seen = 0
            self._tel_fallback_seen: set[str] = set()
            for sc in (self.sc_queue, self.sc_kv, self.sc_chunk,
                       self.sc_admit, self.sc_cache, self.sc_spec):
                if sc is not None:
                    sc.attach_audit(self._tel.audit)

    # ------------------------------------------------------------------ API
    def _reject(self, req: Request, reason: RejectReason) -> RejectReason:
        """Typed rejection: the request is recorded (``shed``), counted,
        and stamped with the reason — never an exception mid-tick."""
        req.reject_reason = reason
        req.done_t = self.clock()
        self.rejected += 1
        self.reject_counts[str(reason)] += 1
        self.shed.append(req)
        if self._tel is not None:
            self._tel.metrics.counter(f"serve.reject.{reason}").inc()
            self._tel.tracer.async_end(
                "request", req.req_id, args={"rejected": str(reason)})
        return reason

    def submit(self, req: Request) -> Admission:
        """Validate + enqueue; returns a typed :class:`Admission` receipt
        (truthy on acceptance, carrying the reject reason otherwise, plus
        the request's block footprint and — when the prefix cache is on —
        an advisory count of prompt tokens a cache hit would cover right
        now).  Invalid work is rejected *here*, at the door — an empty
        prompt, a prompt that cannot fit the KV ring, or a footprint no
        block budget could ever hold would otherwise crash (or silently
        spin) the scheduler mid-tick."""
        req.prompt_bytes = len(req.prompt) * QUEUE_TOKEN_BYTES
        req.submitted_t = self.clock()
        fp = self._footprint_blocks(req)
        if self._draining or self.preemption.triggered:
            return Admission(False, self._reject(req, RejectReason.DRAINING),
                             footprint_blocks=fp)
        if len(req.prompt) == 0:
            return Admission(False,
                             self._reject(req, RejectReason.EMPTY_PROMPT),
                             footprint_blocks=fp)
        npatch = self.cfg.num_patches if self.cfg.frontend == "vision" else 0
        total = npatch + len(req.prompt) + req.max_new_tokens
        if total > self.cache_len:
            # beyond cache_len the KV ring wraps (prompt history or sampled
            # tokens silently fall out) — shed loudly instead
            return Admission(False,
                             self._reject(req, RejectReason.PROMPT_TOO_LONG),
                             footprint_blocks=fp)
        if fp > self._kv_budget_ceiling():
            # no admission order could ever schedule this request under the
            # block budget: refusing now beats queueing it to spin forever
            return Admission(False,
                             self._reject(req, RejectReason.KV_FOOTPRINT),
                             footprint_blocks=fp)
        hit = (self._prefix_cache.probe(req.prompt)
               if self._prefix_cache is not None else 0)
        self.waiting.append(req)
        return Admission(True, None, prefix_hit_tokens=hit,
                         footprint_blocks=fp)

    def _footprint_blocks(self, req: Request) -> int:
        """KV blocks the request needs resident while running."""
        npatch = self.cfg.num_patches if self.cfg.frontend == "vision" else 0
        need = min(npatch + len(req.prompt) + req.max_new_tokens,
                   self.cache_len)
        return -(-need // self.pool.block_tokens)

    def _kv_budget_ceiling(self) -> int:
        """Largest block budget a request could ever see: the live budget
        for static engines, the structural store ceiling when SmartConf owns
        (and may later raise) the budget."""
        if self.sc_kv is not None:
            return self.max_batch * self.blocks_per_seq
        return self.pool.max_blocks

    def hbm_bytes(self) -> int:
        return self.accountant.total()

    def kv_shard_bytes(self) -> int:
        """Per-device bytes of the resident KV cache tree — the mesh-aware
        HBM gauge.  Without a mesh this is the whole tree; with one, the
        K/V planes divide by the model-axis size, so for a paged store
        (K/V planes only) ``kv_shard_bytes() * tp_shards`` reproduces the
        single-device total exactly."""
        if self._cache_plan is not None:
            return self._cache_plan.shard_bytes(self.caches)
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.caches))

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill/packed-stream programs compiled so far: one per
        packed stream width (unified), per padded bucket width (bucketed),
        or per distinct prompt length (legacy).  Tracked by input shape on
        the engine side (the jitted callables are per-engine lambdas, so
        shape count == jit cache size) to avoid depending on private jax
        cache introspection."""
        return len(self._prefill_shapes)

    @property
    def model_programs(self) -> int:
        """Total distinct compiled model programs serving the hot loop:
        the prefill/packed-stream shapes plus the standalone decode
        program.  Split-path engines (bucketed/legacy) dispatch the decode
        program every running tick; a unified packed engine compiles it
        only once drain (decode-only) ticks occur — mixed ticks fuse
        decode into the stream dispatch."""
        return len(self._prefill_shapes) + (1 if self._decode_dispatched
                                            else 0)

    # ------------------------------------------------------------- one tick
    def tick(self) -> dict:
        t0 = self.clock()
        self._tick_wait_s = 0.0
        with self._span("tick") as note:
            if self.mesh is None:
                stats = self._tick_impl()
            else:
                # the serving mesh + rule overlay are active for the WHOLE
                # tick: every trace made this tick (step fns, COW copies,
                # resizes) sees current_mesh(), so the attention wrappers
                # engage shard_map and only head-parallel work shards
                # (SERVE_TP_RULES nulls the training-only ff/vocab rules
                # that would change contraction order)
                with use_mesh(self.mesh, rules=SERVE_TP_RULES, fsdp=False):
                    stats = self._tick_impl()
            note.update((k, stats[k]) for k in (
                "tokens", "queued", "running", "rejected", "admit_tier_max",
                "draining"))
        stats["host_s"] = self.clock() - t0 - self._tick_wait_s
        return stats

    @contextlib.contextmanager
    def _span(self, phase: str, **args):
        """One phase of a tick, timed where it runs.  Always a
        ``serve.<phase>`` span on the profiler's host plane, on the time
        base of the device planes (``jax.profiler`` records it only while
        a trace is capturing; otherwise the helper costs about 2.5 us).  The
        tick itself is a step annotation numbered by ``ticks_run``.  With
        telemetry on, also a complete event in ``trace.json`` (``tick N``,
        or the bare phase name with ``tick`` and ``args``) from the
        tracer's clock; the tick's body adds its args to the yielded dict.
        ``wait`` and ``fetch`` spans add to the seconds that ``host_s``
        leaves out."""
        tick = self.ticks_run
        if phase == "tick":
            ann = jax.profiler.StepTraceAnnotation("serve.tick",
                                                   step_num=tick)
        else:
            ann = jax.profiler.TraceAnnotation("serve." + phase, **args)
        t0 = self.clock() if phase in _WAIT_PHASES else None
        try:
            with ann:
                if self._tel is None:
                    yield {}
                else:
                    event = ((f"tick {tick}", {}) if phase == "tick"
                             else (phase, {"tick": tick, **args}))
                    with self._tel.tracer.span(*event) as note:
                        yield note
        finally:
            if t0 is not None:
                self._tick_wait_s += self.clock() - t0

    def _tick_impl(self) -> dict:
        t0 = self.clock()
        self._tick_issued = self._tick_live = 0
        self._tick_packed_segments = 0
        self._tick_dispatches = 0
        self._tick_decode = 0
        self._tick_prefix_hit = 0
        self._tick_spec_proposed = self._tick_spec_accepted = 0
        self._tick_spec_lanes = self._tick_decode_slots = 0
        self._tick_kv_blocks = 0.0
        tel = self._tel
        if tel is not None:
            tel.audit.tick = self.ticks_run
            self._tick_readings = {}
            self._tick_rejects0 = self.rejected
        if self.preemption.triggered:
            # worker preemption: drain once (requeue every in-flight
            # request, copy-free), then idle — never crash mid-tick.  The
            # queue survives for a handoff or an in-place resume.
            with self._span("drain"):
                if not self._draining:
                    self._drain_for_preemption()
            self.tick_latency.record(self.clock() - t0)
            stats = self._stats(0)
            self.ticks_run += 1
            if tel is not None:
                self._tel_finish_tick(stats, self.clock() - t0)
            return stats
        self._draining = False          # preemption cleared: resume serving
        with self._span("control"):
            self._update_controllers()
            self._shed_expired()
        with self._span("admit"):
            self._admit()
        with self._span("schedule"):
            self._schedule()
        if self.spec_enabled:
            n_tokens = self._tick_spec()
        elif self.prefill_impl == "packed":
            n_tokens = self._tick_unified()
        else:
            self._prefill_tick()
            n_tokens = self._decode_tick()
        with self._span("finish"):
            self._finish()
            if self._window_evict:
                self._trim_windows()
        self.tick_latency.record(self.clock() - t0)
        stats = self._stats(n_tokens)
        self.ticks_run += 1
        if tel is not None:
            self._tel_finish_tick(stats, self.clock() - t0)
        return stats

    def _stats(self, n_tokens: int) -> dict:
        # NOTE: keys and their order are the frozen TickStats schema
        # (TICK_STATS_KEYS, regression-tested) — extend at the end only.
        return {
            "tick": self.ticks_run,
            "queued": len(self.queued),
            "waiting": len(self.waiting),
            "running": len(self.running) + len(self.prefilling),
            "finished": len(self.finished), "hbm": self.hbm_bytes(),
            "tokens": n_tokens,
            # prefill-knob deputy sensors: the fraction of this tick's
            # issued prefill tokens that were dead padding, and how many
            # request segments shared the tick's prefill call(s) (packed:
            # several per call even when their natural buckets differ)
            "pad_fraction": (1.0 - self._tick_live / self._tick_issued
                             if self._tick_issued else 0.0),
            "packed_segments": self._tick_packed_segments,
            # jitted model calls this tick: the unified packed path costs
            # exactly one; split paths cost up to two (prefill + decode)
            "dispatches": self._tick_dispatches,
            # work mix this tick (the open-loop harness's virtual cost
            # model charges prefill lanes — padding included, it costs
            # compute — and decode tokens separately)
            "prefill_tokens": self._tick_live,
            "prefill_issued_tokens": self._tick_issued,
            "decode_tokens": self._tick_decode,
            # pool-pressure sensors (budget-vs-occupancy, bench_serving)
            "kv_used_blocks": self.pool.used_blocks,
            "kv_budget_blocks": self.pool.max_blocks,
            "kv_capacity_blocks": getattr(self.pool, "capacity",
                                          self.pool.max_blocks),
            "kv_over_budget": self.pool.over_budget,
            "kv_frag_tokens": self.pool.frag_tokens,
            "preemptions": self.preemptions,
            # SLO / overload sensors (serve/README.md)
            "admit_tier_max": self.admit_tier_max,
            "rejected": self.rejected,
            "draining": self._draining,
            "slo_good_tokens": self.slo_good_tokens,
            "slo_miss_tokens": self.slo_miss_tokens,
            # prefix-cache sensors (radix tree over refcounted blocks)
            "prefix_hit_tokens": self._tick_prefix_hit,
            "prefix_cache_blocks": (self._prefix_cache.blocks_held
                                    if self._prefix_cache is not None
                                    else 0),
            "kv_cache_share": self.kv_cache_share,
            # speculative-decode sensors (draft-and-verify on the packed
            # stream); decode_slots is the per-tick KV-read unit the cost
            # model charges now that decode_tokens can exceed it
            "spec_depth": self.spec_depth,
            "accept_rate": (self._tick_spec_accepted
                            / self._tick_spec_proposed
                            if self._tick_spec_proposed else 0.0),
            "spec_lanes": self._tick_spec_lanes,
            "decode_slots": self._tick_decode_slots,
            # mesh-serving sensor: model-axis shards behind this tick
            "tp_shards": self.tp_shards,
            # the host's part of the tick: tick() sets it once the tick's
            # span has closed
            "host_s": 0.0,
            "attn_kv_blocks": self._tick_kv_blocks,
        }

    def run(self, ticks: int) -> list[dict]:
        return [self.tick() for _ in range(ticks)]

    # ----------------------------------------------------------- telemetry
    def _tel_finish_tick(self, stats: dict, wall_dt: float) -> None:
        """Per-tick telemetry epilogue (only reached when enabled): fold
        the stats into the metrics, snapshot the sensor readings into the
        flight-recorder ring, and dump the ring on any guardrail fault,
        fallback engagement, or rejection storm."""
        tel = self._tel
        tick = stats["tick"]
        self._tel_c_ticks.inc()
        self._tel_c_tokens.inc(stats["tokens"])
        if wall_dt > 0.0:
            # wall span of the tick body; under a VirtualClock this is 0
            # (the clock is frozen within a tick) and the open-loop driver
            # charges the virtual cost through charge_tick_cost instead
            self._tel_h_tick.record(wall_dt)
        m = tel.metrics
        m.gauge("serve.hbm_bytes").set(float(stats["hbm"]))
        m.gauge("serve.admit_tier_max").set(float(stats["admit_tier_max"]))
        tel.flight.record(tick, dict(self._tick_readings))
        faults = 0
        for sc in (self.sc_queue, self.sc_kv, self.sc_chunk, self.sc_admit,
                   self.sc_cache, self.sc_spec):
            if sc is None:
                continue
            faults += sc.sensor_faults
            if sc.sensor_failed:
                if sc.conf_name not in self._tel_fallback_seen:
                    self._tel_fallback_seen.add(sc.conf_name)
                    tel.flight.dump(f"fallback:{sc.conf_name}", tick)
            else:
                self._tel_fallback_seen.discard(sc.conf_name)
        if faults > self._tel_faults_seen:
            self._tel_faults_seen = faults
            tel.flight.dump("guardrail_fault", tick)
        if self.rejected - self._tick_rejects0 >= _REJECT_STORM_PER_TICK:
            tel.flight.dump("rejection_storm", tick)

    def note_chaos(self, name: str) -> None:
        """Chaos-injection stamp (called by ChaosMonkey): the fault lands
        on the trace timeline next to the tick it hit, counts in the
        metrics, and dumps the flight recorder — fault <-> controller
        response causality in one artifact set."""
        if self._tel is None:
            return
        tel = self._tel
        tel.tracer.instant(f"chaos:{name}", tid=Tracer.TID_CHAOS,
                           args={"tick": self.ticks_run})
        tel.metrics.counter(f"chaos.{name.split(':', 1)[0]}").inc()
        tel.flight.dump(f"chaos:{name.split(':', 1)[0]}", self.ticks_run)

    def note_arrival(self, req: Request) -> None:
        """Driver-side arrival stamp: an instant on the driver track plus
        the open end of the request's async lifetime span (closed at
        finish or rejection)."""
        if self._tel is None:
            return
        trc = self._tel.tracer
        trc.instant("arrival", tid=Tracer.TID_DRIVER,
                    args={"req": req.req_id, "tier": req.tier})
        trc.async_begin("request", req.req_id,
                        args={"tier": req.tier,
                              "prompt_len": int(len(req.prompt)),
                              "deadline_s": req.deadline_s})

    def charge_tick_cost(self, dt: float, *, decoded: bool = False) -> None:
        """Virtual-time cost feedback from the open-loop driver: the clock
        is frozen within a tick, so the driver charges the modeled tick
        cost into the latency sensors (and telemetry histograms) after the
        fact — the controllers and the trace see the same virtual time the
        requests experience."""
        self.tick_latency.record(dt)
        if decoded:
            self.decode_latency.record(dt)
        if self._tel is not None:
            self._tel_h_tick.record(dt)
            if decoded:
                self._tel_h_decode.record(dt)

    # ------------------------------------------------------------ internals
    def _sense(self, name: str, value: float) -> float:
        """Controller-facing sensor read — the ONE road a reading takes to
        a controller.  Routed through the chaos tap when one is installed
        (fault injection corrupts readings here; the SmartConf guardrails
        must absorb whatever comes back) and recorded raw+tapped into the
        flight recorder's per-tick snapshot, so chaos, the controllers,
        and the flight recorder all observe the identical stream.  Every
        reading a controller consumes must pass through here — including
        the indirect confs' deputies."""
        tap = self.sensor_tap
        out = tap(name, value) if tap is not None else value
        if self._tel is not None:
            self._tick_readings[name] = (value, out)
        return out

    def _update_controllers(self) -> None:
        if not self.enable_smartconf:
            return
        if self.sc_queue is not None:
            hbm = self._sense("hbm_bytes", float(self.hbm_bytes()))
            self.sc_queue.set_perf(
                hbm, self._sense("queued_tokens", float(self.queued_tokens)))
            self.max_queue_tokens = max(0, int(self.sc_queue.get_conf()))
            self.sc_kv.set_perf(
                hbm,
                self._sense("kv_used_blocks", float(self.pool.used_blocks)))
            self.pool.set_budget(max(1, int(self.sc_kv.get_conf())))
            if self.paged and self.pool.over_budget:
                # the budget bit below occupancy: make the cut physical
                self._enforce_kv_budget()
            if self.sc_chunk is not None:
                self.sc_chunk.set_perf(
                    self._sense("decode_p99_s", self.decode_latency.p99()))
                self.prefill_chunk = max(1, int(self.sc_chunk.get_conf()))
        if self.sc_admit is not None:
            # per-tick censored observation: the head-of-line request's
            # eventual TTFT is at least its current wait; an empty queue
            # contributes zero.  Without this the sensor FREEZES when the
            # gate closes (nothing finishes -> no samples -> p99 pinned at
            # the burst-era value) and the brownout latches shut while the
            # engine idles; with it the window drains in ~window ticks of
            # calm and the gate re-opens.
            now = self.clock()
            if self.queued:
                head = self.queued[0]
                epoch = head.queued_t if head.queued_t is not None \
                    else head.submitted_t
                self.ttft_ctrl.record(max(0.0, now - epoch))
            else:
                self.ttft_ctrl.record(0.0)
            self.sc_admit.set_perf(
                self._sense("ttft_p99_s", self.ttft_ctrl.p99()))
            self.admit_tier_max = int(self.sc_admit.get_conf())
        if self.sc_cache is not None and self._hit_window:
            # token-weighted hit rate over the recent admission window:
            # raw per-lookup hit counts overweight short prompts, and the
            # reclaimed capacity the share buys is proportional to tokens.
            # No admissions yet -> no observation -> no actuation (a cold
            # window is not evidence the share is wrong)
            hw = self._hit_window
            rate = sum(h for h, _ in hw) / max(1, sum(p for _, p in hw))
            self.sc_cache.set_perf(self._sense("prefix_hit_rate", rate))
            self.kv_cache_share = float(self.sc_cache.get_conf())
            self._prefix_cache.enforce(
                int(self.kv_cache_share * self.pool.max_blocks))
        if self.sc_spec is not None and self._accept_window:
            # windowed accept rate drives the depth; no drafts verified yet
            # -> no observation -> no actuation.  The accept-rate goal is
            # SOFT and subordinate: when decode p99 blows its (engine-wide)
            # goal, verifying lanes are what the tick can shed fastest, so
            # the depth steps down one regardless of what Eq. 2 wants.
            aw = self._accept_window
            rate = sum(a for a, _ in aw) / max(1, sum(p for _, p in aw))
            self.sc_spec.set_perf(self._sense("accept_rate", rate))
            depth = int(self.sc_spec.get_conf())
            if (self._decode_goal is not None
                    and self.decode_latency.p99() > self._decode_goal):
                depth = min(depth, max(1, self.spec_depth - 1))
            self.spec_depth = max(1, min(depth, self.spec_depth_max))

    def _stamp_first_token(self, req: Request, now: float) -> None:
        """One TTFT sample per request, at the first compute response
        (preempted requests keep their original stamp).  Two sensors: the
        client-true TTFT (from submit; decides goodput) and the
        controller-facing TTFT (from first admission past the tier gate;
        feeds sc_admit — see the ttft_ctrl construction note)."""
        if req.first_token_t is not None:
            return
        req.first_token_t = now
        self.ttft.record(now - req.submitted_t)
        epoch = req.queued_t if req.queued_t is not None else req.submitted_t
        self.ttft_ctrl.record(now - epoch)
        if self._tel is not None:
            self._tel_h_ttft.record(now - req.submitted_t)

    def _shed_expired(self) -> None:
        """Deadline-expired requests still waiting in line are shed with a
        typed reason: serving them would burn capacity on tokens no client
        is waiting for (zero goodput), which is exactly what an overloaded
        engine cannot afford."""
        now = self.clock()

        def expired(req: Request) -> bool:
            return (req.deadline_s is not None
                    and now - req.submitted_t > req.deadline_s)

        if any(expired(r) for r in self.waiting):
            keep: collections.deque[Request] = collections.deque()
            for req in self.waiting:
                if expired(req):
                    self._reject(req, RejectReason.DEADLINE_EXPIRED)
                else:
                    keep.append(req)
            self.waiting = keep
        if any(expired(r) for r in self.queued):
            keep = collections.deque()
            for req in self.queued:
                if expired(req):
                    self.queued_tokens -= len(req.prompt)
                    self.accountant.credit("queue", req.prompt_bytes)
                    self._reject(req, RejectReason.DEADLINE_EXPIRED)
                else:
                    keep.append(req)
            self.queued = keep

    def _admit(self) -> None:
        """FIFO admission gated by the brownout tier: requests above
        ``admit_tier_max`` stay in the waiting line while their TTFT SLO is
        still winnable (requeue — the brownout may lift) without blocking
        eligible tiers behind them (no head-of-line starvation across
        tiers).  Once a browned-out request's TTFT SLO is already blown it
        is *shed* with a typed reason: serving it late is zero goodput that
        would queue ahead of fresh, still-winnable traffic when the gate
        re-opens — the client gets a fast typed rejection instead of a slow
        useless answer."""
        # the gate applies to the already-admitted queue too: when it
        # drops, queued requests above it (not yet prefilling — no KV to
        # drop) are pushed back to the *front* of the waiting line in
        # admission order.  Without this, the gulp admitted during the
        # controller's reaction lag at a load shift (or an off-burst
        # re-open) sits in the queue ahead of premium traffic and blows
        # the very TTFT the gate closed to protect.
        if any(r.tier > self.admit_tier_max for r in self.queued):
            keep: collections.deque[Request] = collections.deque()
            back: list[Request] = []
            for req in self.queued:
                if req.tier > self.admit_tier_max:
                    self.queued_tokens -= len(req.prompt)
                    self.accountant.credit("queue", req.prompt_bytes)
                    back.append(req)
                else:
                    keep.append(req)
            self.queued = keep
            self.waiting.extendleft(reversed(back))
        browned: collections.deque[Request] = collections.deque()
        now = self.clock()
        while self.waiting:
            req = self.waiting.popleft()
            if req.tier > self.admit_tier_max:
                if (self.slo is not None
                        and now - req.submitted_t > self.slo.ttft_s):
                    self._reject(req, RejectReason.BROWNOUT_SHED)
                else:
                    browned.append(req)     # shed lowest tiers first: wait
                continue
            if self.queued_tokens + len(req.prompt) > self.max_queue_tokens:
                browned.append(req)         # queue full: FIFO order holds
                break
            if req.queued_t is None:
                req.queued_t = now          # the ttft_ctrl epoch (once)
            self.queued.append(req)
            self.queued_tokens += len(req.prompt)
            self.accountant.charge("queue", req.prompt_bytes)
        browned.extend(self.waiting)
        self.waiting = browned

    def _schedule(self) -> None:
        while self.queued and self._free_slots:
            req = self.queued[0]
            total = len(req.prompt) + req.max_new_tokens
            need = min(total, self.cache_len)
            if self._footprint_blocks(req) > self.pool.max_blocks:
                # the budget (possibly cut mid-run, below this request's
                # remaining footprint) can NEVER hold it: park it out of
                # the schedule with a typed reason instead of the
                # preempt-readmit-recompute livelock a blind retry becomes
                self.queued.popleft()
                self.queued_tokens -= len(req.prompt)
                self.accountant.credit("queue", req.prompt_bytes)
                self._reject(req, RejectReason.KV_FOOTPRINT)
                continue
            lease, hit = self._lease_for(req, need)
            if lease is None:
                break  # KV budget exhausted; stay queued
            self.queued.popleft()
            self.queued_tokens -= len(req.prompt)
            self.accountant.credit("queue", req.prompt_bytes)
            req.slot = self._free_slots.popleft()
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            req.lease = lease
            req.prefix_hit = hit
            req.prefilled = hit      # cached prefix: skip to the suffix
            if hit:
                self.prefix_hit_tokens_total += hit
                self._tick_prefix_hit += hit
            if self._prefix_cache is not None:
                self._hit_window.append((hit, len(req.prompt)))
            if self.paged:
                self._bt_np[req.slot] = lease.table_row()
                self._bt_dirty = True
            if self.fused_prefill:
                self.prefilling[req.slot] = req
            else:
                self._do_prefill_legacy(req)
                self.running[req.slot] = req

    def _lease_for(self, req: Request,
                   need: int) -> tuple[object | None, int]:
        """Acquire the request's KV lease, adopting any cached prefix and
        materializing the COW boundary copy.  On allocation failure the
        coldest cached prefix is evicted and the acquisition retried — cold
        cache yields before live traffic waits (and long before anything is
        preempted).  Returns ``(lease, prefix_hit_tokens)`` or
        ``(None, 0)`` when the budget genuinely cannot hold the request."""
        cache = self._prefix_cache
        T = self.pool.block_tokens
        while True:
            if cache is not None:
                hit, shared = cache.lookup(req.prompt, self.ticks_run)
            else:
                hit, shared = 0, []
            fresh = -(-need // T) - len(shared)
            if self.paged and self.pool.free_blocks < fresh:
                # store smaller than demand (start-small under an HBM goal,
                # or shrunk by an earlier cut): grow it first so a free-list
                # miss is never miscounted as an allocation failure
                self._grow_store_for(fresh * T)
            lease = self.pool.lease(need, shared=shared or None)
            if lease is not None:
                pairs = lease.writable(hit, need) if hit else []
                if pairs is not None:
                    if pairs:
                        self._apply_cow(pairs)
                    return lease, hit
                lease.release()    # COW target blocks unavailable: retry
            if cache is None or cache.evict_lru_leaf() == 0:
                return None, 0

    def _apply_cow(self, pairs: list[tuple[int, int]]) -> None:
        """Materialize copy-on-write: one fused gather/scatter duplicates
        each shared source block into its private replacement *before* this
        tick's writes touch the lease.  The pair list is padded to its
        power-of-two bucket by REPEATING the last real pair — a duplicated
        copy writes identical bytes and is shape-stable, whereas a (0, 0)
        filler could collide with a real destination block."""
        n = len(pairs)
        pad = pairs + [pairs[-1]] * (_bucket(n) - n)
        src = jnp.asarray(np.asarray([p[0] for p in pad], np.int32))
        dst = jnp.asarray(np.asarray([p[1] for p in pad], np.int32))
        self.caches = self._copy_blocks(self.caches, src, dst)
        self.cow_copied_blocks += n

    # --------------------------------------------- paged KV: physical budget
    def _bt(self) -> jnp.ndarray:
        """Device block-table operand, refreshed lazily after table edits."""
        if self._bt_dirty:
            self._bt_dev = jnp.asarray(self._bt_np)
            self._bt_dirty = False
        return self._bt_dev

    def set_kv_budget(self, blocks: int) -> None:
        """Manual ``serve.kv_block_budget`` actuation (benchmarks / ops):
        preempts past occupancy and physically resizes the block store."""
        self.pool.set_budget(blocks)
        if self.paged:
            self._enforce_kv_budget()

    def _enforce_kv_budget(self) -> None:
        # a budget cut lands on the cache first: cold cached prefixes are
        # speculative capacity and yield before any live work is undone
        cache = self._prefix_cache
        while (cache is not None and self.pool.over_budget
               and cache.blocks_held > 0):
            if cache.evict_lru_leaf() == 0:
                break
        while self.pool.over_budget and (self.running or self.prefilling):
            self._preempt_lowest_priority()
        bps = self.blocks_per_seq
        target = min(-(-max(1, self.pool.max_blocks) // bps) * bps,
                     self.max_batch * bps)
        target = max(target, bps, self.pool.used_blocks)
        if target < self.pool.capacity:
            keep = jnp.asarray(self.pool.compact(target))
            self.caches = zoo.map_paged_caches(
                self.caches, lambda a, ax: jnp.take(a, keep, axis=ax))
            if self._cache_plan is not None:
                # the eager gather re-laid the stores out; re-pin the Kv-dim
                # placement before the next compiled tick consumes them
                self.caches = self._cache_plan.place(self.caches)
            for reqs in (self.prefilling, self.running):
                for slot, req in reqs.items():
                    self._bt_np[slot] = req.lease.table_row()
            self._bt_dirty = True

    def _grow_store_for(self, tokens: int) -> bool:
        need = -(-tokens // self.pool.block_tokens)
        full = self.max_batch * self.blocks_per_seq
        if (self.pool.used_blocks + need > self.pool.max_blocks
                or need > self.blocks_per_seq):
            return False   # genuinely over budget, not just store-limited
        bps = self.blocks_per_seq
        target = min(-(-(self.pool.used_blocks + need) // bps) * bps, full)
        if target <= self.pool.capacity:
            return False   # store large enough; ensure failed on budget
        head = self.accountant.headroom()
        if head is not None and (
                (target - self.pool.capacity) * self.pool.block_bytes > head):
            return False   # growing the store would blow the hard HBM goal
        added = self.pool.grow(target)

        def pad(a, ax):
            shape = list(a.shape)
            shape[ax] = added
            return jnp.concatenate([a, jnp.zeros(shape, a.dtype)], axis=ax)

        self.caches = zoo.map_paged_caches(self.caches, pad)
        if self._cache_plan is not None:
            self.caches = self._cache_plan.place(self.caches)
        return True

    def _preempt_lowest_priority(self) -> None:
        """Kick the lowest-priority sequence back to the queue — highest
        tier number first (brownout order: shed the cheapest tenants
        before premium traffic), newest-admitted within a tier
        (recompute-on-readmission, paper §4.2: the cut is enforced by
        temporarily undoing work, never by corrupting state)."""
        cands = list(self.prefilling.items()) + list(self.running.items())
        if not cands:
            return
        slot, req = max(cands, key=lambda sr: (sr[1].tier, sr[1].admit_seq))
        self._requeue_slot(slot, req)
        self.preemptions += 1
        if self._tel is not None:
            self._tel.tracer.instant(
                "preempt", args={"req": req.req_id, "tier": req.tier,
                                 "tick": self.ticks_run})
            self._tel.metrics.counter("serve.preemptions").inc()

    def _requeue_slot(self, slot: int, req: Request) -> None:
        """Undo a slot's in-flight work back to the queue head (state reset
        to prefilled=0: recompute on readmission, counted)."""
        self.prefilling.pop(slot, None)
        self.running.pop(slot, None)
        if self._drafter is not None:
            self._drafter.drop(slot)
        if req.lease is not None:
            # COW-safe: release only drops THIS lease's references — blocks
            # the radix tree still holds stay resident for future hits
            req.lease.release()
            req.lease = None
        self._free_slots.append(slot)
        self.slot_pos[slot] = -1
        if self.paged:
            self._bt_np[slot] = -1
            self._bt_dirty = True
        req.slot = None
        # cache-covered tokens were never computed, so they are not
        # recompute debt; the suffix and generated tokens are
        self.recompute_tokens += (req.prefilled - req.prefix_hit
                                  + req.gen_count)
        req.prefilled = 0
        req.prefix_hit = 0
        req.gen_count = 0
        req.generated = []
        req.preempted += 1
        self.queued.appendleft(req)
        self.queued_tokens += len(req.prompt)
        self.accountant.charge("queue", req.prompt_bytes)

    # ------------------------------------------------- worker preemption
    def _drain_for_preemption(self) -> None:
        """The serve-path answer to ``PreemptionHandler.trigger``: every
        in-flight request is requeued (newest first, so the queue keeps
        admission order), new submissions bounce with a typed reason, and
        ticks idle until the signal clears.  Nothing is lost: the queue is
        the elastic-restart handoff state."""
        in_flight = sorted(
            list(self.prefilling.items()) + list(self.running.items()),
            key=lambda sr: sr[1].admit_seq, reverse=True)
        for slot, req in in_flight:
            self._requeue_slot(slot, req)
            self.preemptions += 1
        self._draining = True
        if self._tel is not None:
            self._tel.tracer.instant(
                "worker_preemption_drain",
                args={"requeued": len(in_flight), "tick": self.ticks_run})
            self._tel.metrics.counter("serve.preemptions").inc(
                len(in_flight))

    def drained_requests(self) -> list[Request]:
        """Requests parked by a drain (queued + waiting, admission order):
        what a replacement worker resubmits after an elastic restart."""
        return list(self.queued) + list(self.waiting)

    @property
    def accepting(self) -> bool:
        """Whether ``submit`` would pass the drain gate right now: False
        from the preemption trigger until the first post-recovery tick
        clears the drain.  The replica router dispatches only to accepting
        engines, so a request is never burned on the typed ``draining``
        rejection another replica could have served."""
        return not (self._draining or self.preemption.triggered)

    def take_drained(self) -> list[Request]:
        """Hand off every parked request: the returned requests leave this
        engine's queues AND its memory ledger entirely.  The replica
        router calls this on a preempted replica after its drain tick —
        survivors resubmit the work, so a later rejoin of this engine must
        not also serve it (``drained_requests`` alone would double-serve)."""
        out = self.drained_requests()
        self.queued.clear()
        self.waiting.clear()
        self.queued_tokens = 0
        self.accountant.set("queue", 0)
        return out

    # ------------------------------------------------------------- prefill
    def _prefill_tick(self) -> None:
        if not self.prefilling:
            return
        self._prefill_tick_bucketed()

    def _record_prefill_pad(self, issued: int, live: int, segments: int):
        """Accumulates per tick: legacy mode prefills once per admitted
        request, so a tick can record several calls."""
        self.prefill_issued_tokens += issued
        self.prefill_live_tokens += live
        self._tick_issued += issued
        self._tick_live += live
        self._tick_packed_segments += segments

    @property
    def pad_fraction(self) -> float:
        """Cumulative padded-but-dead fraction of all prefill tokens issued:
        the gap between what ``serve.prefill_chunk_tokens`` claims to spend
        and the prompt tokens actually advanced (near-zero under packing).
        An engine that has issued zero prefill tokens has no padding to
        report — 0.0, not the 1.0 the old ``1 - 0/max(1, 0)`` produced."""
        if self.prefill_issued_tokens == 0:
            return 0.0
        return 1.0 - self.prefill_live_tokens / self.prefill_issued_tokens

    # --------------------------------------- unified prefill+decode stream
    def _tick_unified(self) -> int:
        """ONE ``step_packed`` dispatch advances the whole engine: prefill
        chunks from as many prefilling requests as fit under the live
        ``serve.prefill_chunk_tokens`` budget PLUS one length-1 decode
        segment per running slot, all packed into a single ``[1, width]``
        ragged stream in admission order.

        Decode tokens are mandatory riders — the split path decodes every
        running slot each tick, so token parity demands the same here —
        and they count against the literal token budget; prefill keeps a
        floor of one token per tick so a full decode batch can never
        starve it into livelock.  The stream width is the power-of-two
        bucket of the packed token count: whenever demand saturates the
        budget (the steady state under load) every tick reuses ONE
        compiled shape, and drain-tail ticks shrink to narrow shapes
        instead of issuing a mostly-dead full-width stream.  Returns the
        number of tokens generated this tick (decoders + prefill
        finishers, each of which samples from the same dispatch).

        A tick with no prefill work has nothing to fuse: it routes to the
        specialized decode program instead of padding decode tokens into a
        mostly-dead stream — still one dispatch (the split path never paid
        two on decode-only ticks either), at the decode program's exact
        cost.  The unified stream owns every tick where prefill and decode
        overlap, which is where the split path paid its second dispatch."""
        if not self.prefilling:
            return self._decode_tick()
        with self._span("pack"):
            n_dec = len(self.running)
            budget = max(1, min(int(self.prefill_chunk), self.packed_width))
            demand = sum(len(r.prompt) - r.prefilled
                         for r in self.prefilling.values())
            pre_budget = min(max(1, budget - n_dec), demand)
            # the engine's one documented width cap still applies: a saturated
            # stream on a non-power-of-two cache_len must issue packed_width
            # lanes, not the next power of two's permanently-dead padding
            width = min(_bucket(pre_budget + n_dec), self.packed_width)
            # never truncate the stream
            width = max(width, pre_budget + n_dec)
            tokens = np.zeros((1, width), np.int32)
            slot_id = np.full((width,), -1, np.int32)
            posw = np.zeros((width,), np.int32)
            start = np.zeros((self.max_batch,), np.int32)
            seg_len = np.zeros((self.max_batch,), np.int32)
            is_dec = np.zeros((width,), bool)
            sample = np.zeros((self.max_batch,), bool)
            gidx = np.full((self.max_batch,), self.cache_len, np.int32)
            done = np.zeros((self.max_batch,), bool)
            cursor = 0
            packed: list[tuple[int, Request, int]] = []
            for slot, req in sorted(self.prefilling.items(),
                                    key=lambda sr: sr[1].admit_seq):
                if cursor >= pre_budget:
                    break   # later arrivals re-pack from `prefilled` next tick
                n = min(len(req.prompt) - req.prefilled, pre_budget - cursor)
                tokens[0, cursor:cursor + n] = \
                    req.prompt[req.prefilled:req.prefilled + n]
                slot_id[cursor:cursor + n] = slot
                posw[cursor:cursor + n] = np.arange(req.prefilled,
                                                    req.prefilled + n)
                start[slot] = req.prefilled
                seg_len[slot] = n
                if req.prefilled + n >= len(req.prompt):
                    done[slot] = sample[slot] = True
                    gidx[slot] = 0               # first token -> gen ring head
                packed.append((slot, req, n))
                cursor += n
            pre_cursor = cursor
            decoders: list[tuple[int, Request]] = []
            for slot, req in sorted(self.running.items(),
                                    key=lambda sr: sr[1].admit_seq):
                # the decode token itself lives on device (_slot_tok); the
                # stream carries a placeholder the jitted step fills in
                slot_id[cursor] = slot
                posw[cursor] = int(self.slot_pos[slot])
                is_dec[cursor] = True
                start[slot] = int(self.slot_pos[slot])
                seg_len[slot] = 1
                sample[slot] = True
                # ==len => drop
                gidx[slot] = min(req.gen_count, self.cache_len)
                decoders.append((slot, req))
                cursor += 1
            self._count_kv_blocks(slot_id, posw)
        t_disp = self.clock()
        with self._span("dispatch", program="unified", width=width):
            self.caches, self._slot_tok, self._gen_buf = self._step_unified(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(slot_id), jnp.asarray(posw), jnp.asarray(start),
                jnp.asarray(seg_len), jnp.asarray(is_dec),
                jnp.asarray(sample), jnp.asarray(gidx), self._slot_tok,
                self._gen_buf, self._bt() if self.paged else None)
        self.model_dispatches += 1
        self._tick_dispatches += 1
        self._prefill_shapes.add(width)        # O(1): one packed shape
        if packed:
            self.prefill_calls += 1
            # the prefill-knob deputy counts prefill lanes only: decode
            # riders are always live and not governed by the knob
            self._record_prefill_pad(width - n_dec, pre_cursor, len(packed))
        self._tick_packed_segments += n_dec
        if n_dec or done.any():
            # a sampled token is a completion boundary: wait for the device
            # (no host transfer) so TTFT/decode latency reflect compute,
            # not async dispatch depth
            with self._span("wait"):
                self._slot_tok.block_until_ready()
        with self._span("sample"):
            if n_dec:
                dt = self.clock() - t_disp
                self.decode_latency.record(dt)
                if self._tel is not None and dt > 0.0:
                    self._tel_h_decode.record(dt)
            now = self.clock()
            for slot, req, n in packed:
                req.prefilled += n
                req.prefill_chunks += 1
                if done[slot]:
                    req.gen_count = 1            # first token is on device
                    self._stamp_first_token(req, now)
                    self.slot_pos[slot] = len(req.prompt)
                    self.running[slot] = self.prefilling.pop(slot)
                    self._cache_insert(req)
            for slot, req in decoders:
                self.slot_pos[slot] += 1
                req.gen_count += 1
        self._tick_decode = n_dec
        self._tick_decode_slots = n_dec
        n_tokens = n_dec + int(done.sum())
        if n_tokens:
            self.throughput.record(n_tokens)
        return n_tokens

    def _count_kv_blocks(self, slot_id: np.ndarray, posw: np.ndarray):
        """The tick stat ``attn_kv_blocks``: blocks the paged segment
        kernel walks for this stream, by the kernel's own tiling rule, per
        query head and averaged over the attention layers."""
        if not self.paged:
            return
        walked = sum(n * live_blocks(live_block_ranges(
            posw, slot_id, num_slots=self.max_batch,
            max_blocks=self.blocks_per_seq,
            block_tokens=self.pool.block_tokens, window=w))
            for w, n in self._attn_windows.items())
        self._tick_kv_blocks = walked / sum(self._attn_windows.values())

    # --------------------------------- speculative prefill+decode stream
    def _tick_spec(self) -> int:
        """:meth:`_tick_unified` with draft-and-verify decode segments.

        Each running slot's mandatory decode rider grows from one lane to
        ``1 + d``: the device-resident pending token followed by ``d``
        host-drafted continuations (``NGramDrafter``, deterministic), all
        verified by per-offset argmax inside the SAME compiled dispatch
        that advances prefill chunks.  Greedy acceptance keeps the longest
        matching draft prefix plus the model's own next token, so a slot
        emits ``accept + 1`` tokens per dispatch — token-identical to
        ``accept + 1`` sequential non-speculative ticks by construction
        (``models/transformer.step_spec``), and ``spec_depth == 0`` is
        exactly the unified path.  Draft lanes ride the same width budget
        prefill does; the per-slot clamp keeps every draft inside the
        request's remaining token and cache budget, so speculation can
        never over-emit or outrun the KV lease."""
        if not self.prefilling and not self.running:
            return 0
        with self._span("pack"):
            L = self._spec_len_max
            k_live = min(self.spec_depth, L - 1)
            # drafts first — the stream width depends on how many verify
            # lanes ride this tick
            drafts: list[tuple[int, Request, np.ndarray]] = []
            spec_tokens = 0
            for slot, req in sorted(self.running.items(),
                                    key=lambda sr: sr[1].admit_seq):
                d_cap = min(k_live, req.max_new_tokens - req.gen_count - 1,
                            self.cache_len - 1 - int(self.slot_pos[slot]))
                d = self._drafter.propose(slot, d_cap) if d_cap > 0 \
                    else np.zeros(0, np.int32)
                drafts.append((slot, req, d))
                spec_tokens += 1 + len(d)
            n_dec = len(drafts)
            budget = max(1, min(int(self.prefill_chunk), self.packed_width))
            demand = sum(len(r.prompt) - r.prefilled
                         for r in self.prefilling.values())
            pre_budget = min(max(1, budget - n_dec), demand) if demand else 0
            width = min(_bucket(max(1, pre_budget + spec_tokens)),
                        self.packed_width)
            width = max(width, pre_budget + spec_tokens)
            tokens = np.zeros((1, width), np.int32)
            slot_id = np.full((width,), -1, np.int32)
            posw = np.zeros((width,), np.int32)
            start = np.zeros((self.max_batch,), np.int32)
            seg_len = np.zeros((self.max_batch,), np.int32)
            is_dec = np.zeros((width,), bool)
            spec_rows = np.zeros((self.max_batch,), bool)
            sample = np.zeros((self.max_batch,), bool)
            gidx = np.full((self.max_batch,), self.cache_len, np.int32)
            spec_idx = np.zeros((self.max_batch, L), np.int32)
            draft_len = np.zeros((self.max_batch,), np.int32)
            done = np.zeros((self.max_batch,), bool)
            cursor = 0
            packed: list[tuple[int, Request, int]] = []
            for slot, req in sorted(self.prefilling.items(),
                                    key=lambda sr: sr[1].admit_seq):
                if cursor >= pre_budget:
                    break   # later arrivals re-pack from `prefilled` next tick
                n = min(len(req.prompt) - req.prefilled, pre_budget - cursor)
                tokens[0, cursor:cursor + n] = \
                    req.prompt[req.prefilled:req.prefilled + n]
                slot_id[cursor:cursor + n] = slot
                posw[cursor:cursor + n] = np.arange(req.prefilled,
                                                    req.prefilled + n)
                start[slot] = req.prefilled
                seg_len[slot] = n
                if req.prefilled + n >= len(req.prompt):
                    done[slot] = sample[slot] = True
                    gidx[slot] = 0               # first token -> gen ring head
                    # draft_len = 0, so accept = 0 and the sampled token is the
                    # argmax at the segment's last lane — the first token
                    spec_idx[slot, :] = cursor + n - 1
                packed.append((slot, req, n))
                cursor += n
            pre_cursor = cursor
            for slot, req, d in drafts:
                seg = 1 + len(d)
                spos = int(self.slot_pos[slot])
                # lane 0 carries a placeholder the jitted step fills from the
                # device token ring; drafts ride host-side
                if len(d):
                    tokens[0, cursor + 1:cursor + seg] = d
                slot_id[cursor:cursor + seg] = slot
                posw[cursor:cursor + seg] = np.arange(spos, spos + seg)
                is_dec[cursor] = True
                start[slot] = spos
                seg_len[slot] = seg
                spec_rows[slot] = sample[slot] = True
                # ==len => drop
                gidx[slot] = min(req.gen_count, self.cache_len)
                spec_idx[slot, :] = cursor + np.minimum(np.arange(L), seg - 1)
                draft_len[slot] = len(d)
                cursor += seg
            self._count_kv_blocks(slot_id, posw)
        t_disp = self.clock()
        with self._span("dispatch", program="spec", width=width):
            (self.caches, self._slot_tok, self._gen_buf, accept_d,
             toks_d) = self._step_spec(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(slot_id), jnp.asarray(posw), jnp.asarray(start),
                jnp.asarray(seg_len), jnp.asarray(is_dec),
                jnp.asarray(spec_rows), jnp.asarray(sample),
                jnp.asarray(gidx), jnp.asarray(spec_idx),
                jnp.asarray(draft_len), self._slot_tok, self._gen_buf,
                self._bt() if self.paged else None)
        self.model_dispatches += 1
        self._tick_dispatches += 1
        self._prefill_shapes.add(width)
        if packed:
            self.prefill_calls += 1
            self._record_prefill_pad(width - spec_tokens, pre_cursor,
                                     len(packed))
        self._tick_packed_segments += n_dec
        # acceptance decides how far every slot advanced: the one host sync
        # of the tick (accept + per-offset argmaxes feed the drafter)
        with self._span("fetch"):
            accept = np.asarray(accept_d)
            tks = np.asarray(toks_d)
        with self._span("sample"):
            if n_dec:
                dt = self.clock() - t_disp
                self.decode_latency.record(dt)
                if self._tel is not None and dt > 0.0:
                    self._tel_h_decode.record(dt)
            now = self.clock()
            for slot, req, n in packed:
                req.prefilled += n
                req.prefill_chunks += 1
                if done[slot]:
                    req.gen_count = 1            # first token is on device
                    self._stamp_first_token(req, now)
                    self.slot_pos[slot] = len(req.prompt)
                    self.running[slot] = self.prefilling.pop(slot)
                    self._cache_insert(req)
                    self._drafter.begin(slot, req)
                    self._drafter.extend(slot, tks[slot, :1])
            n_emitted = 0
            for slot, req, d in drafts:
                a = int(accept[slot])
                self._drafter.extend(slot, tks[slot, :a + 1])
                self.slot_pos[slot] += a + 1
                req.gen_count += a + 1
                n_emitted += a + 1
                self._tick_spec_proposed += len(d)
                self._tick_spec_accepted += a
                if self._tel is not None:
                    self._tel_h_spec.record(float(a))
            if self._tick_spec_proposed:
                self.spec_proposed += self._tick_spec_proposed
                self.spec_accepted += self._tick_spec_accepted
                self._accept_window.append((self._tick_spec_accepted,
                                            self._tick_spec_proposed))
                if self._tel is not None:
                    self._tel_c_spec_prop.inc(self._tick_spec_proposed)
                    self._tel_c_spec_acc.inc(self._tick_spec_accepted)
        self._tick_spec_lanes = spec_tokens - n_dec
        self._tick_decode = n_emitted
        self._tick_decode_slots = n_dec
        n_tokens = n_emitted + int(done.sum())
        if n_tokens:
            self.throughput.record(n_tokens)
        return n_tokens

    # ----------------------------------------------- bucketed chunked prefill
    def _prefill_tick_bucketed(self) -> None:
        """Advance every prefilling slot by one chunk in a single padded
        call.  The chunk width is the power-of-two bucket covering the
        largest chunk this tick, so mixed prompt lengths reuse compiles."""
        with self._span("pack"):
            cap = max(1, int(self.prefill_chunk))
            width = _bucket(max(min(len(r.prompt) - r.prefilled, cap)
                                for r in self.prefilling.values()))
            tokens = np.zeros((self.max_batch, width), np.int32)
            start = np.zeros((self.max_batch,), np.int32)
            lengths = np.zeros((self.max_batch,), np.int32)
            done = np.zeros((self.max_batch,), bool)
            for slot, req in self.prefilling.items():
                n = min(len(req.prompt) - req.prefilled, cap, width)
                tokens[slot, :n] = req.prompt[req.prefilled:
                                              req.prefilled + n]
                start[slot] = req.prefilled
                lengths[slot] = n
                done[slot] = req.prefilled + n >= len(req.prompt)
        with self._span("dispatch", program="prefill", width=width):
            self.caches, self._slot_tok, self._gen_buf = self._prefill_chunk(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(start), jnp.asarray(lengths), jnp.asarray(done),
                self._slot_tok, self._gen_buf,
                self._bt() if self.paged else None)
        self.prefill_calls += 1
        self.model_dispatches += 1
        self._tick_dispatches += 1
        self._prefill_shapes.add(width)
        self._record_prefill_pad(width * len(self.prefilling),
                                 int(lengths.sum()),
                                 int((lengths > 0).sum()))
        if done.any():
            # a first token is a completion boundary: wait for the device
            # (no host transfer) so TTFT reflects compute, not dispatch
            with self._span("wait"):
                self._slot_tok.block_until_ready()
        with self._span("sample"):
            now = self.clock()
            for slot in list(self.prefilling):
                req = self.prefilling[slot]
                req.prefilled += int(lengths[slot])
                req.prefill_chunks += 1
                if done[slot]:
                    req.gen_count = 1            # first token is on device
                    self._stamp_first_token(req, now)
                    self.slot_pos[slot] = len(req.prompt)
                    self.running[slot] = self.prefilling.pop(slot)
                    self._cache_insert(req)

    def _cache_insert(self, req: Request) -> None:
        """Prefill-complete hook: adopt the finished prompt's full-block
        prefix into the radix tree (one refcount per block; decode and any
        partial tail land strictly beyond the inserted blocks, so tree-held
        KV is immutable), then hold the tree to its SmartConf-actuated
        share of the block budget."""
        cache = self._prefix_cache
        if cache is None or req.lease is None:
            return
        if cache.insert(req.prompt, req.lease.blocks, self.ticks_run):
            cache.enforce(int(self.kv_cache_share * self.pool.max_blocks))

    # ------------------------------------------------ legacy one-shot prefill
    def _do_prefill_legacy(self, req: Request) -> None:
        """Exact whole-prompt prefill for the modality-frontend families the
        padded path can't serve (vision/encoder-decoder prefixes), and for
        explicit ``prefill_mode='legacy'`` baseline comparisons."""
        assert not self.paged, "legacy prefill has no paged-cache merge path"
        prompt = jnp.asarray(req.prompt[None, :], jnp.int32)
        batch = {"tokens": prompt}
        if self.cfg.frontend == "vision":
            batch["patches"] = jnp.zeros(
                (1, self.cfg.num_patches, self.cfg.frontend_dim), jnp.float32)
        if self.cfg.encoder_decoder:
            batch["frames"] = jnp.zeros(
                (1, self.cfg.enc_seq, self.cfg.d_model), jnp.float32)
        with self._span("dispatch", program="prefill",
                        width=len(req.prompt)):
            logits, one_cache = self._prefill(self.params, batch)
            self.caches = self._merge(self.caches, one_cache,
                                      jnp.asarray(req.slot, jnp.int32))
        self.prefill_calls += 1
        self.model_dispatches += 1
        self._tick_dispatches += 1
        self._prefill_shapes.add(len(req.prompt))
        self._record_prefill_pad(len(req.prompt), len(req.prompt), 1)
        with self._span("fetch"):
            first = int(jnp.argmax(logits[0]))
        self._slot_tok = self._slot_tok.at[req.slot].set(first)
        self._gen_buf = self._gen_buf.at[req.slot, 0].set(first)
        req.gen_count = 1
        req.prefilled = len(req.prompt)
        req.prefill_chunks = 1
        self._stamp_first_token(req, self.clock())
        npatch = self.cfg.num_patches if self.cfg.frontend == "vision" else 0
        self.slot_pos[req.slot] = len(req.prompt) + npatch

    # --------------------------------------------------------------- decode
    def _decode_tick(self) -> int:
        if not self.running:
            return 0
        with self._span("pack"):
            active = np.zeros((self.max_batch,), bool)
            gidx = np.full((self.max_batch,), self.cache_len, np.int32)
            for slot, req in self.running.items():
                active[slot] = True
                # ==len => drop
                gidx[slot] = min(req.gen_count, self.cache_len)
            pos = np.maximum(self.slot_pos, 0).astype(np.int32)
        # the decode-only latency sensor wraps just the dispatch + device
        # wait (no host transfer): the sc_chunk controller acting on its
        # p99 sees real decode compute, not admission/scheduling host work
        # (that whole-tick span is tick_latency's job)
        t_disp = self.clock()
        with self.decode_latency.measure():
            with self._span("dispatch", program="decode", width=None):
                self._slot_tok, self.caches, self._gen_buf = self._decode(
                    self.params, self.caches, self._slot_tok,
                    jnp.asarray(pos), jnp.asarray(active), self._gen_buf,
                    jnp.asarray(gidx), self._bt() if self.paged else None)
            with self._span("wait"):
                self._slot_tok.block_until_ready()
        if self._tel is not None:
            dt = self.clock() - t_disp
            if dt > 0.0:
                self._tel_h_decode.record(dt)
        self.model_dispatches += 1
        self._tick_dispatches += 1
        self._decode_dispatched = True
        n = 0
        with self._span("sample"):
            for slot, req in self.running.items():
                self.slot_pos[slot] += 1
                req.gen_count += 1
                n += 1
        self._tick_decode = n
        self._tick_decode_slots = n
        self.throughput.record(n)
        return n

    def _finish(self) -> None:
        done = [(s, r) for s, r in self.running.items()
                if r.gen_count >= r.max_new_tokens]
        if not done:
            return
        # completion boundary: the only device->host token sync in the loop
        with self._span("fetch"):
            gen = np.asarray(self._gen_buf)
        for slot, req in done:
            req.done_t = self.clock()
            # the prefill tick also decodes, so gen_count can overshoot
            # max_new_tokens by one — cap the readback at the request
            req.generated = [int(t) for t in
                             gen[slot, :min(req.gen_count,
                                            req.max_new_tokens)]]
            req.slo_ok = self._meets_slo(req)
            if req.slo_ok:
                self.slo_good_requests += 1
                self.slo_good_tokens += len(req.generated)
            else:
                self.slo_miss_requests += 1
                self.slo_miss_tokens += len(req.generated)
            if self._tel is not None:
                self._tel.tracer.async_end(
                    "request", req.req_id,
                    args={"slo_ok": bool(req.slo_ok),
                          "tokens": len(req.generated)})
            self.finished.append(req)
            del self.running[slot]
            self._free_slots.append(slot)
            if self._drafter is not None:
                self._drafter.drop(slot)
            if req.lease is not None:
                if self.spec_enabled:
                    # accepted-token KV only: the final sampled token was
                    # never consumed and any rejected draft tail is junk —
                    # cut both out of the lease BEFORE the radix tree may
                    # adopt its blocks, then extend the cacheable prefix
                    # with the request's own output (prompt + accepted
                    # continuation), so a repeat of this stream warm-hits
                    # past the prompt
                    valid = len(req.prompt) + max(0, len(req.generated) - 1)
                    req.lease.truncate(valid)
                    if self._prefix_cache is not None and req.generated:
                        ext = np.concatenate([
                            np.asarray(req.prompt, np.int32),
                            np.asarray(req.generated[:-1], np.int32)])
                        if self._prefix_cache.insert(ext, req.lease.blocks,
                                                     self.ticks_run):
                            self._prefix_cache.enforce(
                                int(self.kv_cache_share
                                    * self.pool.max_blocks))
                req.lease.release()
                req.lease = None
            self.slot_pos[slot] = -1
            if self.paged:
                self._bt_np[slot] = -1
                self._bt_dirty = True

    def _trim_windows(self) -> None:
        """Block-level sliding-window eviction (all-window archs only):
        blocks wholly below every live position's attention window return
        to the pool, and their table entries go to -1 — the paged gather
        masks them, so the kernel never reads a freed block.  The keep
        point is conservative by up to one block (``cur - window`` even
        mid-block) so a token still inside any window is never dropped.
        Mutually exclusive with the prefix cache: a trimmed lease's blocks
        are position-holed and cannot be adopted as a shared prefix."""
        w = int(self.cfg.window)
        T = self.pool.block_tokens
        changed = False
        for reqs in (self.prefilling, self.running):
            for slot, req in reqs.items():
                if req.lease is None:
                    continue
                cur = (int(self.slot_pos[slot])
                       if self.slot_pos[slot] >= 0 else req.prefilled)
                first_keep = max(0, cur - w) // T
                if req.lease.trim_front(first_keep):
                    self._bt_np[slot] = req.lease.table_row()
                    changed = True
        if changed:
            self._bt_dirty = True

    def _meets_slo(self, req: Request) -> bool:
        """Goodput-under-SLO membership: the request's own TTFT met the SLO
        bound and it completed inside its deadline.  Tokens served outside
        either are wasted capacity, not goodput."""
        if (req.deadline_s is not None and req.done_t is not None
                and req.done_t - req.submitted_t > req.deadline_s):
            return False
        if (self.slo is not None and req.first_token_t is not None
                and req.first_token_t - req.submitted_t > self.slo.ttft_s):
            return False
        return True

    @property
    def goodput_tokens(self) -> int:
        """Cumulative generated tokens of finished requests that met their
        SLO — the serving metric the paper's control loop optimizes for
        (raw tokens/s counts wasted work; goodput cannot)."""
        return self.slo_good_tokens

    def close(self) -> None:
        if self._closed:          # idempotent: drain paths may close twice
            return
        self._closed = True
        for sc in (self.sc_queue, self.sc_kv, self.sc_chunk, self.sc_admit,
                   self.sc_cache, self.sc_spec):
            if sc is not None:
                sc.close()
