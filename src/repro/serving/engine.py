"""Slot-based continuous batching over the compiled H²EAL step triple.

The lockstep loop in ``launch/serve.py`` forces every request in a batch
to share one prompt length and one generation length — exactly the
workload imbalance the paper's load-balancing scheduler (§IV-C) targets
at the bank level, replayed at the batch level. This engine removes the
lockstep:

  * ``BatchState`` holds a **fixed max-batch** compiled decode shape:
    per-slot caches, a per-slot ``length`` (B,) vector threaded through
    cache appends / attention validity (core/cache.py,
    core/hybrid_attention.py), a per-slot ``active`` mask, a per-slot
    ``prefilling`` mask, and a per-slot share-window ``phase``.
  * Admission comes in two modes. **Chunked** (``prefill_chunk=N``, the
    production path): a request is admitted to a free slot IMMEDIATELY
    in a ``PREFILLING`` phase — the slot's cache rows are cleared to the
    empty sentinels by one donated dynamic-slot reset, and each engine
    step feeds up to ``N`` prompt tokens (one STATIC chunk-size bucket,
    per-slot lengths dynamic) **directly into the slot's rows of the
    batched sharded state** through the layout protocol
    (core/layouts.py ``prefill_chunk``), interleaved with the normal
    ragged decode of every other slot. No decode slot ever stalls for a
    prompt: time-to-first-token is bounded by ceil(S/N) engine steps
    and inter-token latency by one chunk's compute, regardless of
    prompt length. **Prefill-then-pack** (``prefill_chunk=None``): the
    legacy monolithic admission — batch-1 prefill (one compile per
    prompt bucket) packed into a free slot with a donated
    ``dynamic_update_slice`` tree op; kept as the token-exactness
    oracle chunked admission is tested against. Recurrent mixers
    (mamba2/xlstm) resume their per-slot scan state across chunk
    boundaries (models/ssm.py, models/xlstm.py ``*_prefill_chunk``),
    so chunked admission covers every mixer.
  * Retirement flips ``active`` off; the slot's caches stay bit-stable
    (appends are masked) until the next admission resets/overwrites
    them.
  * Page selection refreshes on each slot's OWN share-window cadence
    (``phase % w == 0`` — so a slot always selects on its first decode
    step), and the ``select`` variant applies the fresh selection
    **only** to slots whose refresh is due (``need_select`` blending).
    A slot's refresh schedule is therefore a function of its own phase
    alone — its decode logits are invariant to other slots joining or
    leaving AND to how its own admission was scheduled (packed, or
    chunked at any chunk size); the co-placement exactness argument
    applied to continuous batching, tested in tests/test_serving.py.
  * The decode loop never blocks on the device: retirement is
    budget-driven, so generated tokens are left on device (one (B,)
    vector per step) and extracted once at the end of ``run()``
    (``finalize()``). The host loop dispatches steps back-to-back just
    like the lockstep driver.

After warmup (one prefill compile per prompt bucket + the two decode
variants + pack), the steady state runs with zero recompiles regardless
of how requests arrive — verified via jit cache-miss counts in
benchmarks/serve_throughput.py.

The engine runs under ANY layout registered in core/layouts.py
(AttentionLayout registry): the layout's ``plan()`` resolves and
validates the mesh, rounds the cache capacity, and decides whether the
batched state lives in a sharded placement — all at construction time,
so every layout gets the same early validation. ``coplace_shmap``
(paper §IV-B: pages sharded over the mesh 'model' axis, each device
computing partial attention for exactly the pages it stores, merged
with a cross-device log-sum-exp combine — core/hybrid_attention.py)
and ``interleave`` (paper Fig 7b: GSPMD within-page token striping) are
the sharded entries; the per-slot length/active/need_select vectors
thread straight through either decode body, and
``admission="balanced"`` adds the paper's §IV-C load balancing at the
batch dimension: queued requests are admitted in the order that keeps
per-device page load flattest (sched/balance.py). See docs/serving.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.runtime import serve as serve_rt
from repro.serving import sampling as samplib

# Profiler spans on the host: one ``engine.poll`` step span per poll()
# with a child for each phase of the step (``engine.admit``,
# ``engine.schedule``, ``engine.chunk``, ``engine.decode``,
# ``engine.sample``, ``engine.account``), and ``engine.sync``. They sit
# around phases, never inside a per-slot loop; while no profiler trace is
# being taken, each costs about a microsecond.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    """One generation request. Under packed admission
    (``prefill_chunk=None``) the ``prompt`` length must be one of the
    engine's prompt buckets (pad upstream; the padded prompt is
    canonical). Chunked admission compiles per chunk bucket instead, so
    any length in ``[1, capacity)`` is admissible unpadded."""

    uid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int
    # per-request sampling policy (serving/sampling.py). Defaults are
    # greedy argmax — bit-identical to the pre-sampling engine. The RNG
    # key stream is owned by (seed, uid), never by the slot, so traces
    # are invariant to slot churn and admission order.
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0


@dataclasses.dataclass
class Completion:
    uid: int
    prompt_len: int
    tokens: List[int]            # filled by Engine.finalize()
    admitted_step: int
    finished_step: int = -1
    first_token_step: int = -1    # EngineStats.engine_steps at first token
    admitted_engine_step: int = -1  # EngineStats.engine_steps at admission
    # device-side bookkeeping until finalize():
    _first_tok: object = None    # device scalar from the prefill logits
    _slot: int = -1
    _seq: int = -1               # admission sequence (FIFO chunk order)
    _step_idx: List[int] = dataclasses.field(default_factory=list)
    # slot index at which each trace row was emitted — recorded per row
    # (not derived from _slot at finalize) so live migration between
    # slot indices (Engine(rebalance=...)) never invalidates old rows
    _slot_idx: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineStats:
    decode_steps: int = 0
    select_steps: int = 0
    reuse_steps: int = 0
    engine_steps: int = 0        # logical steps (a fused window counts
                                 # each of its in-scan steps)
    admissions: int = 0          # requests admitted into a slot
    prefill_chunks: int = 0      # chunked-prefill steps (mixed steps;
                                 # in-scan chunk iterations count too)
    tokens_out: int = 0
    occupancy_sum: float = 0.0   # sum over steps of live-slot fraction
    wall_s: float = 0.0          # set by run()
    admission_reorders: int = 0  # balanced admission: non-FIFO picks
    # dispatch accounting (PR 10): before fused windows, decode_steps
    # doubled as the dispatch count; a fused window collapses up to w-1
    # steps into ONE dispatch, so the two are split. ``dispatches``
    # counts every jitted call the engine issues (decode, sample,
    # prefill, pack/reset, chunk, tier ops, verify, migrate — draft-
    # provider internals excluded).
    dispatches: int = 0
    fused_windows: int = 0       # fused decode-window dispatches
    fused_steps: int = 0         # decode steps consumed inside them
    # tiered residency (Engine(hot_pages=N); all counts are PAGES):
    tier_hits: int = 0           # selected pages found device-resident
    tier_misses: int = 0         # selected pages cold — filled + replayed
    tier_spills: int = 0         # pages archived to the far store
    tier_fills: int = 0          # demand fills (miss repair)
    tier_prefetch: int = 0       # speculative fills one window ahead
    # batched tier transfers (PR 10): one refresh plan = one batched
    # fill + one batched spill dispatch across every (slot, page) pair
    tier_fill_batches: int = 0   # batched fill dispatches
    tier_spill_batches: int = 0  # batched spill dispatches
    tier_gather_batches: int = 0  # batched first-spill archive gathers
    tier_batch_pages_max: int = 0  # largest single batched transfer
    # speculative decode (Engine(spec_tokens=k)):
    spec_steps: int = 0          # verify dispatches (batched steps)
    spec_slot_steps: int = 0     # per-slot verify events (accept samples)
    spec_drafted: int = 0        # draft tokens proposed (k-1 per event)
    spec_accepted: int = 0       # tokens emitted by verify steps (>= 1 each)
    # dynamic rebalancing (Engine(rebalance=...); sched/rebalance.py):
    rebalance_checks: int = 0    # planner invocations (post-cooldown)
    rebalances: int = 0          # plans applied (>= 1 migration each)
    rebalance_skipped: int = 0   # triggers rejected (cooldown/hysteresis)
    migrations: int = 0          # slot moves executed
    migrated_tokens: int = 0     # context tokens moved (traffic model)
    imbalance_pre_sum: float = 0.0   # cost imbalance at each check
    imbalance_post_sum: float = 0.0  # ... after the applied plan (if any)

    @property
    def prefills(self) -> int:
        """Deprecated pre-chunking name: the old counter conflated
        compiles, admissions, and (now) chunks — read ``admissions``
        and ``prefill_chunks`` instead."""
        return self.admissions

    @property
    def occupancy(self) -> float:
        return self.occupancy_sum / max(self.decode_steps, 1)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def steps_per_s(self) -> float:
        """Decode-step rate. Identical to ``tokens_per_s`` per slot
        without speculation; under ``spec_tokens=k`` one verify step
        emits up to k tokens per slot, so the two rates split — report
        BOTH (the PR-8 stats fix; benchmarks/serve_throughput.py)."""
        return self.decode_steps / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def engine_steps_per_s(self) -> float:
        """Logical engine-step rate (the PR-10 stats fix: decode_steps
        conflated steps with dispatches once windows fuse — this is the
        step rate, ``steps_per_dispatch`` is the fusion factor)."""
        return self.engine_steps / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def steps_per_dispatch(self) -> float:
        """Decode steps per jitted dispatch — the directly-observable
        dispatch reduction of fused decode windows (~1/2 per-step: each
        decode step costs a decode + a sample dispatch; up to ~w-1 of a
        share window rides one fused dispatch)."""
        return (self.decode_steps / self.dispatches
                if self.dispatches else 0.0)

    @property
    def tier_fill_batch_mean(self) -> float:
        """Mean pages per batched tier fill (demand + prefetch)."""
        return ((self.tier_fills + self.tier_prefetch)
                / self.tier_fill_batches if self.tier_fill_batches
                else 0.0)

    @property
    def tier_spill_batch_mean(self) -> float:
        """Mean pages per batched tier spill."""
        return (self.tier_spills / self.tier_spill_batches
                if self.tier_spill_batches else 0.0)

    @property
    def mean_accepted_len(self) -> float:
        """Mean tokens emitted per per-slot verify event (1.0 = every
        draft rejected; k = every draft accepted)."""
        return (self.spec_accepted / self.spec_slot_steps
                if self.spec_slot_steps else 0.0)

    @property
    def tier_hit_rate(self) -> float:
        seen = self.tier_hits + self.tier_misses
        return self.tier_hits / seen if seen else 1.0

    @property
    def imbalance_pre(self) -> float:
        """Mean max/mean device-compute imbalance AT rebalance checks
        (1.0 = perfectly balanced; 1.0 when no check ever ran)."""
        return (self.imbalance_pre_sum / self.rebalance_checks
                if self.rebalance_checks else 1.0)

    @property
    def imbalance_post(self) -> float:
        """Same checks, scored after the applied plan (equals the pre
        value whenever a check proposed no moves)."""
        return (self.imbalance_post_sum / self.rebalance_checks
                if self.rebalance_checks else 1.0)


@dataclasses.dataclass
class BatchState:
    """Host view of the batched serve state.

    ``serve`` is the device pytree (per-slot caches + (B,) length);
    the numpy arrays mirror per-slot scheduling metadata the host loop
    needs without device round-trips. A slot is in exactly one of four
    phases: FREE (no mask set), PREFILLING (``prefilling``; length
    counts prompt tokens fed so far), READY (``ready``; prompt done and
    first token emitted, waiting for the batch's shared refresh
    boundary), or DECODING (``active``).
    """

    serve: dict                  # model serve state, length: (B,) int32
    active: np.ndarray           # (B,) bool — decoding slots
    prefilling: np.ndarray       # (B,) bool — chunked-prefill slots
    ready: np.ndarray            # (B,) bool — awaiting phase-aligned start
    lengths: np.ndarray          # (B,) int64 — host mirror of serve length
    phase: np.ndarray            # (B,) int64 — decode steps since admission
    uid: np.ndarray              # (B,) int64 — -1 when free
    remaining: np.ndarray        # (B,) int64 — generation budget left
    prompt_left: np.ndarray      # (B,) int64 — prompt tokens not yet fed
    # per-slot sampling lanes (device arrays; serving/sampling.py). Rows
    # are (re)written eagerly at admission; ``samp_gen`` — the per-slot
    # generation index driving in-graph key derivation — additionally
    # advances inside the sample/verify jits.
    samp_base: jax.Array = None  # (B, 2) uint32 — request base keys
    samp_temp: jax.Array = None  # (B,) f32
    samp_topp: jax.Array = None  # (B,) f32
    samp_gen: jax.Array = None   # (B,) int32 — tokens sampled so far

    @property
    def max_batch(self) -> int:
        return self.active.shape[0]

    def free_slots(self) -> List[int]:
        return [i for i in range(self.max_batch)
                if not self.active[i] and not self.prefilling[i]
                and not self.ready[i]]


def jit_cache_size(fn) -> int:
    """Number of compiled entries behind a jax.jit function (recompile
    counter for the no-recompiles-after-warmup check); -1 if unknown."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def _pack_slot(big: dict, small: dict, slot):
    """Write the batch-1 serve state ``small`` into slot ``slot`` of the
    batched state ``big``. Slot index is dynamic — one compile total.

    Leaf batch axis: 1 for scan-stacked "blocks" leaves, else 0;
    "length" is scalar in ``small`` and (B,) in ``big``.
    """
    def upd(path, bg, sm):
        ps = jax.tree_util.keystr(path)
        if ps.endswith("['length']"):
            return jax.lax.dynamic_update_slice(
                bg, jnp.reshape(sm, (1,)).astype(bg.dtype), (slot,))
        axis = 1 if "['blocks']" in ps else 0
        start = (0,) * axis + (slot,) + (0,) * (bg.ndim - axis - 1)
        return jax.lax.dynamic_update_slice(bg, sm.astype(bg.dtype), start)

    return jax.tree_util.tree_map_with_path(upd, big, small)


def _reset_slot(big: dict, slot):
    """Clear slot ``slot`` of the batched serve state to the EMPTY-cache
    sentinels (±inf page metadata, -1 page_start / ring positions, zeros
    elsewhere, length 0) — the state a fresh PagedCache/StreamCache
    constructor produces. Chunked admission starts from this clean row so
    no stale token of a previous occupant can pass a validity mask and
    the incremental chunk-append min/max metadata merge is exact. Slot
    index is dynamic — one compile total, mirroring ``_pack_slot``.
    """
    from repro.core import cache as cachelib

    def upd(path, bg):
        ps = jax.tree_util.keystr(path)
        if ps.endswith("['length']"):
            return jax.lax.dynamic_update_slice(
                bg, jnp.zeros((1,), bg.dtype), (slot,))
        axis = 1 if "['blocks']" in ps else 0
        row_shape = bg.shape[:axis] + (1,) + bg.shape[axis + 1:]
        row = jnp.full(row_shape, cachelib.empty_fill_value(ps), bg.dtype)
        start = (0,) * axis + (slot,) + (0,) * (bg.ndim - axis - 1)
        return jax.lax.dynamic_update_slice(bg, row, start)

    return jax.tree_util.tree_map_with_path(upd, big)


class Engine:
    """Continuous-batching engine. See module docstring.

    Parameters
    ----------
    cfg, params : model config + parameters.
    max_batch   : number of slots (the compiled decode batch).
    capacity    : max context tokens any slot may reach (cache size).
    prompt_buckets : allowed prompt lengths; one prefill compile each
                  (packed mode). Chunked mode compiles per CHUNK bucket,
                  not per prompt bucket, so any prompt length below
                  capacity is admissible — the buckets then only size
                  the state-shape probe and remain the benchmark's
                  workload vocabulary.
    prefill_chunk : per-step chunked-prefill token budget (the static
                  chunk-size bucket). None (default) = legacy
                  prefill-then-pack admission. With an int N, admission
                  is immediate (PREFILLING phase) and each engine step
                  feeds at most N prompt tokens across the prefilling
                  slots, interleaved with the decode of every other
                  slot — bounded time-to-first-token and no decode
                  stall on long prompts. Works with every mixer
                  (recurrent mixers resume their per-slot scan state);
                  requires token prompts (frontend-stub archs keep
                  packed admission).
    impl        : attention kernel implementation, ``"ref"`` (pure-jnp
                  oracle) or ``"pallas"`` (Pallas kernels; interpret mode
                  on CPU). Validated and BAKED INTO the compiled step
                  functions here at construction — impl switching never
                  happens per step, so the zero-recompile invariant is
                  unaffected (docs/serving.md). Exposed as ``--attn-impl``
                  by launch/serve.py and benchmarks/serve_throughput.py.
    layout      : serve-cache layout name, resolved through the
                  core/layouts registry (unknown names raise listing the
                  registered layouts). ``None`` is a deprecated alias for
                  ``"default"``. The layout's ``plan()`` runs here at
                  construction: it resolves/validates the mesh, rounds
                  the cache capacity to the layout's quantum, and decides
                  whether the batched state is device_put into a sharded
                  placement — so a layout whose mesh requirements aren't
                  met fails NOW, not at the first decode step.
    mesh        : mesh override for sharded layouts (each layout builds
                  its own host-local default). Every jitted call runs
                  inside this mesh's context so shard_map / GSPMD paths
                  can see it.
    admission   : ``"fifo"`` (default) or ``"balanced"`` — balanced looks
                  at the first ``admit_lookahead`` queued requests and
                  admits the one that keeps per-device page load most
                  balanced (sched/balance.admission_score; the paper's
                  §IV-C balancing applied to the batch dimension). Under
                  a tiered engine the score caps each slot's pages at
                  ``hot_pages`` — admission scores hot-set size, not
                  total pages.
    hot_pages   : per-slot device-resident page budget enabling TIERED
                  residency (None = all-resident). Cold pages spill to
                  the host far store (the simulated HB far bank); the
                  engine prefetches the hottest cold pages one share
                  window ahead of each selection refresh, detects
                  selected-but-cold pages via the metadata-only
                  selection, and serves them late (fill + replay) —
                  token traces are bit-identical to the all-resident
                  engine (docs/serving.md §Tiered residency). Counted
                  in ``EngineStats.tier_*``.
    spec_tokens : draft length k enabling SPECULATIVE decoding: each
                  decode step drafts k-1 tokens per active slot
                  (serving/draft.py), verifies all k in ONE chunked
                  forward at the static (B, k) bucket (the PR-6
                  pre-append chunk path), and accepts via coupled
                  rejection sampling — lossless, so traces (greedy AND
                  stochastic) are identical to ``spec_tokens=None``
                  (docs/serving.md §Speculative decode). Only accepted
                  prefixes are ever appended (attend-before-append; tau
                  scatter-min/max is not invertible, so there is nothing
                  to roll back). Requires all-attention mixers, full
                  attention pattern, H²EAL enabled, token prompts, no
                  tiering, and 1 <= k <= h2eal.local (the verify chunk
                  tail must fit the local window).
    draft       : DraftProvider instance or builtin name — ``"ngram"``
                  (host prompt-lookup, deterministic, default) or
                  ``"streaming"`` (self-draft on the model's streaming
                  heads). Ignored without ``spec_tokens``.
    rebalance   : dynamic load rebalancing trigger — ``"off"`` (default),
                  ``"retire"`` (re-plan when a slot retires: the moment
                  drift appears), or ``"interval"`` (every
                  ``rebalance_interval`` engine steps). A triggered check
                  scores every live slot's next-step compute
                  (sched/cost.CostModel: streaming/retrieval head mix,
                  hot-capped page reads, spec-verify horizon, chunked
                  prefill backlog) and migrates slots into free indices
                  via greedy-LPT (sched/rebalance.plan_rebalance) when
                  that flattens per-bank compute by at least
                  ``rebalance_min_gain`` (hysteresis), at most once per
                  ``rebalance_cooldown`` engine steps. Migration copies
                  the slot's cache rows / sampling lanes / tier residency
                  verbatim through ONE donated jit with dynamic indices
                  — token traces are bit-exact and the zero-recompile
                  invariant holds (docs/serving.md §Rebalancing).
    rebalance_banks : bank count the compute loads aggregate over
                  (contiguous slot-index blocks — the batch-axis sharding
                  view). Default: the layout's ``balance_shards`` when
                  sharded, else one bank per two slots (capped at 4) so
                  LPT can pair heavy slots with light ones within a bank.
    decode_window : fused decode-window length w enabling ONE-dispatch
                  execution of the reuse steps between two selection
                  boundaries: a ``lax.scan`` over the reuse step body
                  with sampling folded in-scan and device-side
                  retirement via a sched-computed per-slot budget vector
                  (sched/windows.py) — the host learns of retirements
                  only at the window boundary, where READY admission and
                  rebalance checks already live. Token traces are
                  bit-identical to per-step dispatch (the scanned body
                  IS the per-step program). None/1 = per-step dispatch
                  (the default, unchanged). Composes with chunked
                  prefill (the prefilling slots' chunk schedule is
                  presimulated on the host and threaded through the
                  scan) and with tiered residency (reuse steps only read
                  pinned-resident pages, so a fused window can never
                  cold-miss — the selection step stays per-step and
                  handles miss-replay). INCOMPATIBLE with
                  ``spec_tokens`` (verify steps advance phases by
                  variable accepted counts; the per-step fallback must
                  be requested explicitly by passing decode_window=None)
                  — validated here, never a silent fallback. See
                  docs/serving.md §Fused decode windows.
    """

    def __init__(self, cfg: ArchConfig, params, *, max_batch: int,
                 capacity: int, prompt_buckets: Sequence[int],
                 impl: str = "ref", layout: Optional[str] = "default",
                 mesh=None, admission: str = "fifo",
                 admit_lookahead: int = 4,
                 balance_shards: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 hot_pages: Optional[int] = None,
                 spec_tokens: Optional[int] = None,
                 draft="ngram",
                 rebalance: str = "off",
                 rebalance_interval: int = 16,
                 rebalance_min_gain: float = 0.02,
                 rebalance_cooldown: int = 8,
                 rebalance_banks: Optional[int] = None,
                 decode_window: Optional[int] = None):
        from repro.core import layouts as layoutlib
        from repro.kernels.ops import resolve_impl

        self.cfg = cfg
        self.params = params
        self.attn_impl = resolve_impl(impl)   # raises on unknown impls
        self.layout = layoutlib.resolve_layout(layout)  # raises on unknown
        # construction-time layout planning: mesh resolution/validation,
        # capacity rounding, sharded-state requirements — every layout
        # (not just coplace_shmap) gets the same early validation
        self.plan = layoutlib.get_layout(self.layout).plan(cfg, mesh)
        self.mesh = self.plan.mesh
        assert admission in ("fifo", "balanced"), admission
        self.admission = admission
        self.admit_lookahead = max(int(admit_lookahead), 1)
        # shard count the balanced admission scores against; defaults to
        # the layout plan's (1 → FIFO). Override for an engine whose
        # pages are sharded externally (or in tests).
        self.balance_shards = balance_shards
        self.capacity = int(capacity)
        # the sharded cache needs a whole number of pages per device; the
        # retirement boundary stays at the caller's `capacity`
        self.cache_capacity = self.plan.round_capacity(self.capacity)
        self.prompt_buckets = tuple(sorted(int(b) for b in prompt_buckets))
        assert self.prompt_buckets, "need at least one prompt bucket"
        assert self.prompt_buckets[-1] < self.capacity, (
            f"largest prompt bucket {self.prompt_buckets[-1]} must leave "
            f"room to decode within capacity {self.capacity}")
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None:
            assert self.prefill_chunk >= 1, prefill_chunk
            if cfg.embed_frontend_stub:
                raise ValueError(
                    "chunked prefill feeds token chunks through the "
                    "embedding; frontend-stub archs (vlm/audio) need "
                    "prefill_chunk=None (prefill-then-pack)")
        self.share_window = max(cfg.h2eal.share_window, 1)
        self.spec_tokens = int(spec_tokens) if spec_tokens else None
        self.draft = None
        if self.spec_tokens is not None:
            from repro.configs.base import (ATTN_LOCAL_GLOBAL,
                                            MIXER_ATTENTION)
            from repro.serving import draft as draftlib
            # the verify chunk runs the attention decode body only: no
            # recurrent-mixer chunk resume, no local-global windows, and
            # the chunk tail must fit inside every later query's local
            # window (k <= h2eal.local — the no-extra-pages gather
            # argument in core/paging.verify_token_validity)
            if cfg.mixer_pattern and any(m != MIXER_ATTENTION
                                         for m in cfg.mixer_pattern):
                raise ValueError(
                    "spec_tokens requires all-attention mixers; "
                    f"mixer_pattern={cfg.mixer_pattern}")
            if cfg.attn_pattern == ATTN_LOCAL_GLOBAL:
                raise ValueError(
                    "spec_tokens requires the full attention pattern "
                    "(local_global windows have no verify-chunk path)")
            if not cfg.h2eal.enabled:
                raise ValueError("spec_tokens requires h2eal.enabled")
            if cfg.embed_frontend_stub:
                raise ValueError(
                    "spec_tokens feeds token chunks through the "
                    "embedding; frontend-stub archs are unsupported")
            if hot_pages:
                raise ValueError(
                    "spec_tokens is incompatible with tiered residency "
                    "(the verify jit donates its input state; miss "
                    "repair needs it preserved)")
            if not 1 <= self.spec_tokens <= cfg.h2eal.local:
                raise ValueError(
                    f"spec_tokens={self.spec_tokens} must be in "
                    f"[1, h2eal.local={cfg.h2eal.local}]")
            self.draft = draftlib.resolve_draft(draft)
        self.decode_window = 1 if decode_window is None else int(decode_window)
        if self.decode_window < 1:
            raise ValueError(
                f"decode_window={decode_window} must be >= 1 "
                "(1 == per-step dispatch)")
        if self.decode_window > 1 and self.spec_tokens is not None:
            # verify steps advance each slot's phase by a VARIABLE
            # accepted count, so a fixed-budget in-scan window cannot
            # encode the stop conditions. The per-step fallback must be
            # chosen by the caller, never silently substituted.
            raise ValueError(
                "decode_window > 1 is incompatible with spec_tokens "
                "(verify steps advance phases by variable accepted "
                "counts); pass decode_window=None for per-step dispatch")
        if rebalance not in ("off", "retire", "interval"):
            raise ValueError(
                f"rebalance={rebalance!r}: valid triggers are "
                "'off', 'retire', 'interval'")
        self.rebalance = rebalance
        self.rebalance_interval = max(int(rebalance_interval), 1)
        self.rebalance_min_gain = float(rebalance_min_gain)
        self.rebalance_cooldown = max(int(rebalance_cooldown), 0)
        if rebalance_banks is not None:
            self.rebalance_banks = min(max(int(rebalance_banks), 1),
                                       int(max_batch))
        else:
            # one bank per TWO slot indices: a bank block must hold at
            # least two slots for LPT to pair a heavy slot with a light
            # one (n_banks == max_batch degenerates to pure permutations
            # — zero gain, always rejected by hysteresis)
            nb = (self.plan.balance_shards if self.plan.balance_shards > 1
                  else max(min(int(max_batch) // 2, 4), 1))
            self.rebalance_banks = min(nb, int(max_batch))
        self._cost_model = None
        if self.rebalance != "off":
            from repro.sched.cost import CostModel
            self._cost_model = CostModel.from_config(
                cfg, hot_cap=int(hot_pages) if hot_pages else None,
                spec_tokens=int(spec_tokens) if spec_tokens else 0,
                chunk_budget=self.prefill_chunk or 0)
        self._rebalance_due = False
        self._last_rebalance_step = -(1 << 30)
        scfg = serve_rt.ServeConfig(capacity=self.cache_capacity,
                                    layout=self.layout, impl=self.attn_impl)
        self._prefill = jax.jit(serve_rt.make_prefill(cfg, scfg))
        self.batch = self._init_batch_state(max_batch)
        # Under a sharded layout the batched state must live in ONE stable
        # sharded placement from step 0: otherwise the first decode
        # reshards it (unsharded zeros in, sharded layout out) and
        # pack/decode each compile a second entry AFTER warmup. Pinning
        # out_shardings keeps every steady-state call on a single
        # compiled program — for the chunk/reset admission ops too.
        dec_shard = {}
        reset_shard = {}
        self.hot_pages = int(hot_pages) if hot_pages else None
        # _pack_slot/_reset_slot are module-level, and jax.jit keys its
        # cache on the wrapped callable: jitting them directly would share
        # one cache across every Engine in the process, so another
        # engine's state pytree (e.g. a recurrent mixer's scan state)
        # would show up in this engine's jit_cache_sizes() recompile
        # counter. A fresh per-instance wrapper keeps the cache private.
        def _pack_fn(big, small, slot):
            return _pack_slot(big, small, slot)

        def _reset_fn(big, slot):
            return _reset_slot(big, slot)
        if self.plan.shard_state:
            from repro.runtime import sharding as shardlib
            ss = self.plan.state_shardings(cfg, self.batch.serve,
                                           batch_size=max_batch)
            self.batch.serve = jax.device_put(self.batch.serve, ss)
            # weights replicated on the mesh once, here: left on the
            # default device, every sharded step would re-broadcast them
            self.params = jax.device_put(params,
                                         shardlib.replicated(self.mesh))
            dec_shard = {"out_shardings":
                         shardlib.serve_step_out_shardings(self.mesh, ss)}
            reset_shard = {"out_shardings": ss}
            self._pack = jax.jit(_pack_fn, donate_argnums=(0,),
                                 out_shardings=ss)
        else:
            self._pack = jax.jit(_pack_fn, donate_argnums=(0,))
        # tiered mode keeps the select step's INPUT state alive: the
        # engine may have to fill cold-missed pages into it and replay
        # the same step (miss repair), so the select jit must not donate.
        # Reuse steps never miss (every page a reuse step reads is
        # pinned resident), so they keep the donation.
        sel_donate = {} if self.hot_pages else {"donate_argnums": (1,)}
        self._dec_sel = jax.jit(
            serve_rt.make_ragged_decode_step(cfg, scfg, do_select=True),
            **sel_donate, **dec_shard)
        self._dec_reuse = jax.jit(
            serve_rt.make_ragged_decode_step(cfg, scfg, do_select=False),
            donate_argnums=(1,), **dec_shard)
        # per-slot sampling (always on; temp=0 rows take the argmax lane
        # bit-identically) + the speculative verify step (PR 8). Draft
        # providers reuse these out_shardings dicts for their own jits.
        self._dec_out_shard = dec_shard
        self._state_out_shard = reset_shard
        samp_shard = {}
        ver_shard = {}
        if self.plan.shard_state:
            rep = shardlib.replicated(self.mesh)
            samp_shard = {"out_shardings": (rep, rep)}
            ver_shard = {"out_shardings":
                         shardlib.verify_step_out_shardings(self.mesh, ss)}
        self._sample = jax.jit(serve_rt.make_sample_step(cfg, scfg),
                               **samp_shard)

        def _sample_one_fn(logits, base, gen, temp, topp):
            with jax.named_scope("sample"):
                return samplib.sample_tokens(logits[None], base[None],
                                             gen[None], temp[None],
                                             topp[None])[0]
        self._sample_one = jax.jit(_sample_one_fn)
        # fused decode windows (PR 10): the reuse steps between two
        # selection boundaries collapse into ONE dispatched lax.scan
        # (runtime/serve.make_fused_window_step, routed through the
        # layout registry's decode_window hook). Built only when a
        # window can hold a reuse step at all (share_window > 1); the
        # selection step itself always stays per-step — it carries the
        # tiered miss-replay and the host-visible refresh digest.
        self._fused = None
        self._fused_mix = None
        self._fused_len = 0
        if self.decode_window > 1 and self.share_window > 1:
            self._fused_len = min(self.decode_window,
                                  self.share_window - 1)
            fw_shard = {}
            if self.plan.shard_state:
                fw_shard = {"out_shardings":
                            shardlib.fused_window_out_shardings(
                                self.mesh, ss)}
            self._fused = jax.jit(
                serve_rt.make_fused_window_step(
                    cfg, scfg, window=self._fused_len),
                donate_argnums=(1,), **fw_shard)
            if self.prefill_chunk is not None:
                self._fused_mix = jax.jit(
                    serve_rt.make_fused_window_step(
                        cfg, scfg, window=self._fused_len,
                        chunk=self.prefill_chunk),
                    donate_argnums=(1,), **fw_shard)
        self._migrate = None
        if self.rebalance != "off":
            # live slot migration (sched/rebalance.py): copy every
            # serve-state row src→dst (the _pack_slot leaf-axis
            # conventions), clear src to the empty sentinels (the
            # _reset_slot body), and move the sampling lanes + pending
            # token feed alongside — ONE donated jit with dynamic
            # indices, so any number of moves reuses a single compiled
            # entry. The token feed is NOT donated: _trace rows alias
            # the same array and finalize() reads them later.
            def _migrate_fn(big, tok, base, temp, topp, gen, src, dst):
                def move(path, bg):
                    ps = jax.tree_util.keystr(path)
                    if ps.endswith("['length']"):
                        row = jax.lax.dynamic_slice(bg, (src,), (1,))
                        return jax.lax.dynamic_update_slice(bg, row,
                                                            (dst,))
                    axis = 1 if "['blocks']" in ps else 0
                    sizes = bg.shape[:axis] + (1,) + bg.shape[axis + 1:]
                    s0 = (0,) * axis + (src,) + (0,) * (bg.ndim - axis - 1)
                    d0 = (0,) * axis + (dst,) + (0,) * (bg.ndim - axis - 1)
                    row = jax.lax.dynamic_slice(bg, s0, sizes)
                    return jax.lax.dynamic_update_slice(bg, row, d0)
                big = jax.tree_util.tree_map_with_path(move, big)
                big = _reset_slot(big, src)

                def lane(a, fill=0):
                    row = jax.lax.dynamic_slice_in_dim(a, src, 1, 0)
                    a = jax.lax.dynamic_update_slice_in_dim(a, row, dst, 0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        a, jnp.full(row.shape, fill, a.dtype), src, 0)
                return (big, lane(tok), lane(base), lane(temp),
                        lane(topp, 1), lane(gen))
            mig_shard = {}
            if self.plan.shard_state:
                mig_shard = {"out_shardings":
                             (ss, rep, rep, rep, rep, rep)}
            self._migrate = jax.jit(_migrate_fn,
                                    donate_argnums=(0, 2, 3, 4, 5),
                                    **mig_shard)
        self._samp_host: Dict[int, tuple] = {}   # slot -> (base, t, p)
        self._verify = None
        if self.spec_tokens is not None:
            self._verify = jax.jit(
                serve_rt.make_verify_step(cfg, scfg, k=self.spec_tokens),
                donate_argnums=(1,), **ver_shard)
            self._spec_history: Dict[int, List[int]] = {}
            self._spec_emitted = np.zeros((max_batch,), np.int64)
        self._tier = None
        self._tier_plan = None       # pending (need, sel, hotness) refresh
        if self.hot_pages is not None:
            self._init_tier(reset_shard)
        if self.prefill_chunk is not None:
            self._chunk = jax.jit(
                serve_rt.make_prefill_chunk_step(
                    cfg, scfg, chunk=self.prefill_chunk),
                donate_argnums=(1,), **dec_shard)
            self._reset = jax.jit(_reset_fn, donate_argnums=(0,),
                                  **reset_shard)
        self._tok = jnp.zeros((max_batch,), jnp.int32)   # next-token feed
        self._act_dev = jnp.zeros((max_batch,), bool)    # device active mask
        self._act_mirror = np.zeros((max_batch,), bool)  # host copy of it
        # device-side token trace: a list of (k, B) row BLOCKS (one row
        # per per-step decode, k rows per verify step, up to window rows
        # per fused window); finalize() concatenates. _trace_rows is the
        # running row count — Completion._step_idx indexes rows, so it
        # must never be derived from len(_trace) or decode_steps.
        self._trace: List[jax.Array] = []
        self._trace_rows = 0
        # engine_steps watermark from the previous step() — the interval
        # rebalance trigger fires on CROSSING a multiple of the interval
        # (identical to `% == 0` per-step; a fused window can jump past
        # the multiple without ever landing on it)
        self._prev_engine_steps = 0
        self._prompts: Dict[int, np.ndarray] = {}        # slot -> prompt
        self._admit_seq = 0                              # FIFO chunk order
        self._queue: deque[Request] = deque()
        self._live: Dict[int, Completion] = {}       # slot -> in-flight
        self.completions: Dict[int, Completion] = {}  # uid -> finished
        self.stats = EngineStats()

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------

    def _mesh_ctx(self):
        """Ambient-mesh context for jitted calls: the shard_map co-placement
        path discovers the mesh at trace time (runtime/hints.current_mesh),
        so every prefill/decode/pack dispatch runs inside it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _init_batch_state(self, max_batch: int) -> BatchState:
        """All-free batched state. Cache contents are irrelevant until a
        slot is admitted (pack overwrites every leaf row), so zeros are
        fine — validity masks keep the math NaN-free."""
        cfg = self.cfg
        if cfg.embed_frontend_stub:
            probe = jax.ShapeDtypeStruct(
                (max_batch, self.prompt_buckets[0], cfg.d_model), jnp.float32)
        else:
            probe = jax.ShapeDtypeStruct(
                (max_batch, self.prompt_buckets[0]), jnp.int32)
        shapes = jax.eval_shape(
            lambda p, b: M.prefill(cfg, p, b, capacity=self.cache_capacity),
            self.params, probe)[1]
        serve = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        serve["length"] = jnp.zeros((max_batch,), jnp.int32)
        return BatchState(
            serve=serve,
            active=np.zeros((max_batch,), bool),
            prefilling=np.zeros((max_batch,), bool),
            ready=np.zeros((max_batch,), bool),
            lengths=np.zeros((max_batch,), np.int64),
            phase=np.zeros((max_batch,), np.int64),
            uid=np.full((max_batch,), -1, np.int64),
            remaining=np.zeros((max_batch,), np.int64),
            prompt_left=np.zeros((max_batch,), np.int64),
            samp_base=jnp.zeros((max_batch, 2), jnp.uint32),
            samp_temp=jnp.zeros((max_batch,), jnp.float32),
            samp_topp=jnp.ones((max_batch,), jnp.float32),
            samp_gen=jnp.zeros((max_batch,), jnp.int32),
        )

    # ------------------------------------------------------------------
    # tiered residency (hot/cold KV pages; core/cache.TieredPagedCache)
    # ------------------------------------------------------------------

    def _init_tier(self, reset_shard: dict):
        from repro.core import cache as cachelib

        flat = jax.tree_util.tree_flatten_with_path(self.batch.serve)[0]
        kv = [(jax.tree_util.keystr(p), leaf) for p, leaf in flat
              if jax.tree_util.keystr(p).endswith(".k_pages")]
        if not kv:
            raise ValueError(
                "hot_pages tiering requires a paged retrieval-head cache; "
                "this config's serve state has no k_pages leaves")
        ps, leaf = kv[0]
        n_pages = leaf.shape[cachelib._leaf_batch_axis(ps) + 2]
        if not 1 <= self.hot_pages <= n_pages:
            raise ValueError(
                f"hot_pages={self.hot_pages} must be in [1, {n_pages}] "
                f"(cache capacity {self.cache_capacity} holds {n_pages} "
                f"pages of {self.cfg.h2eal.page_size})")
        h2 = self.cfg.h2eal
        self._tier = cachelib.TieredPagedCache(
            n_slots=self.batch.max_batch, n_pages=n_pages,
            hot_pages=self.hot_pages, page_size=h2.page_size,
            sink=h2.sink, local=h2.local,
            stripe_shards=self.plan.page_stripe_shards)

        # every batched transfer pads its (slot, page) pair vectors to
        # ONE static capacity, so any refresh plan — one page or the
        # whole cache — reuses a single compiled entry per op
        self._tier_pair_cap = self.batch.max_batch * n_pages

        # per-instance wrappers: keep each engine's jit caches private
        # (the _pack_fn rationale above)
        def _gather_fn(state, slots, pages):
            return cachelib.gather_kv_rows_pairs(state, slots, pages)

        def _spill_fn(state, slots, pages):
            return cachelib.spill_kv_rows_pairs(state, slots, pages)

        def _fill_fn(state, slots, pages, rows):
            return cachelib.fill_kv_rows_pairs(state, slots, pages, rows)

        self._tier_gather = jax.jit(_gather_fn)
        self._tier_spill = jax.jit(_spill_fn, donate_argnums=(0,),
                                   **reset_shard)
        self._tier_fill = jax.jit(_fill_fn, donate_argnums=(0,),
                                  **reset_shard)

    def _tier_digest(self, serve, need: np.ndarray):
        """Read back the fresh selection + accumulated page hotness for
        the slots that refreshed this step (one device_get per select
        step — the only host sync tiering adds). Returns
        ``(sel_by_slot, hot_by_slot)``: physical page-index sets and
        (n_pages,) importance sums, summed over layers and heads."""
        t = self._tier
        flat = jax.tree_util.tree_flatten_with_path(serve)[0]
        sel_leaves, imp_leaves = {}, {}
        for path, leaf in flat:
            ps = jax.tree_util.keystr(path)
            if ps.endswith(".sel_idx"):
                sel_leaves[ps] = leaf
            elif ps.endswith(".importance"):
                imp_leaves[ps] = leaf
        got_sel, got_imp = jax.device_get((sel_leaves, imp_leaves))
        sel_by, hot_by = {}, {}
        for slot in np.nonzero(need)[0]:
            slot = int(slot)
            sel: set = set()
            for ps, a in got_sel.items():
                ax = 1 if "['blocks']" in ps else 0
                v = np.moveaxis(a, ax, 0)[slot]
                sel.update(int(x) for x in v.ravel()
                           if 0 <= x < t.n_pages)
            hot = np.zeros((t.n_pages,), np.float64)
            for ps, a in got_imp.items():
                ax = 1 if "['blocks']" in ps else 0
                v = np.moveaxis(a, ax, 0)[slot]
                hot += np.asarray(v, np.float64).reshape(-1, t.n_pages
                                                         ).sum(axis=0)
            sel_by[slot], hot_by[slot] = sel, hot
        return sel_by, hot_by

    def _tier_pair_vectors(self, pairs):
        """(slot, page) pairs padded (-1) to the static pair capacity —
        one compiled entry per transfer op regardless of batch size."""
        m = self._tier_pair_cap
        assert len(pairs) <= m, (len(pairs), m)
        slots = np.full((m,), -1, np.int32)
        pages = np.full((m,), -1, np.int32)
        for i, (s, p) in enumerate(pairs):
            slots[i] = s
            pages[i] = p
        return jnp.asarray(slots), jnp.asarray(pages)

    def _tier_fill_work(self, serve, work, *, prefetch: bool):
        """Restore far-store rows onto the device for EVERY (slot, pages)
        entry of ``work`` in ONE batched scatter (demand fill on a cold
        miss, or speculative prefetch one share window ahead). Every
        filled page was spilled earlier, so its rows are in the far
        store by construction."""
        t = self._tier
        pairs = [(int(s), int(p)) for s, pg in work for p in pg]
        slots, pages = self._tier_pair_vectors(pairs)
        template = t.far[pairs[0]]
        rows = {ps: np.zeros((self._tier_pair_cap,) + r.shape, r.dtype)
                for ps, r in template.items()}
        for i, key in enumerate(pairs):
            for ps, r in t.far[key].items():
                rows[ps][i] = r
        serve = self._tier_fill(
            serve, slots, pages,
            {ps: jnp.asarray(v) for ps, v in rows.items()})
        self.stats.dispatches += 1
        self.stats.tier_fill_batches += 1
        self.stats.tier_batch_pages_max = max(
            self.stats.tier_batch_pages_max, len(pairs))
        for s, p in pairs:
            t.resident[s, p] = True
        if prefetch:
            self.stats.tier_prefetch += len(pairs)
        else:
            self.stats.tier_fills += len(pairs)
        return serve

    def _tier_spill_work(self, serve, work):
        """Archive EVERY (slot, pages) entry of ``work`` to the far store
        (first spill of a page gathers its rows off device — one batched
        gather for all first-timers; later spills reuse the archived
        copy, complete pages never change) and zero the device rows in
        ONE batched scatter."""
        t = self._tier
        pairs = [(int(s), int(p)) for s, pg in work for p in pg]
        to_gather = [key for key in pairs if key not in t.far]
        if to_gather:
            gs, gp = self._tier_pair_vectors(to_gather)
            rows = jax.device_get(self._tier_gather(serve, gs, gp))
            self.stats.dispatches += 1
            self.stats.tier_gather_batches += 1
            t.store_pair_rows([s for s, _ in to_gather],
                              [p for _, p in to_gather], rows,
                              len(to_gather))
        slots, pages = self._tier_pair_vectors(pairs)
        serve = self._tier_spill(serve, slots, pages)
        self.stats.dispatches += 1
        self.stats.tier_spill_batches += 1
        self.stats.tier_batch_pages_max = max(
            self.stats.tier_batch_pages_max, len(pairs))
        for s, p in pairs:
            t.resident[s, p] = False
        self.stats.tier_spills += len(pairs)
        return serve

    def _tier_select(self, need: np.ndarray, need_dev, act_dev):
        """Tiered select step: dispatch (non-donated), read back the
        metadata-only selection, and — if any selected page is cold —
        fill it into the PRESERVED input state and replay the step,
        until a pass selects only resident pages. A layer's selection
        depends on tau metadata + page_start + its q, never on page
        contents, but its q depends on the earlier layers' attention:
        a pass that read a cold page can select differently in deeper
        layers, so one replay is not always enough. The clean pass is
        exactly the all-resident step: misses are served late, never
        skipped, and its selection is what the refresh plan keeps
        resident."""
        b = self.batch
        first = True
        while True:
            logits, serve2 = self._dec_sel(self.params, b.serve, self._tok,
                                           act_dev, need_dev)
            self.stats.dispatches += 1
            sel_by, hot_by = self._tier_digest(serve2, need)
            miss_work = []
            for slot in np.nonzero(need)[0]:
                slot = int(slot)
                missing = self._tier.missing(slot, sel_by[slot])
                if first:
                    self.stats.tier_hits += (len(sel_by[slot])
                                             - len(missing))
                self.stats.tier_misses += len(missing)
                if missing:
                    miss_work.append((slot, missing))
            if not miss_work:
                break
            first = False
            # every missed slot's repair rides ONE batched fill (PR 10)
            b.serve = self._tier_fill_work(b.serve, miss_work,
                                           prefetch=False)
        self._tier_plan = (need.copy(), sel_by, hot_by)
        return logits, serve2

    def _tier_refresh(self):
        """Post-step residency refresh for the slots that just selected:
        prefetch the hottest cold pages (one share window ahead of their
        NEXT selection) and spill resident candidates that fell out of
        the hot set."""
        need, sel_by, hot_by = self._tier_plan
        self._tier_plan = None
        b = self.batch
        fill_work, spill_work = [], []
        for slot in np.nonzero(need)[0]:
            slot = int(slot)
            if not b.active[slot]:          # retired this step
                continue
            to_fill, to_spill = self._tier.plan_refresh(
                slot, int(b.lengths[slot]), sel_by[slot], hot_by[slot])
            if to_fill:
                fill_work.append((slot, to_fill))
            if to_spill:
                spill_work.append((slot, to_spill))
        # the whole refresh plan rides ONE batched gather-fill and ONE
        # batched spill across every (slot, page) pair (PR 10) — the
        # per-slot per-op dispatch storm was the tiered engine's largest
        # fixed cost at small page counts
        if fill_work:
            b.serve = self._tier_fill_work(b.serve, fill_work,
                                           prefetch=True)
        if spill_work:
            b.serve = self._tier_spill_work(b.serve, spill_work)

    def tier_force_spill(self, uid: int) -> int:
        """Test/chaos hook: spill EVERY complete non-sink page of
        ``uid``'s slot — including the currently selected ones — so the
        slot's next selection refresh is guaranteed to cold-miss. Only
        legal when that refresh is the slot's next decode step
        (``phase % w == 0``): between refreshes the current selection is
        read by reuse steps, which must never see a cold page. Returns
        the number of pages spilled."""
        if self._tier is None:
            raise ValueError("tier_force_spill requires Engine(hot_pages=N)")
        slots = [s for s, c in self._live.items() if c.uid == uid]
        if not slots:
            raise ValueError(f"uid {uid} is not live")
        slot = slots[0]
        b = self.batch
        if not b.active[slot]:
            raise ValueError(f"uid {uid} is not decoding yet")
        if b.phase[slot] % self.share_window != 0:
            raise ValueError(
                "tier_force_spill is only legal at a selection boundary "
                f"(slot phase {int(b.phase[slot])} % "
                f"{self.share_window} != 0)")
        t = self._tier
        pages = [p for p in t.spill_candidates(slot, int(b.lengths[slot]),
                                               selected=set())
                 if t.resident[slot, p]]
        if pages:
            with self._mesh_ctx():
                b.serve = self._tier_spill_work(b.serve, [(slot, pages)])
        return len(pages)

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------

    def submit(self, req: Request):
        if self.prefill_chunk is None:
            if len(req.prompt) not in self.prompt_buckets:
                raise ValueError(
                    f"prompt length {len(req.prompt)} not in buckets "
                    f"{self.prompt_buckets}; pad upstream")
        elif not 1 <= len(req.prompt) < self.capacity:
            # chunked admission compiles per CHUNK bucket, so any prompt
            # that leaves room to decode is admissible without padding
            raise ValueError(
                f"prompt length {len(req.prompt)} must be in "
                f"[1, capacity={self.capacity})")
        if req.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {req.max_new} "
                             f"(every admitted request emits at least the "
                             f"prefill token)")
        samplib.SamplingParams(temperature=req.temperature,
                               top_p=req.top_p, seed=req.seed).validate()
        self._queue.append(req)

    def _set_sampling(self, req: Request, slot: int):
        """Install the request's sampling lanes into slot ``slot``: the
        base key is a pure function of (seed, uid) — never of the slot —
        so the key stream (and hence any stochastic trace) is invariant
        to slot churn and admission order."""
        base = samplib.request_key(req.seed, req.uid)
        b = self.batch
        b.samp_base = b.samp_base.at[slot].set(base)
        b.samp_temp = b.samp_temp.at[slot].set(req.temperature)
        b.samp_topp = b.samp_topp.at[slot].set(req.top_p)
        b.samp_gen = b.samp_gen.at[slot].set(0)
        self._samp_host[slot] = (base, float(req.temperature),
                                 float(req.top_p))
        if self.spec_tokens is not None and self.draft.needs_host_tokens:
            self._spec_history[slot] = [int(t) for t in
                                        np.asarray(req.prompt)]

    def _first_token(self, slot: int, logits_row):
        """Sample the request's first token (generation index 0) from
        the prefill logits row and advance the slot's generation index."""
        base, temp, topp = self._samp_host[slot]
        first = self._sample_one(logits_row, base, 0, temp, topp)
        self.stats.dispatches += 1
        b = self.batch
        b.samp_gen = b.samp_gen.at[slot].set(1)
        self._tok = self._tok.at[slot].set(first)
        if self.spec_tokens is not None:
            self._spec_emitted[slot] = 1
            if self.draft.needs_host_tokens:
                self._spec_history[slot].append(int(jax.device_get(first)))
        return first

    def _new_completion(self, req: Request, slot: int) -> Completion:
        comp = Completion(uid=req.uid, prompt_len=len(req.prompt),
                          tokens=[],
                          admitted_step=self.stats.decode_steps)
        comp.admitted_engine_step = self.stats.engine_steps
        comp._slot = slot
        comp._seq = self._admit_seq
        self._admit_seq += 1
        self._live[slot] = comp
        self.stats.admissions += 1
        return comp

    def _admit_one(self, req: Request, slot: int):
        """Packed admission: batch-1 prefill + pack; the slot's first
        token is already emitted and it enters READY — it starts
        decoding at the batch's next shared refresh boundary
        (``_promote_ready``), so every active slot's phase stays aligned
        mod the share window."""
        prompt = jnp.asarray(np.asarray(req.prompt)[None])  # (1, S)
        self._set_sampling(req, slot)
        with self._mesh_ctx():
            logits, small = self._prefill(self.params, prompt)
            self.batch.serve = self._pack(self.batch.serve, small,
                                          jnp.int32(slot))
            self.stats.dispatches += 2          # prefill + pack
            first = self._first_token(slot, logits[0])
        if self._tier is not None:
            self._tier.reset_slot(slot)   # pack rewrote every device row
        b = self.batch
        b.ready[slot] = True
        b.lengths[slot] = len(req.prompt)
        b.phase[slot] = 0          # select on the slot's first decode step
        b.uid[slot] = req.uid
        comp = self._new_completion(req, slot)
        comp._first_tok = first
        # packed admission runs between engine steps: the prefill that
        # produced this token completes with the NEXT step's device work
        # (latency harnesses map first_token_step to per-step wall time)
        comp.first_token_step = self.stats.engine_steps + 1
        self.stats.tokens_out += 1
        b.remaining[slot] = req.max_new - 1
        # next append writes at position lengths[slot]; valid while < capacity
        if b.remaining[slot] <= 0 or b.lengths[slot] >= self.capacity:
            self._retire(slot)

    def _admit_one_chunked(self, req: Request, slot: int):
        """Chunked admission: the slot enters the PREFILLING phase
        immediately; its cache rows are cleared to the empty sentinels
        and subsequent engine steps feed the prompt chunk by chunk."""
        b = self.batch
        self._set_sampling(req, slot)
        with self._mesh_ctx():
            b.serve = self._reset(b.serve, jnp.int32(slot))
            self.stats.dispatches += 1
        if self._tier is not None:
            self._tier.reset_slot(slot)   # reset cleared every device row
        b.prefilling[slot] = True
        b.lengths[slot] = 0
        b.phase[slot] = 0
        b.uid[slot] = req.uid
        b.remaining[slot] = req.max_new
        b.prompt_left[slot] = len(req.prompt)
        self._prompts[slot] = np.asarray(req.prompt, np.int32)
        self._new_completion(req, slot)

    def _finish_prefill(self, slot: int, chunk_logits):
        """The chunk that just ran completed this slot's prompt: emit the
        first token from its logits row and flip the slot to READY — it
        starts decoding at the batch's next shared refresh boundary
        (``_promote_ready``), keeping all active phases aligned."""
        b = self.batch
        b.prefilling[slot] = False
        first = self._first_token(slot, chunk_logits[slot])
        b.ready[slot] = True
        b.phase[slot] = 0          # select on the slot's first decode step
        comp = self._live[slot]
        comp._first_tok = first
        comp.first_token_step = self.stats.engine_steps
        self._prompts.pop(slot, None)
        self.stats.tokens_out += 1
        b.remaining[slot] -= 1
        if b.remaining[slot] <= 0 or b.lengths[slot] >= self.capacity:
            self._retire(slot)

    def _finish_prefill_fused(self, slot: int, trace_blk, j: int,
                              engine_step: int):
        """In-scan prompt completion: iteration ``j`` of a fused window
        fed this slot's last prompt tokens and sampled its first token
        in-graph from the chunk logits (generation index 0 — the same
        row-wise sampling lane as ``_first_token``). The decode half
        never writes non-active rows, so trace row ``j`` still holds
        that token when the window returns. Host side mirrors
        ``_finish_prefill``: the slot flips to READY and joins decoding
        at the next shared refresh boundary."""
        b = self.batch
        b.prefilling[slot] = False
        b.ready[slot] = True
        b.phase[slot] = 0          # select on the slot's first decode step
        comp = self._live[slot]
        comp._first_tok = trace_blk[j, slot]
        comp.first_token_step = engine_step
        self._prompts.pop(slot, None)
        self.stats.tokens_out += 1
        b.remaining[slot] -= 1
        if b.remaining[slot] <= 0 or b.lengths[slot] >= self.capacity:
            self._retire(slot)

    def _retire(self, slot: int):
        b = self.batch
        b.active[slot] = False
        b.ready[slot] = False
        b.uid[slot] = -1
        b.remaining[slot] = 0
        if self._tier is not None:
            self._tier.reset_slot(slot)   # next occupant rewrites the rows
        self._samp_host.pop(slot, None)
        if self.spec_tokens is not None:
            self._spec_history.pop(slot, None)
        comp = self._live.pop(slot)
        comp.finished_step = self.stats.decode_steps
        self.completions[comp.uid] = comp
        if self.rebalance == "retire":
            # drift just appeared: re-plan at the END of this step (not
            # here — a retire can fire mid-step with a pending tier plan
            # and a captured active mask still in flight)
            self._rebalance_due = True

    def _pick_request(self) -> Request:
        """Next request to admit. FIFO by default; ``balanced`` scores the
        first ``admit_lookahead`` queued requests with the per-device
        page-load imbalance they would create next to the live slots
        (sched/balance.admission_score) and admits the best, FIFO on ties.
        """
        n_shards = self.balance_shards or self.plan.balance_shards
        if (self.admission != "balanced" or n_shards <= 1
                or len(self._queue) <= 1):
            return self._queue.popleft()
        from repro.sched import balance
        b = self.batch
        # score decoding/ready slots at the page load they WILL reach
        # (fed tokens + prompt still to come); PREFILLING slots go in as
        # (done, left) pairs so the score also sees the in-flight chunk
        # compute they and the candidate will contend for — a freshly
        # chunk-admitted long prompt shows length 0 but will occupy its
        # full page span within ceil(S/chunk) steps
        live, pre_done, pre_left = [], [], []
        for i in range(b.max_batch):
            if b.prefilling[i]:
                pre_done.append(int(b.lengths[i]))
                pre_left.append(int(b.prompt_left[i]))
            elif b.active[i] or b.ready[i]:
                live.append(int(b.lengths[i]) + int(b.prompt_left[i]))
        best_i, best_s = 0, None
        for i in range(min(self.admit_lookahead, len(self._queue))):
            s = balance.admission_score(
                live, len(self._queue[i].prompt), n_shards=n_shards,
                page_size=self.cfg.h2eal.page_size,
                hot_cap=self.hot_pages, spec_tokens=self.spec_tokens,
                prefill_done=pre_done, prefill_left=pre_left,
                chunk_budget=self.prefill_chunk)
            if best_s is None or s < best_s - 1e-12:
                best_i, best_s = i, s
        if best_i == 0:
            return self._queue.popleft()
        self.stats.admission_reorders += 1
        req = self._queue[best_i]
        del self._queue[best_i]
        return req

    def _admit(self):
        admit = (self._admit_one if self.prefill_chunk is None
                 else self._admit_one_chunked)
        for slot in self.batch.free_slots():
            if not self._queue:
                break
            admit(self._pick_request(), slot)

    # ------------------------------------------------------------------
    # the mixed prefill+decode step
    # ------------------------------------------------------------------

    def _chunk_shards(self) -> int:
        """Shard count the chunk allocator scores against (FIFO splits
        under anything but balanced admission)."""
        n = (self.balance_shards or self.plan.balance_shards
             if self.admission == "balanced" else 1)
        return max(n, 1)

    def _schedule_chunks(self):
        """Distribute this step's chunk budget over the prefilling slots.

        Returns (tokens (B, C) int32, chunk_len (B,) int32) or None when
        nothing is prefilling. FIFO by admission order; under
        ``admission="balanced"`` the split is page-granular and
        device-load aware (sched/balance.chunk_allocation scores which
        slot's next page lands on the least-loaded shard).
        """
        b = self.batch
        slots = [i for i in range(b.max_batch) if b.prefilling[i]]
        if not slots:
            return None
        from repro.sched import balance
        slots.sort(key=lambda i: self._live[i]._seq)
        alloc = balance.chunk_allocation(
            [int(b.lengths[i]) for i in slots],
            [int(b.prompt_left[i]) for i in slots],
            self.prefill_chunk, n_shards=self._chunk_shards(),
            page_size=self.cfg.h2eal.page_size)
        tokens = np.zeros((b.max_batch, self.prefill_chunk), np.int32)
        clens = np.zeros((b.max_batch,), np.int32)
        for i, n in zip(slots, alloc):
            if n <= 0:
                continue
            fed = int(b.lengths[i])
            tokens[i, :n] = self._prompts[i][fed:fed + n]
            clens[i] = n
        return tokens, clens

    def _plan_window_chunks(self, n_iters: int):
        """Presimulate the per-step chunk scheduler for ``n_iters``
        in-scan iterations WITHOUT touching the host mirrors: the
        allocator (sched/balance.chunk_allocation) is a deterministic
        function of (lengths, prompt_left) over the prefilling slots, so
        replaying it on local copies yields exactly the chunk blocks the
        per-step loop would feed — except that no admission can join
        mid-window (chunked-admission invariance keeps per-slot traces
        exact either way; docs/serving.md §Fused decode windows).
        Returns (tokens (L, B, C), clens (L, B), finish (L, B)) numpy
        arrays, or None when nothing is prefilling."""
        b = self.batch
        slots = [i for i in range(b.max_batch) if b.prefilling[i]]
        if not slots:
            return None
        from repro.sched import balance
        slots.sort(key=lambda i: self._live[i]._seq)
        n_shards = self._chunk_shards()
        chunk = self.prefill_chunk
        lengths = {i: int(b.lengths[i]) for i in slots}
        left = {i: int(b.prompt_left[i]) for i in slots}
        tokens = np.zeros((n_iters, b.max_batch, chunk), np.int32)
        clens = np.zeros((n_iters, b.max_batch), np.int32)
        finish = np.zeros((n_iters, b.max_batch), bool)
        for j in range(n_iters):
            live = [i for i in slots if left[i] > 0]
            if not live:
                break
            alloc = balance.chunk_allocation(
                [lengths[i] for i in live], [left[i] for i in live],
                chunk, n_shards=n_shards,
                page_size=self.cfg.h2eal.page_size)
            for i, n in zip(live, alloc):
                if n <= 0:
                    continue
                fed = lengths[i]
                tokens[j, i, :n] = self._prompts[i][fed:fed + n]
                clens[j, i] = n
                lengths[i] += n
                left[i] -= n
                if left[i] == 0:
                    # leaves the pool: READY slots take no more chunks
                    finish[j, i] = True
        return tokens, clens, finish

    def _promote_ready(self):
        """Activate READY slots only when every active slot sits at its
        refresh boundary (``phase % w == 0``) — or the batch is empty.
        Newly activated slots start at phase 0, so inductively ALL
        active slots share one phase residue mod the share window: the
        ``select`` decode variant dispatches on ~1/w of decode steps
        instead of nearly every step under staggered phases (the PR-5
        select-dispatch regression; ROADMAP). A slot's own schedule
        still depends only on its own phase — no global clock enters any
        slot's trajectory, so token traces are unchanged; admission is
        merely delayed by at most w-1 steps."""
        b = self.batch
        if not b.ready.any():
            return
        act = b.active
        # Speculative mode: verify steps advance each slot's phase by a
        # VARIABLE accepted count, so active phases de-align permanently
        # and the alignment precondition below could never fire again —
        # READY slots would deadlock. Promote immediately instead; a
        # slot's refresh schedule is a function of its own phase alone
        # either way, so per-slot traces are unchanged.
        if (self.spec_tokens is None and act.any()
                and (b.phase[act] % self.share_window).any()):
            return
        b.active |= b.ready
        b.ready[:] = False

    def step(self):
        """One engine step (non-blocking): feed a prompt chunk to the
        prefilling slots AND run one batched ragged decode over the
        decoding slots — the mixed prefill+decode step. A slot whose
        prompt completes this step emits its first token from the chunk
        logits and starts decoding next step."""
        b = self.batch
        with _span("engine.schedule"):
            self._promote_ready()
            self._prev_engine_steps = self.stats.engine_steps
            # fused decode-window routing: strictly between two
            # selection boundaries every decoding slot runs reuse steps
            # only, so the stretch to the next boundary collapses into
            # ONE dispatched scan. Boundary steps (any slot due a
            # selection refresh) and chunk-only steps stay per-step.
            fused = (self._fused is not None and b.active.any()
                     and not (b.active
                              & (b.phase % self.share_window == 0)).any())
            chunk_work = (self._schedule_chunks()
                          if self.prefill_chunk is not None and not fused
                          else None)
        if fused:
            with self._mesh_ctx():
                self._window_once(b.active.copy())
            if self._cost_model is not None:
                with _span("engine.account"):
                    self._maybe_rebalance()
            return
        active = b.active.copy()
        if chunk_work is None and not active.any():
            return
        self.stats.engine_steps += 1
        with self._mesh_ctx():
            if chunk_work is not None:
                with _span("engine.chunk"):
                    toks, clens = chunk_work
                    logits_c, b.serve = self._chunk(
                        self.params, b.serve, jnp.asarray(toks),
                        jnp.asarray(clens), jnp.asarray(clens > 0))
                    self.stats.dispatches += 1
                    self.stats.prefill_chunks += 1
                    for slot in np.nonzero(clens)[0]:
                        slot = int(slot)
                        b.lengths[slot] += int(clens[slot])
                        b.prompt_left[slot] -= int(clens[slot])
                        if b.prompt_left[slot] == 0:
                            self._finish_prefill(slot, logits_c)
            if active.any():
                self._decode_once(active)
        if self._cost_model is not None:
            with _span("engine.account"):
                self._maybe_rebalance()

    # ------------------------------------------------------------------
    # dynamic rebalancing (sched/cost.py + sched/rebalance.py)
    # ------------------------------------------------------------------

    def _slot_views(self):
        """Cost-model snapshot of every occupied slot (host mirrors
        only — building views never syncs the device)."""
        from repro.sched.cost import SlotView
        b = self.batch
        views = []
        for i in range(b.max_batch):
            if b.prefilling[i]:
                ph = "prefill"
            elif b.ready[i]:
                ph = "ready"
            elif b.active[i]:
                ph = "decode"
            else:
                continue
            views.append(SlotView(slot=i, uid=int(b.uid[i]),
                                  ctx=int(b.lengths[i]),
                                  prompt_left=int(b.prompt_left[i]),
                                  phase=ph))
        return views

    def compute_loads(self) -> List[float]:
        """Per-bank next-step compute loads of the live slots under the
        cost model (``rebalance_banks`` contiguous slot-index blocks;
        works with any ``rebalance`` setting — the balance report uses
        it on plain engines too)."""
        from repro.sched.cost import CostModel, device_compute_loads
        cm = self._cost_model or CostModel.from_config(
            self.cfg, hot_cap=self.hot_pages,
            spec_tokens=self.spec_tokens or 0,
            chunk_budget=self.prefill_chunk or 0)
        costs = cm.slot_costs(self._slot_views(),
                              n_shards=self.plan.page_stripe_shards)
        return device_compute_loads(
            costs, n_banks=self.rebalance_banks,
            max_batch=self.batch.max_batch,
            page_stripe_shards=self.plan.page_stripe_shards)

    def _maybe_rebalance(self):
        """End-of-step rebalance check: score the live slots' next-step
        compute, plan migrations (greedy-LPT into free indices), apply
        when the plan clears the hysteresis gate. Runs only when due
        (a retirement this step, or the interval boundary) and outside
        the cooldown window."""
        due = self._rebalance_due
        # interval trigger: fire when this step CROSSED a multiple of
        # the interval. Identical to `engine_steps % interval == 0` for
        # per-step dispatch (steps advance by 1), but a fused window
        # advances engine_steps by up to w-1 at once and may jump past
        # the multiple without landing on it.
        if (self.rebalance == "interval"
                and self.stats.engine_steps // self.rebalance_interval
                > self._prev_engine_steps // self.rebalance_interval):
            due = True
        if not due:
            return
        self._rebalance_due = False
        if (self.stats.engine_steps - self._last_rebalance_step
                < self.rebalance_cooldown):
            self.stats.rebalance_skipped += 1
            return
        from repro.sched.rebalance import plan_rebalance
        b = self.batch
        views = self._slot_views()
        if len(views) < 2:
            return
        stripes = self.plan.page_stripe_shards
        costs = self._cost_model.slot_costs(views, n_shards=stripes)
        plan = plan_rebalance(
            costs, b.free_slots(), n_banks=self.rebalance_banks,
            max_batch=b.max_batch, page_stripe_shards=stripes,
            min_gain=self.rebalance_min_gain)
        self.stats.rebalance_checks += 1
        self.stats.imbalance_pre_sum += plan.imbalance_before
        self.stats.imbalance_post_sum += plan.imbalance_after
        if not plan.moves:
            self.stats.rebalance_skipped += 1
            return
        for mv in plan.moves:
            self._migrate_slot(mv.src, mv.dst)
        self._last_rebalance_step = self.stats.engine_steps
        self.stats.rebalances += 1

    def _migrate_slot(self, src: int, dst: int):
        """Move the occupant of slot index ``src`` into the FREE index
        ``dst``: one donated jit copies the serve-state rows, sampling
        lanes, and pending token feed verbatim and clears ``src`` to the
        empty sentinels; host mirrors, far-store keys, and completion
        bookkeeping re-key alongside. Cache contents move bit-exact and
        sampling keys are owned by (seed, uid) — never the slot index —
        so token traces are unchanged (tests/test_rebalance.py)."""
        b = self.batch
        assert src != dst and b.uid[src] != -1 and b.uid[dst] == -1, (
            src, dst)
        with self._mesh_ctx():
            (b.serve, self._tok, b.samp_base, b.samp_temp, b.samp_topp,
             b.samp_gen) = self._migrate(
                b.serve, self._tok, b.samp_base, b.samp_temp,
                b.samp_topp, b.samp_gen, jnp.int32(src), jnp.int32(dst))
        self.stats.dispatches += 1
        for arr, clear in ((b.active, False), (b.prefilling, False),
                           (b.ready, False), (b.lengths, 0),
                           (b.phase, 0), (b.uid, -1), (b.remaining, 0),
                           (b.prompt_left, 0)):
            arr[dst] = arr[src]
            arr[src] = clear
        if src in self._samp_host:
            self._samp_host[dst] = self._samp_host.pop(src)
        if src in self._prompts:
            self._prompts[dst] = self._prompts.pop(src)
        if self.spec_tokens is not None:
            if src in self._spec_history:
                self._spec_history[dst] = self._spec_history.pop(src)
            self._spec_emitted[dst] = self._spec_emitted[src]
            self._spec_emitted[src] = 0
        if self._tier is not None:
            t = self._tier
            t.resident[dst] = t.resident[src].copy()
            for s, p in [k for k in t.far if k[0] == src]:
                t.far[(dst, p)] = t.far.pop((s, p))
            t.reset_slot(src)
        comp = self._live.pop(src)
        comp._slot = dst
        self._live[dst] = comp
        self.stats.migrations += 1
        self.stats.migrated_tokens += int(b.lengths[dst])

    def _decode_once(self, active: np.ndarray):
        """The decode half of a step, over the captured ``active`` mask
        (slots that finished prefilling THIS step start next step)."""
        if self.spec_tokens is not None:
            return self._verify_once(active)
        b = self.batch
        step_idx = self._trace_rows
        with _span("engine.decode"):
            # selection refresh: each slot's own share-window cadence (so
            # a slot's schedule is independent of the global clock, other
            # slots, and how its admission was chunked)
            need = active & (b.phase % self.share_window == 0)
            if not np.array_equal(self._act_mirror, active):
                self._act_dev = jnp.asarray(active)
                self._act_mirror = active.copy()
            act_dev = self._act_dev
            if need.any():
                need_dev = jnp.asarray(need)
                if self._tier is not None:
                    logits, b.serve = self._tier_select(need, need_dev,
                                                        act_dev)
                else:
                    logits, b.serve = self._dec_sel(
                        self.params, b.serve, self._tok, act_dev, need_dev)
                    self.stats.dispatches += 1
                self.stats.select_steps += 1
            else:
                logits, b.serve = self._dec_reuse(
                    self.params, b.serve, self._tok, act_dev)
                self.stats.dispatches += 1
                self.stats.reuse_steps += 1
        with _span("engine.sample"):
            # keep non-active rows of the token feed: a slot that
            # finished prefilling THIS step already holds its first
            # token, which this dispatch (captured mask without it) must
            # not clobber with the sample of an inactive row's garbage
            # logits (the sampler's temp=0 lane IS argmax, so greedy rows
            # stay bit-identical to the pre-sampling engine)
            tok, b.samp_gen = self._sample(logits, b.samp_base, b.samp_gen,
                                           b.samp_temp, b.samp_topp,
                                           act_dev)
            self.stats.dispatches += 1
            self._tok = jnp.where(act_dev, tok, self._tok)
            self._trace.append(self._tok[None])
        with _span("engine.account"):
            self._trace_rows += 1
            self.stats.decode_steps += 1
            self.stats.occupancy_sum += float(active.mean())
            for slot in np.nonzero(active)[0]:
                b.lengths[slot] += 1
                b.phase[slot] += 1
                comp = self._live[slot]
                comp._step_idx.append(step_idx)
                comp._slot_idx.append(int(slot))
                self.stats.tokens_out += 1
                b.remaining[slot] -= 1
                if (b.remaining[slot] <= 0
                        or b.lengths[slot] >= self.capacity):
                    self._retire(slot)
            if self._tier_plan is not None:
                # prefetch/spill for the NEXT share window, one window
                # ahead of the selection refresh that will consume the
                # pages
                self._tier_refresh()

    def _window_once(self, active: np.ndarray):
        """One fused decode window: every reuse step from here to the
        next selection boundary (capped at ``decode_window``) as ONE
        dispatched scan, with sampling and budget-driven retirement
        in-graph (runtime/serve.make_fused_window_step). The host
        applies the whole window's bookkeeping afterwards from the
        budget vector alone — a slot emits EXACTLY ``budgets[i]`` tokens
        by construction, so no device readback is needed and the loop
        stays non-blocking."""
        with _span("engine.decode"):
            from repro.sched import window_budgets
            b = self.batch
            # reuse steps only read pinned-resident pages (spill
            # candidates exclude the selection, sink, and local sections),
            # so a fused window can never cold-miss; any pending refresh
            # plan was already consumed by the selection step that opened
            # this window
            assert self._tier_plan is None, (
                "refresh plan crossed a boundary")
            w = self.share_window
            residue = int(b.phase[np.nonzero(active)[0][0]] % w)
            n_useful, budgets = window_budgets(
                active, b.remaining, b.lengths, capacity=self.capacity,
                phase_residue=residue, share_window=w,
                window=self._fused_len)
            if not np.array_equal(self._act_mirror, active):
                self._act_dev = jnp.asarray(active)
                self._act_mirror = active.copy()
            act_dev = self._act_dev
            plan = (self._plan_window_chunks(self._fused_len)
                    if self._fused_mix is not None else None)
            e0 = self.stats.engine_steps
            if plan is None:
                trace_blk, b.serve, self._tok, b.samp_gen = self._fused(
                    self.params, b.serve, self._tok, act_dev, b.samp_gen,
                    jnp.asarray(budgets), b.samp_base, b.samp_temp,
                    b.samp_topp)
            else:
                toks, clens, finish = plan
                (trace_blk, b.serve, self._tok,
                 b.samp_gen) = self._fused_mix(
                    self.params, b.serve, self._tok, act_dev, b.samp_gen,
                    jnp.asarray(budgets), b.samp_base, b.samp_temp,
                    b.samp_topp, jnp.asarray(toks), jnp.asarray(clens),
                    jnp.asarray(finish))
            self.stats.dispatches += 1
            self.stats.fused_windows += 1
        with _span("engine.account"):
            max_e = int(budgets[active].max())
            chunk_iters = (int((plan[1].sum(axis=1) > 0).sum())
                           if plan is not None else 0)
            # the window consumed as many logical engine steps as its
            # longest-running half (per-step would interleave them 1:1)
            self.stats.engine_steps += max(max_e, chunk_iters)
            self.stats.fused_steps += max_e
            self.stats.decode_steps += max_e
            self.stats.reuse_steps += max_e
            self.stats.prefill_chunks += chunk_iters
            row0 = self._trace_rows
            self._trace.append(trace_blk[:max_e])
            self._trace_rows += max_e
            for j in range(max_e):
                self.stats.occupancy_sum += float(
                    (budgets > j).sum()) / b.max_batch
            # chunk bookkeeping first (disjoint slot sets): a slot whose
            # prompt completed in-scan flips to READY exactly where the
            # per-step mixed step would have flipped it
            if plan is not None:
                for j in range(self._fused_len):
                    for slot in np.nonzero(clens[j])[0]:
                        slot = int(slot)
                        b.lengths[slot] += int(clens[j, slot])
                        b.prompt_left[slot] -= int(clens[j, slot])
                        if finish[j, slot]:
                            self._finish_prefill_fused(slot, trace_blk, j,
                                                       e0 + 1 + j)
            for slot in np.nonzero(active)[0]:
                slot = int(slot)
                emitted = int(budgets[slot])
                comp = self._live[slot]
                comp._step_idx.extend(range(row0, row0 + emitted))
                comp._slot_idx.extend([slot] * emitted)
                b.lengths[slot] += emitted
                # a survivor's budget is exactly n_useful (any smaller
                # budget means a stop condition fired → it retires below),
                # so live phases stay aligned at the next boundary
                b.phase[slot] += emitted
                b.remaining[slot] -= emitted
                self.stats.tokens_out += emitted
                if (b.remaining[slot] <= 0
                        or b.lengths[slot] >= self.capacity):
                    self._retire(slot)

    def _verify_once(self, active: np.ndarray):
        """The speculative decode half of a step: draft k-1 tokens per
        active slot (serving/draft.py), verify all k positions in ONE
        chunked forward at the static (B, k) bucket, and emit each
        slot's accepted prefix (always >= 1 token — the first coupled
        target). Only accepted prefixes are appended (attend-before-
        append), so there is never anything to roll back. ``max_emit``
        clamps acceptance at the slot's next selection-refresh boundary
        (phase hitting 0 mod share_window), its generation budget, and
        capacity — so selection cadence stays a pure function of the
        slot's own phase and the capacity invariant holds."""
        with _span("engine.decode"):
            b = self.batch
            k = self.spec_tokens
            w = self.share_window
            need = active & (b.phase % w == 0)
            if not np.array_equal(self._act_mirror, active):
                self._act_dev = jnp.asarray(active)
                self._act_mirror = active.copy()
            act_dev = self._act_dev
            drafted = self.draft.draft(self, active, k)
            if k > 1:
                tokens = jnp.concatenate(
                    [self._tok[:, None],
                     jnp.asarray(drafted, jnp.int32)], axis=1)
            else:
                tokens = self._tok[:, None]
            max_emit = np.ones((b.max_batch,), np.int64)
            for slot in np.nonzero(active)[0]:
                slot = int(slot)
                r = int(b.phase[slot]) % w
                window_left = (w - r) if r else w
                max_emit[slot] = max(1, min(
                    k, window_left, int(b.remaining[slot]),
                    self.capacity - int(b.lengths[slot])))
            targets, n_dev, next_dev, b.samp_gen, b.serve = self._verify(
                self.params, b.serve, tokens, act_dev, jnp.asarray(need),
                b.samp_base, b.samp_gen, b.samp_temp, b.samp_topp,
                jnp.asarray(max_emit, jnp.int32))
            self.stats.dispatches += 1
        with _span("engine.sample"):
            self._tok = jnp.where(act_dev, next_dev, self._tok)
        with _span("engine.account"):
            if need.any():
                self.stats.select_steps += 1
            else:
                self.stats.reuse_steps += 1
            # the trace gets k rows per verify step (the coupled targets);
            # a slot that accepted n of them owns rows [base, base+n)
            trace_base = self._trace_rows
            self._trace.append(targets.T)               # (k, B) block
            self._trace_rows += k
            self.stats.decode_steps += 1
            self.stats.spec_steps += 1
            self.stats.occupancy_sum += float(active.mean())
            # the one host sync speculation adds: accepted counts (and the
            # target tokens, for host-side draft history) per verify step
            n_host, targets_host = jax.device_get((n_dev, targets))
            need_hist = self.draft.needs_host_tokens
            for slot in np.nonzero(active)[0]:
                slot = int(slot)
                nb = int(n_host[slot])
                comp = self._live[slot]
                comp._step_idx.extend(range(trace_base, trace_base + nb))
                comp._slot_idx.extend([slot] * nb)
                b.lengths[slot] += nb
                b.phase[slot] += nb
                b.remaining[slot] -= nb
                self._spec_emitted[slot] += nb
                self.stats.tokens_out += nb
                self.stats.spec_slot_steps += 1
                self.stats.spec_drafted += k - 1
                self.stats.spec_accepted += nb
                if need_hist:
                    self._spec_history[slot].extend(
                        int(t) for t in targets_host[slot, :nb])
                if (b.remaining[slot] <= 0
                        or b.lengths[slot] >= self.capacity):
                    self._retire(slot)

    def finalize(self):
        """Materialize completion tokens from the device-side trace.
        Idempotent; the only device sync in the serving loop."""
        if self._trace:
            trace = np.asarray(jnp.concatenate(self._trace))  # (T, B)
        else:
            trace = np.zeros((0, self.batch.max_batch), np.int32)
        for comp in list(self.completions.values()) + list(
                self._live.values()):
            if comp.tokens or comp._first_tok is None:
                continue  # already materialized / still prefilling
            toks = [int(np.asarray(comp._first_tok))]
            # rows are read at the slot each was EMITTED in — a later
            # migration of the slot never invalidates earlier rows
            toks.extend(int(trace[t, s]) for t, s in
                        zip(comp._step_idx, comp._slot_idx))
            comp.tokens = toks

    def busy(self) -> bool:
        """True while any work is pending: queued requests, prefilling
        slots, ready slots, or decoding slots."""
        return (bool(self._queue) or bool(self.batch.active.any())
                or bool(self.batch.prefilling.any())
                or bool(self.batch.ready.any()))

    def poll(self) -> bool:
        """Admit whatever fits, then run one engine step — the unit of
        the ``run()`` drain loop, public so external drivers (arrival
        simulators, latency harnesses) need not reach into the
        internals. Returns True if the step dispatched any work."""
        before = self.stats.engine_steps
        with jax.profiler.StepTraceAnnotation("engine.poll",
                                              step_num=before):
            with _span("engine.admit"):
                self._admit()
            self.step()
        return self.stats.engine_steps > before

    def sync(self):
        """Block until the device has caught up with the dispatched
        steps (latency harnesses call this per step for honest
        timestamps; the throughput path never does)."""
        with _span("engine.sync"):
            jax.block_until_ready(self._tok)

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> Dict[int, Completion]:
        """Drain: admit + step until queue and slots are empty.

        Returns a snapshot of the completions map: a later ``run()`` on
        the same engine that reuses a uid replaces the entry in
        ``self.completions`` but never mutates an earlier run's returned
        dict (its Completion tokens are already materialized here)."""
        for r in requests or ():
            self.submit(r)
        t0 = time.time()
        while self.busy():
            self.poll()
        jax.block_until_ready(self.batch.serve["length"])
        self.stats.wall_s += time.time() - t0
        self.finalize()
        return dict(self.completions)

    def reset_metrics(self):
        """Zero stats/completions/trace between a warmup and a measured
        phase. Only legal when idle (no queued or in-flight requests)."""
        assert not self._queue and not self._live, (
            "reset_metrics() requires an idle engine")
        self.finalize()           # materialize anything still deferred
        self._trace.clear()
        self._trace_rows = 0
        self._prev_engine_steps = 0
        self.completions = {}
        self.stats = EngineStats()
        # the cooldown window is measured in engine_steps, which just
        # restarted from 0 — an un-reset watermark would block every
        # rebalance of the measured phase behind a negative delta
        self._last_rebalance_step = -(1 << 30)
        self._rebalance_due = False

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def context_lengths(self) -> np.ndarray:
        """Per-slot context lengths of live slots (for sched/balance)."""
        return self.batch.lengths[self.batch.active].copy()

    def jit_cache_sizes(self) -> Dict[str, int]:
        sizes = {
            "prefill": jit_cache_size(self._prefill),
            "decode_select": jit_cache_size(self._dec_sel),
            "decode_reuse": jit_cache_size(self._dec_reuse),
            "pack": jit_cache_size(self._pack),
        }
        if self.prefill_chunk is not None:
            sizes["prefill_chunk"] = jit_cache_size(self._chunk)
            sizes["reset"] = jit_cache_size(self._reset)
        if self.hot_pages is not None:
            sizes["tier_gather"] = jit_cache_size(self._tier_gather)
            sizes["tier_spill"] = jit_cache_size(self._tier_spill)
            sizes["tier_fill"] = jit_cache_size(self._tier_fill)
        sizes["sample"] = jit_cache_size(self._sample)
        sizes["sample_one"] = jit_cache_size(self._sample_one)
        if self._fused is not None:
            sizes["fused_window"] = jit_cache_size(self._fused)
        if self._fused_mix is not None:
            sizes["fused_window_mixed"] = jit_cache_size(self._fused_mix)
        if self.rebalance != "off":
            sizes["migrate"] = jit_cache_size(self._migrate)
        if self.spec_tokens is not None:
            sizes["verify"] = jit_cache_size(self._verify)
            for name, n in self.draft.jit_cache_sizes().items():
                sizes[f"draft_{name}"] = n
        return sizes
