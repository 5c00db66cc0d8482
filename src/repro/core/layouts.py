"""Pluggable attention/serve-cache layout backends (paper §IV-B).

H²EAL's core claim is that different heads and different memory layouts
want different attention strategies. This module is the single dispatch
point for that choice: every serve-cache layout is an
:class:`AttentionLayout` entry in a registry, and everything above this
layer (``models/transformer.py``, ``serving/engine.py``,
``runtime/serve.py``, the CLIs and benchmarks) resolves layouts by name
— placement is data, not control flow. Unknown names raise with the
registered list, mirroring ``kernels/ops.resolve_impl``.

The protocol (one class ≈ 50 lines; see docs/serving.md for a worked
example):

* ``plan(cfg, mesh) -> LayoutPlan`` — construction-time planning:
  resolve/validate the mesh (or build a default one), declare the
  capacity rounding quantum, whether the batched serve state must be
  device_put into a sharded placement, and the shard count balanced
  admission should score against. Mesh problems surface HERE, not at
  the first decode step.
* ``cache_axes(kind, batch_ok)`` — the paged-cache leaf placement
  (axis names with a ``"batch"`` placeholder) that
  ``runtime/sharding.state_shardings`` turns into PartitionSpecs.
* ``prefill(spec, k, v, length, capacity, perm)`` — build the decode
  state (paged + stream caches) from prefill K/V, in whatever physical
  page order the layout wants.
* ``prefill_chunk(spec, state, inputs)`` — append one prompt chunk of a
  chunked (slot-resident) prefill directly into the layout's sharded
  caches and attend it causally, over a single :class:`PrefillInputs`
  pytree (mirroring ``DecodeInputs``). This is how the serving engine
  prefills without ever leaving the batched sharded state — no batch-1
  unsharded prefill + pack.
* ``decode(spec, state, inputs)`` / ``ragged_decode(spec, state,
  inputs)`` — one decode step against the layout's cache placement.
  Both take a single :class:`DecodeInputs` pytree instead of the long
  positional signatures of ``core/hybrid_attention.py`` (which remain
  as the underlying bodies and as deprecated direct-call aliases for
  one release).
* ``decode_rows(spec, state, inputs, *, layer, ...)`` (optional) — the
  decode step as the model's layer scan runs it: paged leaves read in
  place from the layer-stacked buffers, the new rows returned for one
  write after the scan.

Registered layouts:

  default        — single-program path, no mesh required. The pure
                   algorithm (paper §IV-A); also the token-exactness
                   oracle every other layout is tested against.
  head           — GSPMD baseline head parallelism: kv-heads → 'model',
                   batch → 'data' (paper Fig 3a).
  coplace        — GSPMD memory-compute co-placement: pages → 'model'
                   (paper §IV-B); decode math is the default body,
                   placement comes entirely from ``cache_axes``.
  interleave     — co-placement + interleaved storage: pages → 'model'
                   AND within-page tokens → 'data' (paper Fig 7b).
                   Supports ragged continuous-batching decode purely
                   through this registry entry — the engine has no
                   interleave-specific code.
  coplace_shmap  — explicit shard_map realization of co-placement with
                   round-robin physical page striping: per-device
                   partial softmax over locally-owned pages merged with
                   a cross-device log-sum-exp combine
                   (core/hybrid_attention.py::_paged_decode_coplace).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import hybrid_attention as hattn

Array = jax.Array

LAYOUT_DEFAULT = "default"
LAYOUT_HEAD = "head"
LAYOUT_COPLACE = "coplace"
LAYOUT_INTERLEAVE = "interleave"
LAYOUT_COPLACE_SHMAP = "coplace_shmap"

# legacy spellings accepted for one release (None/"auto" predate the
# registry; the engine and launch CLIs used them for the default path).
# resolve_layout() emits a one-shot DeprecationWarning per spelling,
# mirroring kernels/ops.resolve_impl's impl="kernel" treatment.
_ALIASES = {None: LAYOUT_DEFAULT, "auto": LAYOUT_DEFAULT}
_warned_aliases: set = set()


# ---------------------------------------------------------------------------
# The one decode-step input contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DecodeInputs:
    """Everything a layout's decode hook consumes, as one pytree.

    q: (B, Hq, D) roped at each slot's position; k_new/v_new: (B, Hkv, D).
    lengths: context BEFORE this token — scalar (lockstep) or (B,)
    per-slot (continuous batching). active/need_select: the ragged
    path's per-slot masks (None on the lockstep path); see
    core/hybrid_attention.py::decode_attention for their exact
    semantics.
    """

    q: Array
    k_new: Array
    v_new: Array
    lengths: Array
    active: Optional[Array] = None
    need_select: Optional[Array] = None

    @property
    def is_ragged(self) -> bool:
        return (self.active is not None
                or jnp.asarray(self.lengths).ndim == 1)


jax.tree_util.register_dataclass(
    DecodeInputs,
    data_fields=["q", "k_new", "v_new", "lengths", "active", "need_select"],
    meta_fields=[])


@dataclasses.dataclass
class PrefillInputs:
    """Everything a layout's ``prefill_chunk`` hook consumes, as one
    pytree (the chunked-prefill mirror of :class:`DecodeInputs`).

    q: (B, C, Hq, D) roped at each slot's chunk positions; k_new/v_new:
    (B, C, Hkv, D). start: (B,) context length before the chunk (the
    slot's tokens-so-far); chunk_len: (B,) valid tokens in this chunk
    (rows past it are padding); active: (B,) bool — slots taking a
    chunk this step (None = all).
    """

    q: Array
    k_new: Array
    v_new: Array
    start: Array
    chunk_len: Array
    active: Optional[Array] = None


jax.tree_util.register_dataclass(
    PrefillInputs,
    data_fields=["q", "k_new", "v_new", "start", "chunk_len", "active"],
    meta_fields=[])


@dataclasses.dataclass
class VerifyInputs:
    """Everything a layout's speculative ``verify_chunk`` hook consumes
    (PR 8). q/k_new/v_new: (B, k, ·, D) roped at positions start ..
    start+k-1; start: (B,) context length before the chunk; active: (B,)
    bool live slots; need_select: (B,) bool per-slot share-window phase —
    the chunk's one selection refresh is gated per slot exactly like a
    decode select step."""

    q: Array
    k_new: Array
    v_new: Array
    start: Array
    active: Optional[Array] = None
    need_select: Optional[Array] = None


jax.tree_util.register_dataclass(
    VerifyInputs,
    data_fields=["q", "k_new", "v_new", "start", "active", "need_select"],
    meta_fields=[])


# ---------------------------------------------------------------------------
# Construction-time plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayoutPlan:
    """What the serving engine needs to know before the first step.

    layout           — canonical registry name (feeds state_shardings).
    mesh             — resolved mesh (None = no mesh; single-program).
    capacity_quantum — cache capacity (tokens) must round up to a
                       multiple of this (sharded page dims need a whole
                       number of pages per device).
    shard_state      — the batched serve state must be device_put into
                       its sharded placement at construction and the
                       decode/pack jits must pin out_shardings (the
                       zero-recompile invariant under sharding).
    balance_shards   — shard count ``admission="balanced"`` scores
                       per-device page loads against (1 = FIFO).
    page_stripe_shards — physical page→slot striping factor of the
                       layout's paged cache (1 = physical page order is
                       logical page order). coplace_shmap stripes pages
                       round-robin over the mesh 'model' axis; the
                       tiered-residency controller (core/cache.py
                       TieredPagedCache) reads this so every registered
                       layout inherits hot/cold page spilling with the
                       correct physical pin mapping.
    """

    layout: str
    mesh: Any = None
    capacity_quantum: int = 1
    shard_state: bool = False
    balance_shards: int = 1
    page_stripe_shards: int = 1

    def round_capacity(self, tokens: int) -> int:
        q = max(int(self.capacity_quantum), 1)
        return -(-int(tokens) // q) * q

    def phys_page(self, logical: int, n_pages: int) -> int:
        """Physical page slot of logical page ``logical`` under this
        layout's striping (identity when ``page_stripe_shards == 1``)."""
        from repro.core import paging

        if self.page_stripe_shards <= 1:
            return int(logical)
        return int(paging.interleave_slot(logical, n_pages,
                                          self.page_stripe_shards))

    def state_shardings(self, cfg, state, *, batch_size: int | None = None):
        """NamedSharding pytree for a batched serve state."""
        from repro.runtime import sharding as shardlib

        return shardlib.state_shardings(cfg, self.mesh, state,
                                        layout=self.layout,
                                        batch_size=batch_size)


# ---------------------------------------------------------------------------
# The layout protocol + registry
# ---------------------------------------------------------------------------


class AttentionLayout:
    """Base class / protocol for serve-cache layouts. Subclass, set
    ``name``, override the hooks that differ, and ``register_layout()``
    an instance — the engine, step builders, CLIs, benchmarks and the
    conformance tests pick the new entry up by name."""

    name: str = "abstract"
    #: pages are distributed across devices — balanced admission has an
    #: effect and the benchmark enables it by default
    shards_pages: bool = False

    # -- construction-time ------------------------------------------------
    def plan(self, cfg, mesh=None) -> LayoutPlan:
        raise NotImplementedError(self.name)

    def cache_axes(self, kind: str, *, batch_ok: bool) -> Tuple:
        """Axis names for a paged-cache leaf (``"batch"`` placeholder is
        resolved by runtime/sharding.py). kind: "pages" (B,Hr,C,P,D),
        "tau" (B,Hr,C,D) or "meta" (B,Hr,C)."""
        raise NotImplementedError(self.name)

    # -- prefill ----------------------------------------------------------
    def prefill(self, spec, k, v, length, capacity, perm=None) -> Dict:
        """Build the decode state {"paged", "stream"} from prefill K/V."""
        raise NotImplementedError(self.name)

    def prefill_chunk(self, spec, state: Dict, inputs: PrefillInputs, *,
                      perm=None):
        """Chunked prefill: append one prompt chunk directly into the
        layout's caches and attend it causally
        -> (out (B, C, Hq, D), new state)."""
        raise NotImplementedError(
            f"layout {self.name!r} does not support chunked prefill")

    # -- decode -----------------------------------------------------------
    def decode(self, spec, state: Dict, inputs: DecodeInputs, *,
               do_select: bool, perm=None):
        """Lockstep decode step -> (out (B,Hq,D), new state)."""
        raise NotImplementedError(self.name)

    def ragged_decode(self, spec, state: Dict, inputs: DecodeInputs, *,
                      do_select: bool, perm=None):
        """Continuous-batching decode step (per-slot lengths/active/
        need_select) -> (out, new state)."""
        raise NotImplementedError(
            f"layout {self.name!r} does not support ragged "
            f"(continuous-batching) decode")

    #: ``decode_rows(spec, state, inputs, *, layer, do_select, perm)
    #: -> (out, new state, rows)``: the decode step of the model's layer
    #: scan with the paged leaves read in place (``state["paged"]``'s
    #: ``cache.PAGE_LEAVES`` are the layer-stacked buffers, read at
    #: ``layer``) and the retrieval heads' rows returned for one write
    #: after the scan — see hybrid_attention.decode_attention_rows. None:
    #: the layout appends inside its decode hook, and the scan threads
    #: its whole cache through.
    decode_rows = None

    # -- fused decode windows (PR 10) --------------------------------------
    def decode_window(self, body, carry, xs, *, length: int):
        """Run ``length`` reuse decode steps as one fused program.

        ``body`` is a ``lax.scan``-shaped step built by
        runtime/serve.make_fused_window_step: its per-iteration decode
        math routes through this layout's own ``ragged_decode`` /
        ``prefill_chunk`` hooks, so the default scan realization is
        correct for every registry entry — including shard_map bodies
        (``coplace_shmap``), which scan like any other traced callee. A
        layout only overrides this to change HOW the window iterates
        (e.g. an unrolled or pipelined realization), never the step
        math."""
        return jax.lax.scan(body, carry, xs, length=length)

    # -- speculative verify (PR 8) ---------------------------------------
    def verify_chunk(self, spec, state: Dict, inputs: "VerifyInputs", *,
                     perm=None):
        """Attend k drafted tokens as k decode steps over the PRE-append
        caches (no KV mutation; selection/importance refresh only)
        -> (out (B, k, Hq, D), new state)."""
        raise NotImplementedError(
            f"layout {self.name!r} does not support speculative verify")

    def verify_append(self, spec, state: Dict, inputs: "VerifyInputs",
                      accepted, *, perm=None):
        """Commit the accepted prefix of a verified chunk (ragged chunk
        appends) -> new state."""
        raise NotImplementedError(
            f"layout {self.name!r} does not support speculative verify")


_REGISTRY: Dict[str, AttentionLayout] = {}


def register_layout(layout: AttentionLayout) -> AttentionLayout:
    """Register a layout instance under ``layout.name`` (last wins)."""
    _REGISTRY[layout.name] = layout
    return layout


def available_layouts() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _lookup(name) -> AttentionLayout:
    """Canonicalize (silently) and fetch; raise ValueError if unknown."""
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown attention layout {name!r}; registered layouts: "
            f"{', '.join(available_layouts())}")
    return _REGISTRY[name]


def resolve_layout(name) -> str:
    """Canonicalize a layout name; raise ValueError if unknown.

    The pre-registry spellings ``None`` and ``"auto"`` resolve to
    ``"default"`` but emit a DeprecationWarning once per process (per
    spelling) — they will be removed after one release. Canonical names
    resolve silently.
    """
    if name in _ALIASES:
        canonical = _ALIASES[name]
        if name not in _warned_aliases:
            _warned_aliases.add(name)
            warnings.warn(
                f"layout={name!r} is a deprecated alias for "
                f"{canonical!r} and will be removed; pass "
                f"{canonical!r} instead", DeprecationWarning,
                stacklevel=2)
    return _lookup(name).name


def get_layout(name) -> AttentionLayout:
    """Fetch a layout instance by name. Unlike ``resolve_layout`` this is
    the internal (model-layer) lookup: legacy aliases canonicalize
    silently — the deprecation nudge fires once at the user-facing
    resolution sites (Engine construction, step builders, CLIs)."""
    return _lookup(name)


def dispatch_decode(layout, spec, state: Dict, inputs: DecodeInputs, *,
                    do_select: bool, perm=None):
    """Route one decode step to ``layout``'s decode or ragged_decode hook
    depending on ``inputs.is_ragged`` (trace-time static)."""
    lay = get_layout(layout)
    fn = lay.ragged_decode if inputs.is_ragged else lay.decode
    return fn(spec, state, inputs, do_select=do_select, perm=perm)


def dispatch_decode_window(layout, body, carry, xs, *, length: int):
    """Route a fused decode window (a scan over reuse-step bodies built
    from ``dispatch_decode``) to ``layout``'s decode_window hook."""
    return get_layout(layout).decode_window(body, carry, xs, length=length)


def dispatch_prefill_chunk(layout, spec, state: Dict,
                           inputs: PrefillInputs, *, perm=None):
    """Route one chunked-prefill step to ``layout``'s prefill_chunk
    hook."""
    return get_layout(layout).prefill_chunk(spec, state, inputs, perm=perm)


def dispatch_verify_chunk(layout, spec, state: Dict, inputs: VerifyInputs,
                          *, perm=None):
    """Route one speculative verify attention pass to ``layout``'s
    verify_chunk hook."""
    return get_layout(layout).verify_chunk(spec, state, inputs, perm=perm)


def dispatch_verify_append(layout, spec, state: Dict, inputs: VerifyInputs,
                           accepted, *, perm=None):
    """Route the accepted-prefix commit of a verified chunk to
    ``layout``'s verify_append hook."""
    return get_layout(layout).verify_append(spec, state, inputs, accepted,
                                            perm=perm)


# ---------------------------------------------------------------------------
# Registered layouts
# ---------------------------------------------------------------------------


class DefaultLayout(AttentionLayout):
    """Single-program path: no mesh, no sharding, the §IV-A algorithm as
    plain jittable JAX. The oracle every other layout is compared to."""

    name = LAYOUT_DEFAULT

    def plan(self, cfg, mesh=None) -> LayoutPlan:
        # a caller-provided mesh is kept ambient (e.g. sharding hints)
        # but the state stays unsharded and capacity unrounded
        return LayoutPlan(layout=self.name, mesh=mesh)

    def cache_axes(self, kind: str, *, batch_ok: bool) -> Tuple:
        nd = {"pages": 5, "tau": 4, "meta": 3}[kind]
        return ("batch",) + (None,) * (nd - 1)

    def prefill(self, spec, k, v, length, capacity, perm=None) -> Dict:
        paged, stream = hattn.init_decode_state(spec, k, v, length,
                                                capacity, perm)
        return {"paged": paged, "stream": stream}

    #: physical page→slot striping factor for chunk appends (the
    #: GSPMD layouts keep logical page order; coplace_shmap overrides)
    def _chunk_phys_shards(self) -> int:
        return 1

    def prefill_chunk(self, spec, state, inputs, *, perm=None):
        out, paged, stream = hattn.chunk_prefill_attention(
            spec, inputs.q, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.start,
            inputs.chunk_len, inputs.active, perm=perm,
            phys_shards=self._chunk_phys_shards())
        return out, {"paged": paged, "stream": stream}

    def decode(self, spec, state, inputs, *, do_select, perm=None):
        out, paged, stream = hattn.decode_attention(
            spec, inputs.q, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.lengths,
            do_select=do_select, perm=perm, active=inputs.active,
            need_select=inputs.need_select)
        return out, {"paged": paged, "stream": stream}

    # the default body handles scalar and (B,) lengths uniformly
    ragged_decode = decode

    def decode_rows(self, spec, state, inputs, *, layer, do_select,
                    perm=None):
        out, paged, stream, rows = hattn.decode_attention_rows(
            spec, inputs.q, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.lengths,
            do_select=do_select, perm=perm, active=inputs.active,
            need_select=inputs.need_select, layer=layer)
        return out, {"paged": paged, "stream": stream}, rows

    # speculative verify is a single-program body for every layout: like
    # the chunked-prefill body, its masks are driven by absolute
    # positions/metadata, and _chunk_phys_shards() maps the fixed page
    # sections into coplace_shmap's striped physical order — GSPMD
    # partitions the same program for the placed layouts
    def verify_chunk(self, spec, state, inputs, *, perm=None):
        out, paged, stream = hattn.chunk_verify_attention(
            spec, inputs.q, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.start,
            inputs.active, inputs.need_select, perm=perm,
            phys_shards=self._chunk_phys_shards())
        return out, {"paged": paged, "stream": stream}

    def verify_append(self, spec, state, inputs, accepted, *, perm=None):
        paged, stream = hattn.chunk_verify_append(
            spec, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.start, accepted,
            inputs.active, perm=perm,
            phys_shards=self._chunk_phys_shards())
        return {"paged": paged, "stream": stream}


class _GspmdLayout(DefaultLayout):
    """Shared base for GSPMD-placed layouts: the decode math is the
    default body; the layout lives entirely in ``plan`` +
    ``cache_axes`` (GSPMD partitions the same program differently)."""

    def _default_mesh(self, cfg):
        return jax.make_mesh((1, len(jax.devices())), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    def _validate_mesh(self, mesh, axes=("model",)):
        missing = [a for a in axes if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"layout {self.name!r} requires a mesh with axis(es) "
                f"{missing} (got {tuple(mesh.axis_names)})")
        return mesh

    def plan(self, cfg, mesh=None) -> LayoutPlan:
        mesh = self._validate_mesh(mesh if mesh is not None
                                   else self._default_mesh(cfg))
        nsh = int(mesh.shape["model"])
        quantum = (cfg.h2eal.page_size * nsh if self.shards_pages else 1)
        return LayoutPlan(layout=self.name, mesh=mesh,
                          capacity_quantum=quantum, shard_state=True,
                          balance_shards=nsh if self.shards_pages else 1)


class HeadLayout(_GspmdLayout):
    """Baseline head parallelism (paper Fig 3a): kv-heads → 'model',
    batch → 'data'. No page distribution, so balanced admission is a
    no-op here."""

    name = LAYOUT_HEAD
    shards_pages = False

    def cache_axes(self, kind: str, *, batch_ok: bool) -> Tuple:
        nd = {"pages": 5, "tau": 4, "meta": 3}[kind]
        return ("batch", "model") + (None,) * (nd - 2)


class CoplaceLayout(_GspmdLayout):
    """GSPMD memory-compute co-placement (paper §IV-B): the page dim →
    'model', so each device holds whole pages of every head."""

    name = LAYOUT_COPLACE
    shards_pages = True

    def cache_axes(self, kind: str, *, batch_ok: bool) -> Tuple:
        nd = {"pages": 5, "tau": 4, "meta": 3}[kind]
        return ("batch", None, "model") + (None,) * (nd - 3)


class InterleaveLayout(CoplaceLayout):
    """Co-placement + interleaved storage (paper Fig 7b): pages →
    'model' AND the within-page token dim → 'data', so every page is
    striped across the data axis. Ragged continuous-batching decode
    works through this entry with zero engine changes: ``plan`` rounds
    the capacity, pins the sharded placement, and the default decode
    body is partitioned by GSPMD."""

    name = LAYOUT_INTERLEAVE

    def _default_mesh(self, cfg):
        n = len(jax.devices())
        # within-page striping needs 'data' | page_size; prefer a real
        # data axis when the device count allows one
        data = 2 if (n % 2 == 0 and cfg.h2eal.page_size % 2 == 0) else 1
        return jax.make_mesh((data, n // data), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    def plan(self, cfg, mesh=None) -> LayoutPlan:
        plan = super().plan(cfg, mesh)
        self._validate_mesh(plan.mesh, axes=("model", "data"))
        return plan

    def cache_axes(self, kind: str, *, batch_ok: bool) -> Tuple:
        if kind == "pages" and not batch_ok:
            # batch cannot consume 'data' -> stripe within-page tokens
            return (None, None, "model", "data", None)
        if kind in ("tau", "meta"):
            # Quest min/max metadata + page_start/importance stay
            # replicated: ~1/page_size of the KV bytes, and the SPMD
            # partitioner has miscompiled (or RET_CHECK failed on) the
            # incremental metadata scatter when their page dim is
            # sharded inside the scanned ragged decode body. Only the
            # KV pages themselves are distributed.
            return (None,) * {"tau": 4, "meta": 3}[kind]
        return super().cache_axes(kind, batch_ok=batch_ok)


class CoplaceShmapLayout(CoplaceLayout):
    """Explicit shard_map realization of interleaved co-placement:
    round-robin physical page→shard striping at prefill, per-device
    partial attention over locally-owned pages, cross-device
    log-sum-exp combine (core/hybrid_attention.py). Same plan and cache
    placement as ``coplace`` — only the prefill page order and the
    decode bodies differ."""

    name = LAYOUT_COPLACE_SHMAP

    def plan(self, cfg, mesh=None) -> LayoutPlan:
        plan = super().plan(cfg, mesh)
        # physical pages are striped round-robin over 'model'; the tiered
        # residency controller needs the stripe to map its logical
        # sink/local pins into physical page space (sel_idx/importance
        # are already physical under this layout)
        return dataclasses.replace(
            plan, page_stripe_shards=int(plan.mesh.shape["model"]))

    @staticmethod
    def _ambient_shards() -> int:
        """Round-robin striping factor from the ambient mesh (prefill and
        chunked prefill both run inside the engine's mesh context)."""
        from repro.runtime import hints

        mesh = hints.current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            return int(mesh.shape["model"])
        return 1

    def prefill(self, spec, k, v, length, capacity, perm=None) -> Dict:
        paged, stream = hattn.init_decode_state(
            spec, k, v, length, capacity, perm,
            interleave_shards=self._ambient_shards())
        return {"paged": paged, "stream": stream}

    # chunk appends land on the same physical round-robin page order the
    # shard_map decode body expects; the chunk attention itself is the
    # single-program body partitioned by GSPMD (positions, not slots,
    # drive its masks — see core/paging.py chunk_* helpers)
    def _chunk_phys_shards(self) -> int:
        return self._ambient_shards()

    def decode(self, spec, state, inputs, *, do_select, perm=None):
        out, paged, stream = hattn.decode_attention_coplace(
            spec, inputs.q, inputs.k_new, inputs.v_new,
            state["paged"], state["stream"], inputs.lengths,
            do_select=do_select, perm=perm, active=inputs.active,
            need_select=inputs.need_select)
        return out, {"paged": paged, "stream": stream}

    ragged_decode = decode
    # the shard_map body appends on the owner shard itself
    # (cache.sharded_paged_append), so the layer scan threads its caches
    decode_rows = None


register_layout(DefaultLayout())
register_layout(HeadLayout())
register_layout(CoplaceLayout())
register_layout(InterleaveLayout())
register_layout(CoplaceShmapLayout())
