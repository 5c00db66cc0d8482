"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` selects the backend:
  "ref"    — pure-jnp oracle (kernels/ref.py). Used for CPU tests and for
             the multi-pod dry-run (native HLO is what GSPMD partitions and
             what cost_analysis models).
  "pallas" — Pallas TPU kernel (pl.pallas_call), compiled by Mosaic on
             a TPU. On the CPU backend (tests) the wrappers run the kernel
             in interpret mode; any other backend raises. The choice is
             made at call time (``interpret_mode``), never at import, so
             importing this module does not initialise a backend.
             ("kernel" is accepted as a legacy alias.)

Unknown ``impl`` strings raise ValueError (they used to fall through to
the kernel path silently). See docs/kernels.md for the kernel catalog.
"""
from __future__ import annotations

import functools
import warnings

import jax
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels import ref as _ref

VALID_IMPLS = ("ref", "pallas")
_ALIASES = {"kernel": "pallas"}
_warned_aliases: set[str] = set()


def resolve_impl(impl: str) -> str:
    """Canonicalize an ``impl`` string; raise ValueError if unknown.

    Legacy aliases (``"kernel"``) resolve to their canonical impl but
    emit a DeprecationWarning once per process — they will be removed
    after one release.
    """
    if impl in _ALIASES:
        canonical = _ALIASES[impl]
        if impl not in _warned_aliases:
            _warned_aliases.add(impl)
            warnings.warn(
                f"impl={impl!r} is a deprecated alias for "
                f"{canonical!r} and will be removed; pass "
                f"{canonical!r} instead", DeprecationWarning,
                stacklevel=2)
        impl = canonical
    if impl not in VALID_IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; valid impls: "
            f"{', '.join(VALID_IMPLS)} (legacy alias: "
            f"{', '.join(_ALIASES)})")
    return impl


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted on the current backend:
    compiled on "tpu", interpreted on "cpu"; anything else raises rather
    than silently interpreting."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"impl='pallas' needs a TPU (compiled) or the CPU backend "
        f"(interpret mode); the default backend is {backend!r}")


def _pallas(kernel, *args, **static):
    """Call a Pallas kernel, compiled or interpreted per backend.

    GSPMD cannot partition a Mosaic kernel, so under an ambient mesh
    (``jax.set_mesh``) whose automatic axes span more than one device
    the kernel runs inside ``shard_map``, replicated over all of them:
    every device computes it whole, as GSPMD would for replicated
    operands. Inside a shard_map body (manual axes) it runs as is."""
    fn = functools.partial(kernel, interpret=interpret_mode(), **static)
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}
    if any(mesh.shape[a] > 1 for a in auto):
        fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                           axis_names=auto, check_vma=False)
    return fn(*args)


def flash_attention(q, k, v, *, causal=True, window=0, sink=0, q_offset=0,
                    impl="ref"):
    if resolve_impl(impl) == "ref":
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, sink=sink, q_offset=q_offset)
    from repro.kernels import flash_attention as fk
    return _pallas(fk.flash_attention, q, k, v, causal=causal,
                   window=window, sink=sink, q_offset=q_offset)


def paged_attention(q, k, v, valid, *, impl="ref"):
    if resolve_impl(impl) == "ref":
        return _ref.paged_attention_ref(q, k, v, valid)
    from repro.kernels import paged_attention as pk
    return _pallas(pk.paged_attention, q, k, v, valid)


def chunk_attention(q, k, v, valid, *, impl="ref"):
    """Multi-query attention over a gathered KV buffer with per-query
    validity (the chunked-prefill body; kernels/ref.py for the shape
    contract). impl="pallas" streams KV tiles past the VMEM-resident
    chunk of queries with online softmax (kernels/chunk_attention.py);
    it used to silently fall back to the reference body."""
    if resolve_impl(impl) == "ref":
        return _ref.chunk_attention_ref(q, k, v, valid)
    from repro.kernels import chunk_attention as ck
    return _pallas(ck.chunk_attention, q, k, v, valid)


def chunk_attention_paged(q, k_pages, v_pages, page_start, start, k_new,
                          v_new, *, impl="ref"):
    """Chunked-prefill retrieval attention with the page gather fused:
    attends the PRE-append paged buffer (per-key validity from
    page_start) plus the chunk's own KV (static causal mask) in one
    online-softmax stream — no materialized (B, H, Cq, T) mask. See
    kernels.ref.chunk_attention_paged_ref for the shape contract.

    The chunk KV is cast to the cache dtype first so both impls attend
    exactly what a post-append body would have read back."""
    k_new = k_new.astype(k_pages.dtype)
    v_new = v_new.astype(v_pages.dtype)
    if resolve_impl(impl) == "ref":
        return _ref.chunk_attention_paged_ref(
            q, k_pages, v_pages, page_start, start, k_new, v_new)
    from repro.kernels import chunk_attention as ck
    return _pallas(ck.chunk_attention_paged, q, k_pages, v_pages,
                   page_start, start, k_new, v_new)


def paged_attention_partial(q, k, v, valid, *, impl="ref"):
    """Per-shard flash partials (m, l, o) — see
    kernels.ref.paged_attention_partial_ref for the shape contract."""
    if resolve_impl(impl) == "ref":
        return _ref.paged_attention_partial_ref(q, k, v, valid)
    from repro.kernels import paged_attention as pk
    return _pallas(pk.paged_attention_partial, q, k, v, valid)


def combine_partials(m, l, o, *, axis=0, impl="ref"):
    """Combine stacked flash partials into the normalized output.

    m/l: (N, ..., Hq); o: (N, ..., Hq, D) stacked on ``axis``. The pallas
    impl is the fused cross-bank epilogue and requires axis=0 and the
    (N, B, Hq[, D]) layout the co-placement decode produces.
    """
    if resolve_impl(impl) == "ref":
        return _ref.combine_partials_ref(m, l, o, axis=axis)
    if axis != 0:
        raise ValueError(f"pallas combine_partials requires axis=0, "
                         f"got axis={axis}")
    from repro.kernels import paged_attention as pk
    return _pallas(pk.combine_partials, m, l, o)


def page_score(q, tau_min, tau_max, *, impl="ref"):
    if resolve_impl(impl) == "ref":
        return _ref.page_score_ref(q, tau_min, tau_max)
    from repro.kernels import page_score as sk
    return _pallas(sk.page_score, q, tau_min, tau_max)
