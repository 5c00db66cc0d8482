"""Sharding hints: ZeRO-3 semantics under GSPMD.

Problem (measured, see EXPERIMENTS.md §Perf): with weights STORED 2D-sharded
(FSDP 'data' on the contraction dim × TP 'model'), GSPMD's matmul strategy
sometimes all-gathers the ACTIVATIONS over the batch axis instead of the
(1000× smaller) weight shards — turning a 4k-token train step into 684 GB
of all-gather per device and replicating attention compute ~250×.

Fix: at every weight use site, constrain the weight to its TP-only spec
(P(None,'model') for (in,out) matrices, P('model',None) for (out,in), …).
GSPMD then materializes the storage→use transfer as a weight all-gather
over 'data' — exactly ZeRO-3 — and the matmul itself is a clean TP matmul
against batch-sharded activations. Activations are additionally pinned to
batch-over-('pod','data') at layer-period boundaries so propagation can
never drift back to replication.

Everything is gated on ``enabled()`` — tests and single-device runs see
plain JAX (constraints require an ambient mesh).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


def enabled() -> bool:
    return getattr(_STATE, "on", False)


@contextlib.contextmanager
def sharding_hints(on: bool = True):
    prev = getattr(_STATE, "on", False)
    _STATE.on = on
    try:
        yield
    finally:
        _STATE.on = prev


def _axes():
    """Axis names of the ambient mesh (``jax.set_mesh``), or None."""
    mesh = current_mesh()
    return None if mesh is None else mesh.axis_names


def current_mesh():
    """The ambient mesh set by ``jax.set_mesh`` (an AbstractMesh during
    tracing), or None when no mesh is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x, spec: P):
    if not enabled():
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def batch_axes_spec():
    ax = _axes()
    if ax is None:
        return None
    return ("pod", "data") if "pod" in ax else ("data",)


_SP_RESIDUAL = threading.local()


def set_sp_residual(on: bool):
    """Enable Megatron-style sequence-parallel residuals + seq-par
    attention. On by default; turned off per-arch when attention heads
    divide the model axis (plain TP attention wins there)."""
    _SP_RESIDUAL.on = on


def sp_residual() -> bool:
    return getattr(_SP_RESIDUAL, "on", True)


def act(x):
    """Pin activations: batch over ('pod','data'), and for full-sequence
    (B, S, d) residuals also sequence over 'model' (Megatron-style
    sequence parallelism — norms are per-token, TP matmul outputs arrive
    as reduce-scatters instead of all-reduces)."""
    if not enabled():
        return x
    ba = batch_axes_spec()
    if ba is None:
        return x
    if x.ndim == 3 and sp_residual():
        spec = P(ba, "model", None)
    else:
        spec = P(ba, *([None] * (x.ndim - 1)))
    return constrain(x, spec)


def pin(x, *axes):
    """Generic pin: axes entries are 'batch' (→ ('pod','data')), a mesh
    axis name, or None. No-op when hints are off / no mesh."""
    if not enabled():
        return x
    ba = batch_axes_spec()
    if ba is None:
        return x
    resolved = tuple(ba if a == "batch" else a for a in axes)
    return constrain(x, P(*resolved))


def decode_qkv(x):
    """Decode-step q/k/v (B, H, D): batch over data, heads replicated —
    uneven head counts (8 kv heads on a 16-way model axis) must never leak
    into the KV cache's sharding, or GSPMD re-gathers the entire stacked
    cache at the scan boundary (measured: 86 GB/step on qwen2-72b)."""
    if not enabled():
        return x
    ba = batch_axes_spec()
    if ba is None:
        return x
    return constrain(x, P(ba, None, None))


def _model_size():
    m = current_mesh()
    if m is None or "model" not in m.axis_names:
        return None
    return int(m.shape["model"])


def attn_q_chunks(qc):
    """Attention sharding for the chunked prefill/train path.
    qc: (B, nq, CQ, H, D).

    * heads divide the model axis → classic head-parallel (Megatron)
      attention: psum-free forward AND backward.
    * otherwise → sequence-parallel attention (beyond-paper; the TPU
      answer to the paper's Challenge-3 head/bank mismatch): shard the
      within-chunk q rows over 'model' — balanced for ANY head count
      (15, 5, 3, ...), at the cost of dk/dv partial-sums in backward."""
    if not enabled():
        return qc
    ba = batch_axes_spec()
    if ba is None:
        return qc
    # NOTE: a head-parallel variant (heads→'model' when divisible) was
    # tried and REFUTED: it conflicts with the sequence-sharded residual
    # and GSPMD falls into involuntary full rematerialization (see
    # EXPERIMENTS.md §Perf cell 1, iteration 5).
    if not sp_residual():
        return qc  # divisible heads: GSPMD's own TP plan is psum-free
    return constrain(qc, P(ba, None, "model", None, None))


def attn_kv(kv):
    """K/V for the chunked path, GQA-expanded: (B, S, Hq, D). Replicated
    over 'model' under sequence-parallel attention (the all-gather is tiny
    next to the compute); untouched under plain TP."""
    if not enabled():
        return kv
    ba = batch_axes_spec()
    if ba is None:
        return kv
    if not sp_residual():
        return kv
    return constrain(kv, P(ba, None, None, None))


def attn_out(out):
    """Chunk outputs, sharded like q. out: (nq, B, CQ, H, D) (scan-stacked)."""
    if not enabled():
        return out
    ba = batch_axes_spec()
    if ba is None:
        return out
    if not sp_residual():
        return out
    return constrain(out, P(None, ba, "model", None, None))


# weight use-time specs by parameter name (mirrors runtime/sharding.py
# storage rules with the 'data' storage axis stripped)
_USE_SPECS = {
    "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
    "w_qkv": P(None, "model"), "w_o": P(None, "model"),
    "w_if": P(None, "model"),
    "in_proj": P(None, "model"), "w": P(None, "model"),
    "w_z": P(None, "model"), "w_x": P(None, "model"),
    "w_B": P(None, "model"), "w_C": P(None, "model"),
    "w_dt": P(None, "model"),
    "wo": P("model", None), "out_proj": P("model", None),
    "lm_head": P(None, "model"),
}
_USE_SPECS_FFN = {
    "w_gate": P(None, "model"), "w_up": P(None, "model"),
    "w_down": P("model", None),
}
# MoE experts are used with their storage sharding — never gathered (a
# 1T-param expert gather would be absurd) and never re-constrained (the
# storage spec is mode-dependent; see runtime/sharding.py)
_USE_SPECS_MOE = {}


def unshard_block_params(p: dict) -> dict:
    """Apply use-time (TP-only) constraints to a block's parameter dict.

    Leaves not named here (norms, biases, metadata) pass through. The
    constraint is a no-op when hints are disabled or no mesh is ambient.
    """
    if not enabled() or _axes() is None:
        return p

    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                if k == "moe":
                    sub = dict(v)
                    for kk, spec in _USE_SPECS_MOE.items():
                        if kk in sub and sub[kk].ndim == 3:
                            sub[kk] = constrain(sub[kk], spec)
                    if "shared" in sub:
                        sh = dict(sub["shared"])
                        for kk, spec in _USE_SPECS_FFN.items():
                            if kk in sh:
                                sh[kk] = constrain(sh[kk], spec)
                        sub["shared"] = sh
                    out[k] = sub
                elif k == "ffn":
                    sub = dict(v)
                    for kk, spec in _USE_SPECS_FFN.items():
                        if kk in sub:
                            sub[kk] = constrain(sub[kk], spec)
                    out[k] = sub
                else:
                    out[k] = walk(v)
            else:
                spec = _USE_SPECS.get(k)
                if spec is not None and v.ndim == len(spec):
                    out[k] = constrain(v, spec)
                else:
                    out[k] = v
        return out

    return walk(p)
