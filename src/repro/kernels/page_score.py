"""Pallas TPU page-relevance scoring (Quest min/max metadata).

score(page) = Σ_{g in group} Σ_d max(q_gd · τmin_d, q_gd · τmax_d)
            = Σ_g [ relu(q_g)·τmax + min(q_g, 0)·τmin ]

(the per-coordinate max of a linear function over an interval sits at an
endpoint, picked by sign(q_d) — so the sum-of-maxes is exactly two MXU
matmuls with a sign-split q). The metadata tensors stream through VMEM in
(BC, D) tiles — the paper's memory-die min/max metadata units,
re-expressed for the MXU.

Layout: q (BH, G, D); tau (BH, C, D) -> scores (BH, 1, C), BH = B * Hkv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, tmin_ref, tmax_ref, o_ref):
    q = q_ref[0].astype(jnp.float32)          # (G, D)
    tmin = tmin_ref[0].astype(jnp.float32)    # (BC, D)
    tmax = tmax_ref[0].astype(jnp.float32)    # (BC, D)
    qp = jnp.maximum(q, 0.0)
    qn = jnp.minimum(q, 0.0)
    hi = jnp.dot(qp, tmax.T, preferred_element_type=jnp.float32)  # (G, BC)
    lo = jnp.dot(qn, tmin.T, preferred_element_type=jnp.float32)
    o_ref[0] = (hi + lo).sum(axis=0, keepdims=True)              # (1, BC)


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def page_score(q, tau_min, tau_max, *, bc=512, interpret=False):
    """q: (B, Hq, D); tau_min/max: (B, Hkv, C, D) -> (B, Hkv, C) f32."""
    b, hq, d = q.shape
    h_kv, c = tau_min.shape[1], tau_min.shape[2]
    g = hq // h_kv
    qg = q.reshape(b * h_kv, g, d)
    tn = tau_min.reshape(b * h_kv, c, d)
    tx = tau_max.reshape(b * h_kv, c, d)

    bc_ = min(bc, c)
    nc = pl.cdiv(c, bc_)
    out = pl.pallas_call(
        _kernel,
        grid=(b * h_kv, nc),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((1, bc_, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, bc_, d), lambda bh, ci: (bh, ci, 0)),
        ],
        # (BH, 1, C): a lane row of scores per program, TPU-tileable
        out_specs=pl.BlockSpec((1, 1, bc_), lambda bh, ci: (bh, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b * h_kv, 1, c), jnp.float32),
        interpret=interpret,
    )(qg, tn, tx)
    return out.reshape(b, h_kv, c)
