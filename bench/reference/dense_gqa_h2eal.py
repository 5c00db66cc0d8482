"""Plain reference for a dense GQA decoder (Llama/Mistral block) served
with H2EAL hybrid sparse attention, and its weights made from a seed.

It imports nothing of the program. The weights are made here from the
seed, in bfloat16 (the type they are served in); the reference reads
those values in float32 and computes at full float32 matmul precision.

Block: x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x));
x += Wdown·(silu(Wgate·n2(x)) * Wup·n2(x)); n(x) = x / rms(x) * g.
RoPE rotates the two halves of each head (rotate_half), with inverse
frequencies theta^(-i/half). Query head h reads KV head h // group.

H2EAL (arXiv:2508.16653, §IV-A), as the configuration's ``h2eal`` group
states it. The first ``n_kv - round(n_kv * static_sparsity)`` KV heads
are retrieval heads, the rest streaming heads. Pages hold ``page_size``
consecutive positions. For a query at position p with ctx = p + 1:

* streaming heads attend positions j <= p with j < sink or j > p - local,
  in prefill and decode alike;
* retrieval heads attend every j <= p while the prompt is processed
  (p < prompt length);
* in decode, retrieval heads attend the sink pages (the first
  ceil(sink / page) pages), the positions from the local page boundary
  max(first_local, n_sink) * page on, where first_local =
  max(ctx - local, 0) // page, and the selected pages that lie in
  [n_sink, first_local). Decode step j (the query at prompt length + j)
  uses the selection made at step w * (j // w), w the share window: the
  top select_budget // page_size pages in [n_sink, first_local) of that
  step, scored per KV head by the Quest bound summed over the head's
  query group, sum_d max(q_d * min_page k_d, q_d * max_page k_d).

``logit_gaps`` returns, for each served token, by how much its logit
lies below the largest logit of the reference at that position. The
control (``control=True``) computes the same model with every matmul operand
(weights per output channel, activations per row, keys and values per
head vector) rounded to float8 e4m3: the precision below bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
EMBED_STD = 0.1
NORM_STD = 0.1
ROW_BLOCK = 128       # query rows per attention block
MLP_BLOCK = 2048      # rows per MLP block
LOGIT_BLOCK = 256     # served positions per logits block


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(conf: dict) -> dict:
    """name -> (shape, std); norms are 1 + std * N(0, 1)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, n = conf["head_dim"], conf["num_hidden_layers"]
    v = conf["vocab_size"]
    shapes = {
        "embed": ((v, d), EMBED_STD),
        "ln1": ((n, d), NORM_STD),
        "wq": ((n, d, hq * hd), d ** -0.5),
        "wk": ((n, d, hk * hd), d ** -0.5),
        "wv": ((n, d, hk * hd), d ** -0.5),
        "wo": ((n, hq * hd, d), (hq * hd) ** -0.5),
        "ln2": ((n, d), NORM_STD),
        "w_gate": ((n, d, f), d ** -0.5),
        "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), f ** -0.5),
        "final_norm": ((d,), NORM_STD),
    }
    if not conf["tie_word_embeddings"]:
        shapes["lm_head"] = ((d, v), d ** -0.5)
    return shapes


def seed_words(seed: int):
    """Two int32 words of a seed up to 62 bits, for a jitted key."""
    return (np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF))


def make_weights(conf: dict, lo, hi) -> dict:
    """bfloat16 weights from the seed words (trace under jax.jit)."""
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out = {}
    for i, (name, (shape, std)) in enumerate(
            sorted(weight_shapes(conf).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = (1.0 + std * z) if name in ("ln1", "ln2", "final_norm") \
            else std * z
        out[name] = w.astype(jnp.bfloat16)
    return out


@functools.lru_cache(maxsize=None)
def _weights_fn(conf_key):
    conf = dict(conf_key)
    return jax.jit(lambda lo, hi: make_weights(conf, lo, hi))


def _freeze(conf: dict):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def weights(conf: dict, seed: int) -> dict:
    return _weights_fn(_freeze(conf))(*seed_words(seed))


# ---------------------------------------------------------------------------
# float32 forward with H2EAL masks
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32 at full precision."""
    w = w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _norm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, H, D) rotated at positions pos (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _h2(conf):
    h = conf["h2eal"]
    hk = conf["num_key_value_heads"]
    n_ret = hk - round(hk * h["static_sparsity"])
    page = h["page_size"]
    return dict(n_ret=n_ret, sink=h["sink"], local=h["local"], page=page,
                n_sink=-(-h["sink"] // page) if h["sink"] else 0,
                top_k=max(1, h["select_budget"] // page),
                window=max(h["share_window"], 1))


def _selection(conf, q, k, prompt_len):
    """Page selection of every possible select step: (M, Hr, C) bool.

    Select step m is the query at prompt_len + w * m; pages selectable
    there lie in [n_sink, first_local(ctx)) and are complete."""
    h = _h2(conf)
    t = k.shape[0]
    page, w, nr = h["page"], h["window"], h["n_ret"]
    hd = q.shape[-1]
    group = q.shape[1] // k.shape[1]
    c = t // page
    m = t // w
    kr = k[:, :nr].reshape(c, page, nr, hd)                # (C, P, Hr, D)
    tmin = kr.min(axis=1).transpose(1, 0, 2)               # (Hr, C, D)
    tmax = kr.max(axis=1).transpose(1, 0, 2)
    spos = prompt_len + w * jnp.arange(m)                   # (M,)
    qs = q[jnp.minimum(spos, t - 1), : nr * group]          # (M, HqR, D)
    qs = qs.reshape(m, nr, group, hd)
    score = (jnp.einsum("mhgd,hcd->mhc", jnp.maximum(qs, 0), tmax,
                        precision=HI)
             + jnp.einsum("mhgd,hcd->mhc", jnp.minimum(qs, 0), tmin,
                          precision=HI))
    ctx = spos + 1
    first_local = jnp.maximum(ctx - h["local"], 0) // page
    cidx = jnp.arange(c)
    ok = ((cidx[None, :] >= h["n_sink"])
          & (cidx[None, :] < first_local[:, None]))[:, None, :]
    score = jnp.where(ok, score, NEG)
    k_eff = min(h["top_k"], c)
    vals, idx = jax.lax.top_k(score, k_eff)                 # (M, Hr, K)
    hit = (vals > NEG / 2)
    sel = jnp.zeros((m, nr, c), bool)
    mi = jnp.arange(m)[:, None, None]
    hi_ = jnp.arange(nr)[None, :, None]
    return sel.at[mi, hi_, idx].max(hit)


def allowed(conf, pos_blk, t, sel, prompt_len, n_valid):
    """Which keys (R, Hkv, T) the queries at positions pos_blk attend."""
    h = _h2(conf)
    hk = conf["num_key_value_heads"]
    r = pos_blk.shape[0]
    nr, page = h["n_ret"], h["page"]
    kp = jnp.arange(t)
    p = pos_blk[:, None]
    causal = (kp[None, :] <= p) & (kp[None, :] < n_valid)       # (R, T)
    stream = causal & ((kp[None, :] < h["sink"])
                       | (kp[None, :] > p - h["local"]))
    ctx = pos_blk + 1
    first_local = (jnp.maximum(ctx - h["local"], 0) // page)[:, None]
    kpage = kp // page
    sink_tok = kpage[None, :] < h["n_sink"]
    local_tok = kp[None, :] >= jnp.maximum(first_local, h["n_sink"]) * page
    step = jnp.clip((pos_blk - prompt_len) // h["window"], 0,
                    sel.shape[0] - 1)
    sel_tok = jnp.take(sel[step], kpage, axis=2)                # (R, Hr, T)
    sel_tok = sel_tok & ((kpage[None, :] >= h["n_sink"])
                         & (kpage[None, :] < first_local))[:, None, :]
    dec = causal[:, None, :] & ((sink_tok | local_tok)[:, None, :] | sel_tok)
    pre = jnp.broadcast_to(causal[:, None, :], dec.shape)
    ret = jnp.where((pos_blk >= prompt_len)[:, None, None], dec, pre)
    return jnp.concatenate(
        [ret, jnp.broadcast_to(stream[:, None, :], (r, hk - nr, t))], axis=1)


def _attend_block(conf, q_blk, pos_blk, k, v, sel, prompt_len, n_valid,
                  quant):
    """Attention of query rows at positions pos_blk (R,) over all keys."""
    t, hk, hd = k.shape
    r = q_blk.shape[0]
    group = q_blk.shape[1] // hk
    allow = allowed(conf, pos_blk, t, sel, prompt_len, n_valid)
    qg = q_blk.reshape(r, hk, group, hd)
    if quant:
        qg = _q8(qg, -1)
    s = jnp.einsum("rhgd,thd->rhgt", qg, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(allow[:, :, None, :], s, NEG)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhgt,thd->rhgd", prob, v, precision=HI)
    return o.reshape(r, hk * group, hd)


def _layer(conf, quant, x, lw, pos, prompt_len, n_valid):
    hq, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, eps = conf["head_dim"], conf["rms_norm_eps"]
    t = x.shape[0]
    hn = _norm(x, lw["ln1"], eps)
    q = _rope(_mm(hn, lw["wq"], quant).reshape(t, hq, hd), pos,
              conf["rope_theta"])
    k = _rope(_mm(hn, lw["wk"], quant).reshape(t, hk, hd), pos,
              conf["rope_theta"])
    v = _mm(hn, lw["wv"], quant).reshape(t, hk, hd)
    if quant:
        k, v = _q8(k, -1), _q8(v, -1)
    sel = _selection(conf, q, k, prompt_len)
    rb = min(ROW_BLOCK, t)
    blocks = (q.reshape(t // rb, rb, hq, hd), pos.reshape(t // rb, rb))
    o = jax.lax.map(lambda a: _attend_block(conf, a[0], a[1], k, v, sel,
                                            prompt_len, n_valid, quant),
                    blocks)
    x = x + _mm(o.reshape(t, hq * hd), lw["wo"], quant)
    mb = min(MLP_BLOCK, t)

    def mlp(xb):
        hb = _norm(xb, lw["ln2"], eps)
        g = _mm(hb, lw["w_gate"], quant)
        u = _mm(hb, lw["w_up"], quant)
        return xb + _mm(jax.nn.silu(g) * u, lw["w_down"], quant)
    return jax.lax.map(mlp, x.reshape(t // mb, mb, -1)).reshape(x.shape)


LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
              "w_down")


@functools.lru_cache(maxsize=None)
def _forward_fn(conf_key, h2_key, quant):
    conf = dict(conf_key)
    conf["h2eal"] = dict(h2_key)

    def forward(w, tokens, prompt_len, n_valid):
        t = tokens.shape[0]
        pos = jnp.arange(t, dtype=jnp.int32)
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        layers = {k: w[k] for k in LAYER_KEYS}
        x, _ = jax.lax.scan(
            lambda x, lw: (_layer(conf, quant, x, lw, pos, prompt_len,
                                  n_valid), None), x, layers)
        return _norm(x, w["final_norm"], conf["rms_norm_eps"])
    return jax.jit(forward)


@functools.lru_cache(maxsize=None)
def _logits_fn(conf_key, quant):
    conf = dict(conf_key)

    def logits(w, hidden_rows):
        head = (w["embed"].T if conf["tie_word_embeddings"]
                else w["lm_head"])
        return _mm(hidden_rows, head, quant)
    return jax.jit(logits)


def _hidden(conf, w, tokens_np, prompt_len, quant):
    t_pad = int(conf.get("reference_length", len(tokens_np)))
    toks = np.zeros((t_pad,), np.int32)
    toks[:len(tokens_np)] = tokens_np
    fwd = _forward_fn(_freeze(conf), _freeze(conf["h2eal"]), quant)
    return fwd(w, jnp.asarray(toks), jnp.int32(prompt_len),
               jnp.int32(len(tokens_np)))


def logit_gaps(conf: dict, w: dict, prompt: np.ndarray, served: np.ndarray,
               *, control: bool = False) -> dict:
    """Gaps of the served tokens below the reference's best logit.

    ``prompt`` (L,), ``served`` (n,): served[i] came from the logits at
    position L - 1 + i. Returns {"gap": (n,) gaps of the served tokens,
    "flips": served tokens that are not the reference's argmax}, and with
    ``control`` also "control_gap": the gaps of the tokens that the fp8
    computation puts first at the same positions.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n = len(served)
    toks = np.concatenate([prompt, served[:-1]])
    start = len(prompt) - 1
    ref_h = _hidden(conf, w, toks, len(prompt), False)
    ctl_h = _hidden(conf, w, toks, len(prompt), True) if control else None
    lg = _logits_fn(_freeze(conf), False)
    lq = _logits_fn(_freeze(conf), True)
    gaps, cgaps, flips = [], [], 0
    t_pad = ref_h.shape[0]
    for b0 in range(0, n, LOGIT_BLOCK):
        rows = np.arange(start + b0, start + b0 + LOGIT_BLOCK)
        rows_c = jnp.asarray(np.minimum(rows, t_pad - 1))
        ref = np.asarray(lg(w, ref_h[rows_c]))
        m = min(LOGIT_BLOCK, n - b0)
        ref = ref[:m]
        tok = served[b0:b0 + m]
        best = ref.max(axis=1)
        gaps.append(best - ref[np.arange(m), tok])
        flips += int(np.sum(ref.argmax(axis=1) != tok))
        if control:
            ctl = np.asarray(lq(w, ctl_h[rows_c]))[:m]
            pick = ctl.argmax(axis=1)
            cgaps.append(best - ref[np.arange(m), pick])
    out = {"gap": np.concatenate(gaps), "flips": flips}
    if control:
        out["control_gap"] = np.concatenate(cgaps)
    return out
