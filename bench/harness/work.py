"""Operations and bytes that the model's work needs, from its shapes.

Counted from the configuration and the context of each token, never
from the compiled program: what the step must do, so that a share of a
peak is a lower bound on the time and cannot pass 100%. Adapted from the
program's analytical byte model (runtime/perfmodel.py), with the decode
KV traffic counted as the tokens a query attends, not as the fixed-size
page copy that the program gathers.

Dense GQA decoder with H2EAL (see the reference's docstring for what a
query attends). Weights and KV are bfloat16.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def _h2(conf: dict) -> dict:
    h = conf["h2eal"]
    hk = conf["num_key_value_heads"]
    n_ret = hk - round(hk * h["static_sparsity"])
    page = h["page_size"]
    return dict(n_ret=n_ret, n_str=hk - n_ret, sink=h["sink"],
                local=h["local"], page=page,
                n_sink=-(-h["sink"] // page) if h["sink"] else 0,
                top_k=max(1, h["select_budget"] // page))


def attended(conf: dict, ctx: int) -> tuple:
    """Positions one decode query attends per retrieval and per
    streaming KV head, at context ctx (the query included)."""
    h = _h2(conf)
    page = h["page"]
    first_local = max(ctx - h["local"], 0) // page
    sink_tok = min(ctx, h["n_sink"] * page)
    local_lo = max(first_local, h["n_sink"]) * page
    local_tok = max(0, ctx - local_lo)
    sel_tok = page * min(h["top_k"], max(first_local - h["n_sink"], 0))
    stream = min(ctx, h["sink"]) + max(0, ctx - max(h["sink"],
                                                    ctx - h["local"]))
    return sink_tok + local_tok + sel_tok, stream


def _prefill_attended(conf: dict, p: int) -> tuple:
    """Positions a prompt query at position p attends (retrieval: all up
    to p; streaming: sink + local)."""
    h = _h2(conf)
    ctx = p + 1
    stream = min(ctx, h["sink"]) + max(0, ctx - max(h["sink"],
                                                    ctx - h["local"]))
    return ctx, stream


def layer_params(conf: dict) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hk, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    return d * hq * hd * 2 + 2 * d * hk * hd + 3 * d * f + 2 * d


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def _attn_flops(conf: dict, ret_tok: int, str_tok: int) -> float:
    h = _h2(conf)
    g = conf["num_attention_heads"] // conf["num_key_value_heads"]
    hd = conf["head_dim"]
    per_layer = 4 * hd * g * (ret_tok * h["n_ret"] + str_tok * h["n_str"])
    return per_layer * conf["num_hidden_layers"]


def decode_flops(conf: dict, ctx: int) -> float:
    """One decode token at context ctx, without the LM head."""
    return (2 * conf["num_hidden_layers"] * layer_params(conf)
            + _attn_flops(conf, *attended(conf, ctx)))


def prefill_flops(conf: dict, start: int, n: int) -> float:
    """Prompt positions start .. start + n - 1, without the LM head."""
    # sum over p of the attended counts, in closed form where cheap
    ret = sum(range(start + 1, start + n + 1))
    stream = sum(_prefill_attended(conf, p)[1]
                 for p in range(start, start + n))
    return (2 * n * conf["num_hidden_layers"] * layer_params(conf)
            + _attn_flops(conf, ret, stream))


def head_flops(conf: dict) -> float:
    """The LM head for one output token."""
    return 2 * head_params(conf)


def decode_step_bytes(conf: dict, ctxs) -> float:
    """HBM bytes one batched decode step must move: every weight once,
    the attended KV of each decoding slot once, the new KV written, and
    the embedding rows read."""
    wb = BYTES[conf.get("torch_dtype", "bfloat16")]
    h = _h2(conf)
    hd, n = conf["head_dim"], conf["num_hidden_layers"]
    hk, d = conf["num_key_value_heads"], conf["hidden_size"]
    weights = (n * layer_params(conf) + head_params(conf) + d) * wb
    kv = 0
    for ctx in ctxs:
        r, s = attended(conf, ctx)
        kv += n * hd * 2 * wb * (r * h["n_ret"] + s * h["n_str"])
        kv += n * hk * hd * 2 * wb + d * wb
    return weights + kv
