"""The system under test for a dense GQA configuration: the program's
``ArchConfig`` built from bench/configs/<config>.json, and the program's
parameter tree filled with the weights that the configuration's
reference makes from the seed."""
from __future__ import annotations

import jax.numpy as jnp


def arch_config(conf: dict):
    from repro.configs.base import ArchConfig, H2ealConfig

    return ArchConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        h2eal=H2ealConfig(**conf["h2eal"]),
        source=conf["source"])


def _gain(g):
    """The program's norms scale by (1 + w); exact in bfloat16 for the
    reference's gains near 1."""
    return (g.astype(jnp.float32) - 1.0).astype(g.dtype)


def params(conf: dict, w: dict) -> dict:
    """Program parameters (models/transformer.py layout: one scanned
    period of one block) from the reference's weights."""
    block = {"ln1": _gain(w["ln1"]), "wq": w["wq"], "wk": w["wk"],
             "wv": w["wv"], "wo": w["wo"], "ln2": _gain(w["ln2"]),
             "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_down": w["w_down"]}}
    p = {"embed": w["embed"], "blocks": {"pos0": block}, "rem": {},
         "final_norm": _gain(w["final_norm"])}
    if not conf["tie_word_embeddings"]:
        p["lm_head"] = w["lm_head"]
    return p
