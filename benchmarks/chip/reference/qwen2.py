"""Qwen2 (arXiv:2407.10671), plain float32: pre-norm decoder with RMSNorm,
grouped-query attention with biases on q, k and v, rotary embedding over
the whole head, a SiLU-gated MLP and tied input and output embeddings.

Leaf names follow the program's state (``attn.*`` per layer, stacked) so
that the comparison pairs them; the values come from ``weights``. It
follows the published model in every equation used here.
"""
from __future__ import annotations

import jax

from . import common as C


def param_table(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    L = cfg["num_hidden_layers"]

    def mat(shape, scale=None):
        return dict(shape=shape, stack=L, init="normal",
                    scale=scale or shape[0] ** -0.5)

    def vec(width, init):
        return dict(shape=(width,), stack=L, init=init,
                    scale=0.05 if init == "ones" else 0.02)

    table = {
        "embed": dict(shape=(v, d), stack=None, init="normal", scale=0.02),
        "final_norm": dict(shape=(d,), stack=None, init="ones", scale=0.05),
        "attn.ln1": vec(d, "ones"), "attn.ln2": vec(d, "ones"),
        "attn.wq": mat((d, h * hd)), "attn.wk": mat((d, kv * hd)),
        "attn.wv": mat((d, kv * hd)), "attn.wo": mat((h * hd, d)),
        "attn.bq": vec(h * hd, "bias"), "attn.bk": vec(kv * hd, "bias"),
        "attn.bv": vec(kv * hd, "bias"),
        "attn.w_gate": mat((d, ff)), "attn.w_up": mat((d, ff)),
        "attn.w_down": mat((ff, d)),
    }
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("this reference ties the output head to the embedding")
    return table


def loss(params: dict, tokens, cfg: dict, prec: C.Precision, mesh=None):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], d // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    emb = prec.weight(params["embed"])
    x = C.keep_rows(emb[inputs], mesh)
    b, s, _ = x.shape

    def layer(x, p):
        y = C.rms_norm(x, p["ln1"], eps)
        q = (prec.mm(y, p["wq"]) + p["bq"]).reshape(b, s, h, hd)
        k = (prec.mm(y, p["wk"]) + p["bk"]).reshape(b, s, kv, hd)
        v = (prec.mm(y, p["wv"]) + p["bv"]).reshape(b, s, kv, hd)
        q, k = C.rope(q, theta, hd), C.rope(k, theta, hd)
        o = C.causal_attention(q, k, v).reshape(b, s, h * hd)
        x = x + prec.mm(o, p["wo"])
        y = C.rms_norm(x, p["ln2"], eps)
        g = jax.nn.silu(prec.mm(y, p["w_gate"])) * prec.mm(y, p["w_up"])
        return C.keep_rows(x + prec.mm(g, p["w_down"]), mesh)

    x = C.scan_layers(layer, x, C.stacked(params, "attn."))
    x = C.rms_norm(x, params["final_norm"], eps)
    return C.cross_entropy(x, params["embed"], labels, prec)
