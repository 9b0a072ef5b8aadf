"""GPT-NeoX (arXiv:2204.06745), plain float32: parallel residual
(x + attn(LN1 x) + mlp(LN2 x)), LayerNorm with bias, multi-head attention,
rotary embedding, a GELU MLP (tanh form, ``gelu_fast``) with biases, and an
untied output head.

Where it follows the program rather than the published model, as the
configuration's file records: rotary embedding over ``rotary_pct`` of each
head, which the program sets to the whole head (published: 25%), and no
biases on the q, k, v and output projections (published: biases). Leaf
names follow the program's state (``neox.*`` per layer, stacked).
"""
from __future__ import annotations

import jax

from . import common as C


def param_table(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    L = cfg["num_hidden_layers"]

    def mat(shape):
        return dict(shape=shape, stack=L, init="normal", scale=shape[0] ** -0.5)

    def vec(width, init):
        return dict(shape=(width,), stack=L, init=init,
                    scale=0.05 if init == "ones" else 0.02)

    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this reference has an untied output head")
    return {
        "embed": dict(shape=(v, d), stack=None, init="normal", scale=0.02),
        "lm_head": dict(shape=(v, d), stack=None, init="normal", scale=0.02),
        "final_norm": dict(shape=(d,), stack=None, init="ones", scale=0.05),
        "final_norm_b": dict(shape=(d,), stack=None, init="bias", scale=0.02),
        "neox.ln1": vec(d, "ones"), "neox.ln1_b": vec(d, "bias"),
        "neox.ln2": vec(d, "ones"), "neox.ln2_b": vec(d, "bias"),
        "neox.wq": mat((d, d)), "neox.wk": mat((d, d)), "neox.wv": mat((d, d)),
        "neox.wo": mat((d, d)),
        "neox.w_in": mat((d, ff)), "neox.b_in": vec(ff, "bias"),
        "neox.w_out_ff": mat((ff, d)), "neox.b_out": vec(d, "bias"),
    }


def loss(params: dict, tokens, cfg: dict, prec: C.Precision, mesh=None):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, eps = d // h, cfg["layer_norm_eps"]
    rot = int(hd * cfg["rotary_pct"])
    theta = cfg["rotary_emb_base"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    x = C.keep_rows(params["embed"][inputs], mesh)
    b, s, _ = x.shape

    def layer(x, p):
        y = C.layer_norm(x, p["ln1"], p["ln1_b"], eps)
        q = prec.mm(y, p["wq"]).reshape(b, s, h, hd)
        k = prec.mm(y, p["wk"]).reshape(b, s, h, hd)
        v = prec.mm(y, p["wv"]).reshape(b, s, h, hd)
        q, k = C.rope(q, theta, rot), C.rope(k, theta, rot)
        attn = prec.mm(C.causal_attention(q, k, v).reshape(b, s, d), p["wo"])
        y = C.layer_norm(x, p["ln2"], p["ln2_b"], eps)
        mlp = prec.mm(jax.nn.gelu(prec.mm(y, p["w_in"]) + p["b_in"],
                                  approximate=True), p["w_out_ff"]) + p["b_out"]
        return C.keep_rows(x + attn + mlp, mesh)

    x = C.scan_layers(layer, x, C.stacked(params, "neox."))
    x = C.layer_norm(x, params["final_norm"], params["final_norm_b"], eps)
    return C.cross_entropy(x, params["lm_head"], labels, prec)
