"""A test-only second architecture: a windowed GQA decoder whose layers
alternate between two parameter groups.

The program runs it with ``block_pattern=("attn", "attn")``: groups
``b0_attn`` and ``b1_attn``, each stacked over half the layers, applied in
turn (b0, b1, b0, b1, ...), and every attention layer masked to a sliding
window of ``sliding_window`` tokens. Its weights are made one group at a
time and handed over as host arrays, as an architecture too large for the
chip in float32 would make them. Its reference, below, is written from the
same equations as the dense decoder's, with the window and the two groups.
"""
from __future__ import annotations

import functools
import types
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.arch import Attention, Projection
from bench.reference.llama import _mm, rms_norm, rope
from bench.work import model as work

GROUPS = ("b0_attn", "b1_attn")


def sizes(cfg: dict) -> dict:
    m = cfg["model"]
    return {"L": m["num_hidden_layers"] // len(GROUPS),
            "d": m["hidden_size"], "H": m["num_attention_heads"],
            "KV": m["num_key_value_heads"], "hd": m["head_dim"],
            "ff": m["intermediate_size"], "V": m["vocab_size"],
            "window": m["sliding_window"]}


def vocab(cfg: dict) -> int:
    return cfg["model"]["vocab_size"]


def program_model(cfg: dict, remat: bool = False):
    from repro.configs.base import ModelConfig
    from repro.models.transformer import make_model
    m, s = cfg["model"], sizes(cfg)
    mcfg = ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["num_hidden_layers"],
        d_model=s["d"], n_heads=s["H"], n_kv_heads=s["KV"],
        head_dim=s["hd"], d_ff=s["ff"], vocab=s["V"],
        block_pattern=("attn",) * len(GROUPS), attn_window=s["window"],
        rope_theta=float(m["rope_theta"]), tie_embeddings=True,
        norm="rmsnorm", mlp_gated=True, act=m["hidden_act"],
        compute_dtype=cfg["compute_dtype"], param_dtype="float32")
    return mcfg, make_model(mcfg, remat=remat)


def projections(cfg: dict) -> dict:
    s = sizes(cfg)
    d, ff, hd = s["d"], s["ff"], s["hd"]
    shapes = {"wq": ("attn", "wq", s["H"] * hd, d),
              "wk": ("attn", "wk", s["KV"] * hd, d),
              "wv": ("attn", "wv", s["KV"] * hd, d),
              "wo": ("attn", "wo", d, s["H"] * hd),
              "wi": ("mlp", "wi", ff, d),
              "wg": ("mlp", "wg", ff, d),
              "mlp_wo": ("mlp", "wo", d, ff)}
    return {f"{g}.{n}": Projection(("layers", g, sub, leaf), o, i, s["L"])
            for g in GROUPS for n, (sub, leaf, o, i) in shapes.items()}


def attention(cfg: dict) -> list:
    s = sizes(cfg)
    return [Attention(s["L"], s["H"], s["KV"], s["hd"], s["window"])
            for _ in GROUPS]


def _group_fn(cfg: dict):
    s = sizes(cfg)
    L, d, H, KV, hd = (s[k] for k in ("L", "d", "H", "KV", "hd"))
    comp = cfg["compression"]
    block, sp = tuple(comp["block"]), float(comp["sparsity"])
    shapes = {n: (p.out, p.inp) for n, p in projections(cfg).items()
              if n.startswith(GROUPS[0])}

    def make(key):
        keys = jax.random.split(key, len(shapes))
        v = {n.split(".")[1]: W.block_sparse(k, L, o, i, block, sp)
             for k, (n, (o, i)) in zip(keys, shapes.items())}
        ones = {"scale": jnp.ones((L, d), jnp.float32)}
        t = {n: x.transpose(0, 2, 1) for n, x in v.items()}
        return {"pre_norm": ones, "ffn_norm": dict(ones),
                "attn": {"wq": t["wq"].reshape(L, d, H, hd),
                         "wk": t["wk"].reshape(L, d, KV, hd),
                         "wv": t["wv"].reshape(L, d, KV, hd),
                         "wo": t["wo"].reshape(L, H, hd, d)},
                "mlp": {"wi": t["wi"], "wg": t["wg"], "wo": t["mlp_wo"]}}

    return jax.jit(make)


def weights(cfg: dict, seed: int):
    """One group at a time on the device, each copied to the host."""
    s = sizes(cfg)
    key = W.seed_key(seed)
    make = _group_fn(cfg)
    layers = {g: jax.device_get(make(jax.random.fold_in(key, i + 1)))
              for i, g in enumerate(GROUPS)}
    std = float(cfg["model"]["initializer_range"])
    embed = std * jax.random.normal(jax.random.fold_in(key, 0),
                                    (s["V"], s["d"]), jnp.float32)
    return {"embed": {"embedding": np.asarray(embed)}, "layers": layers,
            "final_norm": {"scale": np.ones((s["d"],), np.float32)}}


def serve_tick_flops(cfg: dict, start_pos, n_tokens,
                     stored_weights: int) -> float:
    s = sizes(cfg)
    n = np.asarray(n_tokens, np.int64)
    attn = sum(4.0 * a.heads * a.hd * a.layers
               * work.attention_keys(start_pos, n, a.window)
               for a in attention(cfg))
    return (2.0 * int(n.sum()) * stored_weights
            + 2.0 * int((n > 0).sum()) * s["V"] * s["d"] + attn)


def train_step_flops(cfg: dict, batch: int, seq_len: int,
                     stored_weights: int) -> float:
    s = sizes(cfg)
    attn = sum(4.0 * a.heads * a.hd * a.layers
               * work.attention_keys([0] * batch, [seq_len] * batch,
                                     a.window)
               for a in attention(cfg))
    return (6.0 * batch * seq_len * (stored_weights + s["V"] * s["d"])
            + 3.0 * attn)


# -- the plain reference ------------------------------------------------

def _layer(x, lp, pos, valid, eps, theta, window, quant):
    a = lp["attn"]
    S = x.shape[0]
    H, KV, hd = a["wq"].shape[1], a["wk"].shape[1], a["wq"].shape[2]
    h = rms_norm(x, lp["pre_norm"]["scale"], eps)
    q = rope(_mm("sd,dhk->shk", h, a["wq"], quant), pos, theta)
    k = rope(_mm("sd,dhk->shk", h, a["wk"], quant), pos, theta)
    v = _mm("sd,dhk->shk", h, a["wv"], quant)
    qg = q.reshape(S, KV, H // KV, hd)
    s = _mm("qkgh,ckh->kgqc", qg, k, quant) * hd ** -0.5
    near = (pos[:, None] - pos[None, :]) < window
    mask = (pos[None, :] <= pos[:, None]) & near & valid[None, :]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    o = _mm("kgqc,ckh->qkgh", p, v, quant).reshape(S, H, hd)
    x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
    h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
    mlp = lp["mlp"]
    gate = jax.nn.silu(_mm("sd,df->sf", h, mlp["wg"], quant))
    up = _mm("sd,df->sf", h, mlp["wi"], quant)
    return x + _mm("sf,fd->sd", gate * up, mlp["wo"], quant)


def _stack(weights, x, pos, valid, eps, theta, window, quant, remat):
    def body(x, pair):
        for lp in pair:
            x = _layer(x, lp, pos, valid, eps, theta, window, quant)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    pairs = tuple(weights["layers"][g] for g in GROUPS)
    return jax.lax.scan(body, x, pairs)[0]


@functools.partial(jax.jit,
                   static_argnames=("eps", "theta", "window", "quant"))
def _logits_at(weights, tokens, n_valid, at, *, eps, theta, window, quant):
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    emb = weights["embed"]["embedding"]
    x = _stack(weights, emb[tokens], pos, pos < n_valid, eps, theta, window,
               quant, False)
    x = rms_norm(x[at], weights["final_norm"]["scale"], eps)
    return _mm("sd,vd->sv", x, emb, quant)


def _kw(cfg):
    m = cfg["model"]
    return dict(eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]),
                window=int(m["sliding_window"]))


def logits_at(weights, cfg: dict, tokens, n_valid: int, at,
              quant: Optional[str] = None):
    return _logits_at(weights, jnp.asarray(tokens, jnp.int32),
                      jnp.int32(n_valid), jnp.asarray(at, jnp.int32),
                      quant=quant, **_kw(cfg))


@functools.partial(jax.jit, static_argnames=("eps", "theta", "window",
                                             "z_weight", "quant"))
def _batch_grad(weights, inputs, labels, *, eps, theta, window, z_weight,
                quant):
    def loss(w):
        def row(inp, lab):
            pos = jnp.arange(inp.shape[0], dtype=jnp.int32)
            emb = w["embed"]["embedding"]
            x = _stack(w, emb[inp], pos, jnp.ones(pos.shape, bool), eps,
                       theta, window, quant, True)
            x = rms_norm(x, w["final_norm"]["scale"], eps)
            logits = _mm("sd,vd->sv", x, emb, quant)
            lse = jax.nn.logsumexp(logits, axis=-1)
            nll = lse - jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
            return jnp.mean(nll) + z_weight * jnp.mean(lse * lse)
        return jnp.mean(jax.vmap(row)(inputs, labels))
    return jax.value_and_grad(loss)(weights)


def loss_and_grad(weights, cfg: dict, inputs, labels, z_weight: float,
                  quant: Optional[str] = None):
    """Mean loss and gradient over the whole batch at once (test sizes)."""
    return _batch_grad(weights, jnp.asarray(inputs), jnp.asarray(labels),
                       z_weight=float(z_weight), quant=quant, **_kw(cfg))


reference = types.SimpleNamespace(logits_at=logits_at,
                                  loss_and_grad=loss_and_grad)
