"""Dense GQA decoder (Llama / Qwen3 family): the program side of the seam.

The program runs it as ``family="dense"`` with one layer group,
``b0_attn`` (attention and a gated MLP), stacked over every layer. Its
plain reference is ``bench/reference/llama.py``.

Weights come from one jitted call on the device: every leaf drawn from the
seed, 90 % (the configuration's sparsity) of each projection's blocks
exactly zero. The tied embedding is drawn at the published
``initializer_range``: small against what the layers add, so the last
token's own embedding does not dominate the logits (with unit-scale
embeddings a random tied model only repeats its input, and no served token
would ever be near a tie).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.arch import Attention, Projection
from bench.reference import llama as reference  # noqa: F401  (arch.reference)
from bench.work import model as work

GROUP = "b0_attn"


def sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "d": d, "H": h,
            "KV": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // h,
            "ff": m["intermediate_size"], "V": m["vocab_size"]}


def vocab(cfg: dict) -> int:
    return cfg["model"]["vocab_size"]


def program_model(cfg: dict, remat: bool = False):
    """The program's ModelConfig and Model for a benchmark configuration."""
    from repro.configs.base import ModelConfig
    from repro.models.transformer import make_model
    m = cfg["model"]
    mcfg = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim"), d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), norm="rmsnorm",
        mlp_gated=True, act=m["hidden_act"],
        compute_dtype=cfg["compute_dtype"], param_dtype="float32")
    return mcfg, make_model(mcfg, remat=remat)


def projections(cfg: dict) -> dict:
    s = sizes(cfg)
    d, ff, hd, L = s["d"], s["ff"], s["hd"], s["L"]

    def proj(sub, leaf, out, inp):
        return Projection(("layers", GROUP, sub, leaf), out, inp, L)

    return {"wq": proj("attn", "wq", s["H"] * hd, d),
            "wk": proj("attn", "wk", s["KV"] * hd, d),
            "wv": proj("attn", "wv", s["KV"] * hd, d),
            "wo": proj("attn", "wo", d, s["H"] * hd),
            "wi": proj("mlp", "wi", ff, d),
            "wg": proj("mlp", "wg", ff, d),
            "mlp_wo": proj("mlp", "wo", d, ff)}


def attention(cfg: dict) -> list:
    s = sizes(cfg)
    return [Attention(s["L"], s["H"], s["KV"], s["hd"], None)]


def make_weights_fn(cfg: dict):
    """jit(key) -> dense params in the program's layout, block-sparse."""
    s = sizes(cfg)
    L, d, H, KV, hd, V = (s[k] for k in ("L", "d", "H", "KV", "hd", "V"))
    comp = cfg["compression"]
    block, sp = tuple(comp["block"]), float(comp["sparsity"])
    projs = projections(cfg)
    init_std = float(cfg["model"]["initializer_range"])

    def make(key):
        keys = dict(zip(["embed"] + sorted(projs),
                        jax.random.split(key, 1 + len(projs))))
        view = {n: W.block_sparse(keys[n], L, p.out, p.inp, block, sp)
                for n, p in projs.items()}
        attn = {
            # (out, in) views back to the stored layouts
            "wq": view["wq"].transpose(0, 2, 1).reshape(L, d, H, hd),
            "wk": view["wk"].transpose(0, 2, 1).reshape(L, d, KV, hd),
            "wv": view["wv"].transpose(0, 2, 1).reshape(L, d, KV, hd),
            "wo": view["wo"].transpose(0, 2, 1).reshape(L, H, hd, d),
        }
        if cfg.get("qk_norm"):
            attn["q_norm"] = {"scale": jnp.ones((L, hd), jnp.float32)}
            attn["k_norm"] = {"scale": jnp.ones((L, hd), jnp.float32)}
        mlp = {"wi": view["wi"].transpose(0, 2, 1),
               "wg": view["wg"].transpose(0, 2, 1),
               "wo": view["mlp_wo"].transpose(0, 2, 1)}
        ones = {"scale": jnp.ones((L, d), jnp.float32)}
        layer = {"pre_norm": ones, "attn": attn, "ffn_norm": dict(ones),
                 "mlp": mlp}
        embed = init_std * jax.random.normal(keys["embed"], (V, d),
                                             jnp.float32)
        return {"embed": {"embedding": embed},
                "layers": {GROUP: layer},
                "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}

    return jax.jit(make)


def weights(cfg: dict, seed: int):
    return make_weights_fn(cfg)(W.seed_key(seed))


def serve_tick_flops(cfg: dict, start_pos, n_tokens,
                     stored_weights: int) -> float:
    s = sizes(cfg)
    (a,) = attention(cfg)
    return work.serve_tick(start_pos, n_tokens, stored_weights,
                           s["V"] * s["d"], a.layers, a.heads, a.hd)


def train_step_flops(cfg: dict, batch: int, seq_len: int,
                     stored_weights: int) -> float:
    s = sizes(cfg)
    (a,) = attention(cfg)
    return work.train_step(batch, seq_len, stored_weights, s["V"] * s["d"],
                           a.layers, a.heads, a.hd)
