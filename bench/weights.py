"""Seeded block-sparse weights for a configuration, made on the device.

The benchmark makes its own weights, so the plain reference can take them
without taking anything the program made. One jitted call draws every leaf
from the seed in the program's dense parameter layout (layers stacked on a
leading axis), with 90 % of each projection's (128, 128) blocks exactly
zero. The program's own compression then finds those zero blocks; nothing
is pruned by magnitude.

Kept blocks are drawn N(0, 1 / (fan_in * density)): each projection keeps
unit gain at any sparsity, so attention and the MLP both reach the residual
stream and a fault in either moves the served tokens. The tied embedding is
drawn at the published ``initializer_range``: small against what the layers
add, so the last token's own embedding does not dominate the logits (with
unit-scale embeddings a random tied model only repeats its input, and no
served token would ever be near a tie).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def sizes(cfg: dict) -> dict:
    m = cfg["model"]
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "d": d, "H": h,
            "KV": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // h,
            "ff": m["intermediate_size"], "V": m["vocab_size"]}


def projections(cfg: dict) -> dict:
    """Every compressed projection: name -> (group, leaf, out, in), with
    (out, in) the orientation of the program's BCSR view."""
    s = sizes(cfg)
    d, ff, hd = s["d"], s["ff"], s["hd"]
    return {"wq": ("attn", "wq", s["H"] * hd, d),
            "wk": ("attn", "wk", s["KV"] * hd, d),
            "wv": ("attn", "wv", s["KV"] * hd, d),
            "wo": ("attn", "wo", d, s["H"] * hd),
            "wi": ("mlp", "wi", ff, d),
            "wg": ("mlp", "wg", ff, d),
            "mlp_wo": ("mlp", "wo", d, ff)}


def kept_blocks(out: int, inp: int, block, sparsity: float) -> tuple:
    """(grid rows, grid cols, kept) of one (out, in) matrix: the same count
    the program's magnitude pruning keeps at this sparsity."""
    r, c = -(-out // block[0]), -(-inp // block[1])
    return r, c, r * c - int(round(sparsity * r * c))


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (64-bit seeds too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _block_sparse(key, L, out, inp, block, sparsity):
    """(L, out, in) f32 with exactly ``kept`` nonzero blocks per layer."""
    r, c, kept = kept_blocks(out, inp, block, sparsity)
    k_pick, k_val = jax.random.split(key)
    scores = jax.random.uniform(k_pick, (L, r * c))
    rank = jnp.argsort(jnp.argsort(scores, axis=-1), axis=-1)
    mask = (rank < kept).reshape(L, r, 1, c, 1)
    mask = jnp.broadcast_to(mask, (L, r, block[0], c, block[1]))
    mask = mask.reshape(L, r * block[0], c * block[1])[:, :out, :inp]
    std = (inp * kept / (r * c)) ** -0.5
    vals = std * jax.random.normal(k_val, (L, out, inp), jnp.float32)
    return jnp.where(mask, vals, 0.0)


def make_weights_fn(cfg: dict):
    """jit(key) -> dense params in the program's layout, block-sparse."""
    s = sizes(cfg)
    L, d, H, KV, hd, ff, V = (s[k] for k in ("L", "d", "H", "KV", "hd",
                                             "ff", "V"))
    comp = cfg["compression"]
    block, sp = tuple(comp["block"]), float(comp["sparsity"])
    projs = projections(cfg)
    init_std = float(cfg["model"]["initializer_range"])

    def make(key):
        keys = dict(zip(["embed"] + sorted(projs),
                        jax.random.split(key, 1 + len(projs))))
        view = {n: _block_sparse(keys[n], L, o, i, block, sp)
                for n, (_, _, o, i) in projs.items()}
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
                "layers": {"b0_attn": layer},
                "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}

    return jax.jit(make)


def stored_blocks(cfg: dict) -> dict:
    """Stored (nonzero) blocks per layer of each projection."""
    comp = cfg["compression"]
    out = {}
    for name, (_, _, o, i) in projections(cfg).items():
        out[name] = kept_blocks(o, i, tuple(comp["block"]),
                                float(comp["sparsity"]))[2]
    return out

