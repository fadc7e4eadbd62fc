"""Composable decoder stack covering all assigned architectures.

A config's ``block_pattern`` (e.g. ("attn",) or ("rglru","rglru","attn") or
("rwkv",)) defines a *super-block*; the stack is a ``lax.scan`` over
``n_super_blocks`` stacked copies (HLO/compile-time O(1) in depth) plus an
unrolled remainder (RecurrentGemma's 38 = 12x3 + 2). Each pattern element is
a full layer: mixer (attention / RG-LRU / RWKV time-mix) + FFN (MLP / MoE /
RWKV channel-mix), pre-norm residuals.

Three entry points per model:
    apply_train(params, batch)            full-sequence forward -> logits, aux
    decode_step(params, tok, cache, pos)  one token + cache -> logits, cache
    prefill(params, prompt, cache)        whole prompt -> last logits, cache

All are pure functions built by ``make_model(cfg)``; remat policy for the
scan body is configurable (train memory).

**Compressed runtime**: every entry point accepts either a raw param tree or
a ``repro.sparse.compress.CompressedParams``. The sparse map mirrors the
params nesting and its BlockCSR leaves are stacked over ``n_super`` (padded
to a uniform slot count), so compressed weights ride through the layer-stack
``lax.scan`` next to the dense residue; attention QKV/O, MLP, MoE expert
(per-expert stacks, ``lax.map`` inside ``apply_moe``), RWKV time/channel-mix,
RG-LRU and head projections with a BCSR entry dispatch ``sparse_matmul`` —
the paper's inference-in-compressed-form, whole-model and
architecture-complete.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_ann
from repro.models import attention, moe as moe_lib, rglru, rwkv6
from repro.models.layers import (apply_embed, apply_head, apply_mlp,
                                 apply_norm, init_embed, init_mlp, init_norm,
                                 truncated_normal_init)
from repro.sparse.compress import CompressedParams

Array = jax.Array
PyTree = Any


def _split_params(params) -> tuple[PyTree, Optional[PyTree]]:
    """Accept raw params or CompressedParams everywhere.

    Returns (dense_residue, sparse_map-or-None); the sparse map mirrors the
    params nesting with BlockCSR leaves (stacked over n_super under
    "layers", so it scans alongside the dense stack).
    """
    if isinstance(params, CompressedParams):
        return params.dense, params.sparse
    return params, None


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"pre_norm": init_norm(cfg.d_model, cfg.norm)}
    if kind == "attn":
        p["attn"] = attention.init_attention(ks[0], cfg)
    elif kind == "rglru":
        p["rec"] = rglru.init_rglru(ks[0], cfg)
    elif kind == "rwkv":
        p["tm"] = rwkv6.init_time_mix(ks[0], cfg)
    else:
        raise ValueError(kind)
    p["ffn_norm"] = init_norm(cfg.d_model, cfg.norm)
    if kind == "rwkv":
        p["cm"] = rwkv6.init_channel_mix(ks[1], cfg)
    elif cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated)
    return p


def _zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}


def _mixer_scope(kind: str):
    """Device scope of a layer's mixer half (its norm, the mixer and the
    residual add): ``attention`` for attention layers — the KV write and
    the attention kernel included; recurrent mixers carry none. The FFN
    half is ``mlp``; the layer-stack scan around them (slicing each layer's
    weights and pools in, stacking its outputs and gradients out) is
    ``layers``. Scopes are HLO metadata only (``op_name``): they name the
    ops in a profiler trace and change no compiled operation."""
    if kind == "attn":
        return jax.named_scope("attention")
    return contextlib.nullcontext()


def _apply_layer_train(p: dict, x: Array, cfg: ModelConfig, kind: str,
                       positions: Array, sp: Optional[dict] = None
                       ) -> tuple[Array, dict]:
    sp = sp or {}
    with _mixer_scope(kind):
        h = apply_norm(p["pre_norm"], x, cfg.norm)
        if kind == "attn":
            mix = attention.apply_attention(p["attn"], h, cfg, positions,
                                            sparse=sp.get("attn"))
        elif kind == "rglru":
            mix, _ = rglru.apply_rglru_block(p["rec"], h, cfg, None,
                                             sparse=sp.get("rec"))
        elif kind == "rwkv":
            mix, _ = rwkv6.apply_time_mix(p["tm"], h, cfg, None,
                                          sparse=sp.get("tm"))
        x = x + mix
    with jax.named_scope("mlp"):
        h = apply_norm(p["ffn_norm"], x, cfg.norm)
        aux = _zero_aux()
        if kind == "rwkv":
            f, _ = rwkv6.apply_channel_mix(p["cm"], h, None,
                                           sparse=sp.get("cm"))
        elif cfg.moe is not None:
            f, aux = moe_lib.apply_moe(p["moe"], h, cfg,
                                       sparse=sp.get("moe"))
        else:
            f = apply_mlp(p["mlp"], h, cfg.act, cfg.mlp_gated,
                          sparse_weights=sp.get("mlp"))
        x = x + f
    return x, aux


def _apply_layer_decode(p: dict, x: Array, cfg: ModelConfig, kind: str,
                        cache: dict, pos: Array, sp: Optional[dict] = None
                        ) -> tuple[Array, dict]:
    sp = sp or {}
    new_cache = dict(cache)
    with _mixer_scope(kind):
        h = apply_norm(p["pre_norm"], x, cfg.norm)
        if kind == "attn":
            mix, new_cache["attn"] = attention.decode_attention(
                p["attn"], h, cache["attn"], pos, cfg, sparse=sp.get("attn"))
        elif kind == "rglru":
            mix, new_cache["rec"] = rglru.apply_rglru_block(
                p["rec"], h, cfg, cache["rec"], sparse=sp.get("rec"))
        elif kind == "rwkv":
            mix, new_cache["tm"] = rwkv6.apply_time_mix(
                p["tm"], h, cfg, cache["tm"], sparse=sp.get("tm"))
        x = x + mix
    with jax.named_scope("mlp"):
        h = apply_norm(p["ffn_norm"], x, cfg.norm)
        if kind == "rwkv":
            f, new_cache["cm"] = rwkv6.apply_channel_mix(
                p["cm"], h, cache["cm"], sparse=sp.get("cm"))
        elif cfg.moe is not None:
            f, _ = moe_lib.apply_moe(p["moe"], h, cfg, sparse=sp.get("moe"))
        else:
            f = apply_mlp(p["mlp"], h, cfg.act, cfg.mlp_gated,
                          sparse_weights=sp.get("mlp"))
        x = x + f
    return x, new_cache


def _apply_layer_prefill(p: dict, x: Array, cfg: ModelConfig, kind: str,
                         cache: dict, positions: Array,
                         sp: Optional[dict] = None) -> tuple[Array, dict]:
    """Full-sequence forward that also produces the post-prompt cache state.

    Recurrent kinds run their train-path full-sequence scan from a fresh
    state (the prompt starts at position 0) and keep the final state;
    attention fills the ring KV cache in one write."""
    sp = sp or {}
    new_cache = dict(cache)
    with _mixer_scope(kind):
        h = apply_norm(p["pre_norm"], x, cfg.norm)
        if kind == "attn":
            mix, new_cache["attn"] = attention.prefill_attention(
                p["attn"], h, cache["attn"], positions, cfg,
                sparse=sp.get("attn"))
        elif kind == "rglru":
            mix, new_cache["rec"] = rglru.apply_rglru_block(
                p["rec"], h, cfg, None, sparse=sp.get("rec"))
        elif kind == "rwkv":
            mix, new_cache["tm"] = rwkv6.apply_time_mix(
                p["tm"], h, cfg, None, sparse=sp.get("tm"))
        x = x + mix
    with jax.named_scope("mlp"):
        h = apply_norm(p["ffn_norm"], x, cfg.norm)
        if kind == "rwkv":
            f, new_cache["cm"] = rwkv6.apply_channel_mix(
                p["cm"], h, None, sparse=sp.get("cm"))
        elif cfg.moe is not None:
            f, _ = moe_lib.apply_moe(p["moe"], h, cfg, sparse=sp.get("moe"))
        else:
            f = apply_mlp(p["mlp"], h, cfg.act, cfg.mlp_gated,
                          sparse_weights=sp.get("mlp"))
        x = x + f
    return x, new_cache


def _apply_layer_paged(p: dict, x: Array, cfg: ModelConfig, kind: str,
                       cache: dict, page_table: Array, positions: Array,
                       n_tokens: Array, sp: Optional[dict] = None,
                       attn_backend: Optional[str] = None,
                       kv_splits: int = 1) -> tuple[Array, dict]:
    """Mixed prefill/decode layer against the slot resource pool tree (the
    continuous-batching engine path). Attention layers use block-paged KV
    pools; recurrent mixers (rglru/rwkv) use slot-indexed state pools — no
    paging, O(1) per slot — with chunked prefill handled by intra-chunk
    scans (``apply_rglru_block_paged`` / ``apply_time_mix_paged``).
    ``attn_backend``/``kv_splits`` select the paged-attention kernel
    (see ``attention.paged_attention``)."""
    if kind not in ("attn", "rglru", "rwkv"):
        raise NotImplementedError(
            f"layer kind {kind!r} has no slot resource pool — the engine "
            "covers attn/rglru/rwkv; use the sequential serving path "
            "(launch/serve without --engine)")
    sp = sp or {}
    if kind != "attn":
        # A slot whose FIRST prefill chunk lands this tick (absolute
        # position 0) starts a new request: zero its recurrent state
        # in-step, shape-stably — state left by a previous occupant of the
        # slot must not leak in. (The engine also zeroes recycled slots
        # host-side; this in-step reset is the correctness invariant.)
        fresh = (positions[:, 0] == 0) & (n_tokens > 0)
        cache = jax.tree.map(
            lambda l: jnp.where(
                fresh.reshape((-1,) + (1,) * (l.ndim - 1)),
                jnp.zeros_like(l), l),
            cache)
    new_cache = dict(cache)
    with _mixer_scope(kind):
        h = apply_norm(p["pre_norm"], x, cfg.norm)
        if kind == "attn":
            mix, new_cache["attn"] = attention.paged_attention(
                p["attn"], h, cache["attn"], page_table, positions, n_tokens,
                cfg, sparse=sp.get("attn"), backend=attn_backend,
                kv_splits=kv_splits)
        elif kind == "rglru":
            mix, new_cache["rec"] = rglru.apply_rglru_block_paged(
                p["rec"], h, cfg, cache["rec"], n_tokens,
                sparse=sp.get("rec"))
        elif kind == "rwkv":
            mix, new_cache["tm"] = rwkv6.apply_time_mix_paged(
                p["tm"], h, cfg, cache["tm"], n_tokens, sparse=sp.get("tm"))
        x = x + mix
    with jax.named_scope("mlp"):
        h = apply_norm(p["ffn_norm"], x, cfg.norm)
        if kind == "rwkv":
            f, new_cache["cm"] = rwkv6.apply_channel_mix_paged(
                p["cm"], h, cache["cm"], n_tokens, sparse=sp.get("cm"))
        elif cfg.moe is not None:
            f, _ = moe_lib.apply_moe(p["moe"], h, cfg, sparse=sp.get("moe"))
        else:
            f = apply_mlp(p["mlp"], h, cfg.act, cfg.mlp_gated,
                          sparse_weights=sp.get("mlp"))
        x = x + f
    return x, new_cache


def _init_layer_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                      dtype) -> dict:
    if kind == "attn":
        return {"attn": attention.init_kv_cache(cfg, batch, seq_len, dtype)}
    if kind == "rglru":
        return {"rec": rglru.init_rglru_state(cfg, batch, dtype)}
    if kind == "rwkv":
        st = rwkv6.init_rwkv_state(cfg, batch)
        return {"tm": st["tm"], "cm": st["cm"]}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Super-block (one pattern repeat)
# ---------------------------------------------------------------------------

def _init_super(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"b{i}_{kind}": _init_layer(ks[i], cfg, kind)
            for i, kind in enumerate(cfg.block_pattern)}


def _super_train(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                 sp: Optional[dict] = None):
    sp = sp or {}
    aux = _zero_aux()
    for i, kind in enumerate(cfg.block_pattern):
        key = f"b{i}_{kind}"
        x, a = _apply_layer_train(p[key], x, cfg, kind, positions,
                                  sp.get(key))
        aux = jax.tree.map(jnp.add, aux, a)
    # sequence-parallel residual carry: the inter-layer (bwd-residual) x is
    # seq-sharded over 'model' so the layer-stack residual shrinks by the
    # TP degree (no-op when seq doesn't divide / no mesh). RWKV blocks are
    # exempt: token-shift ddlerp + chunked WKV consume full sequences five
    # ways per block, and the re-gathers cost more than the carry saves
    # (measured 3x memory-term regression; EXPERIMENTS.md §Perf).
    if "rwkv" not in cfg.block_pattern:
        x = shard_ann(x, ("batch", "res_seq", "embed"))
    return x, aux


def _super_decode(p: dict, x: Array, cfg: ModelConfig, cache: dict, pos,
                  sp: Optional[dict] = None):
    sp = sp or {}
    new_cache = {}
    for i, kind in enumerate(cfg.block_pattern):
        key = f"b{i}_{kind}"
        x, new_cache[key] = _apply_layer_decode(p[key], x, cfg, kind,
                                                cache[key], pos, sp.get(key))
    return x, new_cache


def _super_prefill(p: dict, x: Array, cfg: ModelConfig, cache: dict,
                   positions: Array, sp: Optional[dict] = None):
    sp = sp or {}
    new_cache = {}
    for i, kind in enumerate(cfg.block_pattern):
        key = f"b{i}_{kind}"
        x, new_cache[key] = _apply_layer_prefill(p[key], x, cfg, kind,
                                                 cache[key], positions,
                                                 sp.get(key))
    return x, new_cache


def _super_paged(p: dict, x: Array, cfg: ModelConfig, cache: dict,
                 page_table: Array, positions: Array, n_tokens: Array,
                 sp: Optional[dict] = None,
                 attn_backend: Optional[str] = None, kv_splits: int = 1):
    sp = sp or {}
    new_cache = {}
    for i, kind in enumerate(cfg.block_pattern):
        key = f"b{i}_{kind}"
        x, new_cache[key] = _apply_layer_paged(p[key], x, cfg, kind,
                                               cache[key], page_table,
                                               positions, n_tokens,
                                               sp.get(key),
                                               attn_backend=attn_backend,
                                               kv_splits=kv_splits)
    return x, new_cache


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    """Every apply fn accepts raw params OR ``CompressedParams`` — the
    compressed-model runtime: BCSR weights take the sparse_matmul path in
    attention/MLP/head, everything else reads the dense residue."""
    cfg: ModelConfig
    init: Callable
    apply_train: Callable       # (params, batch) -> (logits, aux)
    apply_hidden: Callable      # (params, batch) -> (hidden, aux)  [no head]
    head: Callable              # (params, hidden) -> logits
    decode_step: Callable       # (params, x, cache, pos) -> (logits, cache)
    prefill: Callable           # (params, prompt, cache) -> (logits, cache)
    init_cache: Callable        # (batch, seq_len, dtype) -> cache
    # (params, tokens, pools, page_table, start_pos, n_tokens)
    #   -> (last-valid-token logits, pools) — the continuous-batching
    # engine's mixed step (serve/engine.py) over the slot resource pool
    # tree: block-paged KV for attention layers, slot-indexed state pools
    # for recurrent mixers. None only for layer kinds outside
    # attn/rglru/rwkv coverage.
    paged_step: Optional[Callable] = None


def make_model(cfg: ModelConfig, remat: bool = True,
               remat_policy: str = "nothing") -> Model:
    """remat_policy: 'nothing' (save only the per-layer carry — minimal
    memory, bwd recomputes the layer; §Perf iteration C2) or 'dots' (save
    projection outputs — less recompute, ~6x the residual memory)."""
    cdt = _dtype(cfg.compute_dtype)
    n_super = cfg.n_super_blocks
    rem = cfg.remainder_pattern
    policy = (jax.checkpoint_policies.nothing_saveable
              if remat_policy == "nothing"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def init(key) -> PyTree:
        k_emb, k_layers, k_rem, k_head = jax.random.split(key, 4)
        params: dict = {"embed": init_embed(k_emb, cfg.vocab, cfg.d_model)}
        sub_keys = jax.random.split(k_layers, n_super)
        params["layers"] = jax.vmap(
            lambda kk: _init_super(kk, cfg))(sub_keys)
        if rem:
            rks = jax.random.split(k_rem, len(rem))
            params["rem"] = {f"r{i}_{kind}": _init_layer(rks[i], cfg, kind)
                             for i, kind in enumerate(rem)}
        params["final_norm"] = init_norm(cfg.d_model, cfg.norm)
        if not cfg.tie_embeddings:
            params["head"] = truncated_normal_init(
                k_head, (cfg.d_model, cfg.vocab), 1.0)
        return params

    def embed_inputs(params, inputs):
        """Token ids (B, S) int32, or precomputed embeddings (B, S, d) for
        stub frontends (vlm/audio per the assignment)."""
        if inputs.ndim == 3:                     # frontend stub: embeddings
            return inputs.astype(cdt)
        with jax.named_scope("embed"):
            return apply_embed(params["embed"], inputs, cdt)

    def head(params, x):
        dense, sparse = _split_params(params)
        hp = {"embedding": dense["embed"]["embedding"]} if cfg.tie_embeddings \
            else {"head": dense["head"]}
        sw = {"head": sparse["head"]} if sparse and "head" in sparse else None
        with jax.named_scope("head"):
            x = apply_norm(dense["final_norm"], x, cfg.norm)
            return apply_head(hp, x, cfg.tie_embeddings, cfg.logit_softcap,
                              sparse_weights=sw)

    def apply_hidden(params, batch) -> tuple[Array, dict]:
        dense, sparse = _split_params(params)
        sp_layers = (sparse or {}).get("layers", {})
        sp_rem = (sparse or {}).get("rem", {})
        inputs = batch["inputs"]
        x = embed_inputs(dense, inputs)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def body(carry, xs):
            layer_p, layer_sp = xs
            x, aux = carry
            x2, a = _super_train(layer_p, x, cfg, positions, layer_sp)
            return (x2, jax.tree.map(jnp.add, aux, a)), None

        body_fn = body
        if remat:
            body_fn = jax.checkpoint(body, policy=policy)
        with jax.named_scope("layers"):
            (x, aux), _ = jax.lax.scan(body_fn, (x, _zero_aux()),
                                       (dense["layers"], sp_layers))
        for i, kind in enumerate(rem):
            x, a = _apply_layer_train(dense["rem"][f"r{i}_{kind}"], x, cfg,
                                      kind, positions,
                                      sp_rem.get(f"r{i}_{kind}"))
            aux = jax.tree.map(jnp.add, aux, a)
        return x, aux

    def apply_train(params, batch) -> tuple[Array, dict]:
        x, aux = apply_hidden(params, batch)
        return head(params, x), aux

    def init_cache(batch: int, seq_len: int, dtype=None) -> PyTree:
        dtype = dtype or cdt
        def one_super():
            return {f"b{i}_{kind}": _init_layer_cache(cfg, kind, batch,
                                                      seq_len, dtype)
                    for i, kind in enumerate(cfg.block_pattern)}
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_super,) + x.shape).copy(),
            one_super())
        cache = {"layers": stacked}
        if rem:
            cache["rem"] = {f"r{i}_{kind}": _init_layer_cache(
                cfg, kind, batch, seq_len, dtype)
                for i, kind in enumerate(rem)}
        return cache

    def decode_step(params, inputs, cache, pos) -> tuple[Array, PyTree]:
        """inputs: (B, 1) ids or (B, 1, d) embeddings; pos: scalar int32."""
        dense, sparse = _split_params(params)
        sp_layers = (sparse or {}).get("layers", {})
        sp_rem = (sparse or {}).get("rem", {})
        x = embed_inputs(dense, inputs)

        def body(x, xs):
            layer_p, layer_c, layer_sp = xs
            x2, c2 = _super_decode(layer_p, x, cfg, layer_c, pos, layer_sp)
            return x2, c2

        with jax.named_scope("layers"):
            x, new_layer_cache = jax.lax.scan(
                body, x, (dense["layers"], cache["layers"], sp_layers))
        new_cache = {"layers": new_layer_cache}
        if rem:
            new_cache["rem"] = {}
            for i, kind in enumerate(rem):
                key = f"r{i}_{kind}"
                x, new_cache["rem"][key] = _apply_layer_decode(
                    dense["rem"][key], x, cfg, kind, cache["rem"][key], pos,
                    sp_rem.get(key))
        return head(params, x), new_cache

    def prefill(params, inputs, cache) -> tuple[Array, PyTree]:
        """Consume the whole prompt in one forward, filling the cache.

        inputs: (B, S) ids or (B, S, d) embeddings. Returns (last-position
        logits (B, vocab), cache ready for decode at pos = S) — one jit
        dispatch instead of S stepwise decodes."""
        dense, sparse = _split_params(params)
        sp_layers = (sparse or {}).get("layers", {})
        sp_rem = (sparse or {}).get("rem", {})
        x = embed_inputs(dense, inputs)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def body(x, xs):
            layer_p, layer_c, layer_sp = xs
            x2, c2 = _super_prefill(layer_p, x, cfg, layer_c, positions,
                                    layer_sp)
            return x2, c2

        with jax.named_scope("layers"):
            x, new_layer_cache = jax.lax.scan(
                body, x, (dense["layers"], cache["layers"], sp_layers))
        new_cache = {"layers": new_layer_cache}
        if rem:
            new_cache["rem"] = {}
            for i, kind in enumerate(rem):
                key = f"r{i}_{kind}"
                x, new_cache["rem"][key] = _apply_layer_prefill(
                    dense["rem"][key], x, cfg, kind, cache["rem"][key],
                    positions, sp_rem.get(key))
        return head(params, x[:, -1:])[:, 0], new_cache

    def paged_step(params, tokens, pools, page_table, start_pos, n_tokens,
                   backend: Optional[str] = None, kv_splits: int = 1
                   ) -> tuple[Array, PyTree]:
        """Continuous-batching mixed step over a fixed-capacity slot batch.

        tokens: (B, C) ids — up to C new tokens per slot (decode slots carry
        1, prefill slots a chunk, inactive slots 0 — see ``n_tokens``);
        pools: paged KV tree from ``serve.paged_kv.init_paged_cache``;
        page_table: (B, P) int32; start_pos/n_tokens: (B,) int32. Returns
        (logits at each slot's LAST valid token (B, vocab), new pools) —
        one jit dispatch serves any prefill/decode mix per engine tick.
        ``backend``/``kv_splits`` (static) pick the paged-attention kernel:
        'pallas' = fused page-gather flash-decode, 'ref' = jnp oracle,
        None/'auto' = pallas on TPU.
        """
        dense, sparse = _split_params(params)
        sp_layers = (sparse or {}).get("layers", {})
        sp_rem = (sparse or {}).get("rem", {})
        x = embed_inputs(dense, tokens)
        b, c = x.shape[0], x.shape[1]
        positions = start_pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]

        def body(x, xs):
            layer_p, layer_c, layer_sp = xs
            x2, c2 = _super_paged(layer_p, x, cfg, layer_c, page_table,
                                  positions, n_tokens, layer_sp,
                                  attn_backend=backend, kv_splits=kv_splits)
            return x2, c2

        with jax.named_scope("layers"):
            x, new_layer_pools = jax.lax.scan(
                body, x, (dense["layers"], pools["layers"], sp_layers))
        new_pools = {"layers": new_layer_pools}
        if rem:
            new_pools["rem"] = {}
            for i, kind in enumerate(rem):
                key = f"r{i}_{kind}"
                x, new_pools["rem"][key] = _apply_layer_paged(
                    dense["rem"][key], x, cfg, kind, pools["rem"][key],
                    page_table, positions, n_tokens, sp_rem.get(key),
                    attn_backend=backend, kv_splits=kv_splits)
        last = jnp.clip(n_tokens - 1, 0, c - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)   # (B, 1, d)
        return head(params, xl)[:, 0], new_pools

    paged_ok = all(k in ("attn", "rglru", "rwkv")
                   for k in cfg.block_pattern + rem)
    return Model(cfg=cfg, init=init, apply_train=apply_train,
                 apply_hidden=apply_hidden, head=head,
                 decode_step=decode_step, prefill=prefill,
                 init_cache=init_cache,
                 paged_step=paged_step if paged_ok else None)
