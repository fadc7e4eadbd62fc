"""GQA attention with chunked (flash-style) streaming softmax, QK-norm,
sliding windows, RoPE, and a ring-buffer KV cache for decode.

The training/prefill path never materializes the (S, S) score matrix: it
streams over KV chunks with a running (max, sum, acc) — the pure-JAX flash
formulation. On TPU the same structure is what a Pallas flash kernel would
compute; keeping it in jnp lets XLA partition it with GSPMD and keeps the
dry-run honest about memory (see EXPERIMENTS.md §Perf for the block-skip
iteration).

Decode attention is a single-token product against the cache; for long
contexts the cache's sequence axis is sharded over the 'model' mesh axis
(sequence-parallel decode — softmax reductions become cross-chip collectives).

``prefill_attention`` consumes the whole prompt in one forward and fills the
ring KV cache in a single scatter — one jit dispatch replaces S stepwise
decodes. All three paths take an optional ``sparse`` dict of BlockCSR
projections ({"wq"|"wk"|"wv"|"wo": BlockCSR} in (out, in) layout), built by
``repro.sparse.compress.compress_params`` — the compressed serving runtime.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import run_replicated, shard_ann
from repro.kernels.paged_attention import ops as paged_kops
from repro.models.layers import apply_norm, apply_rope, init_norm, truncated_normal_init
from repro.sparse import ops as sparse_ops

Array = jax.Array
NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal_init(ks[0], (d, h, hd), 1.0),
        "wk": truncated_normal_init(ks[1], (d, kv, hd), 1.0),
        "wv": truncated_normal_init(ks[2], (d, kv, hd), 1.0),
        "wo": truncated_normal_init(ks[3], (h, hd, d), 1.0),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(hd, "rmsnorm")
        p["k_norm"] = init_norm(hd, "rmsnorm")
    return p


def _project_qkv(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                 sparse: Optional[dict] = None):
    """QKV projections; entries of ``sparse`` ({"wq": BlockCSR, ...}, stored
    (heads*hd, d)) take the compressed-kernel path instead of the einsum."""
    dt = x.dtype
    b, s = x.shape[0], x.shape[1]
    hd = cfg.resolved_head_dim

    def proj(name, n_out_heads):
        if sparse and name in sparse:
            y = sparse_ops.sparse_matmul(x.reshape(-1, x.shape[-1]),
                                         sparse[name])
            return y.reshape(b, s, n_out_heads, hd).astype(dt)
        return jnp.einsum("bsd,dhk->bshk", x, p[name].astype(dt))

    q = proj("wq", cfg.n_heads)
    k = proj("wk", cfg.n_kv_heads)
    v = proj("wv", cfg.n_kv_heads)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # 'seq_fb' is a FALLBACK axis: it claims 'model' only when the head
    # count doesn't divide the mesh axis (e.g. smollm's 15 heads), turning
    # 16x-replicated attention into sequence-sharded attention (§Perf A1)
    q = shard_ann(q, ("batch", "seq_fb", "heads", "head_dim"))
    k = shard_ann(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard_ann(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def chunked_attention(q: Array, k: Array, v: Array, *,
                      causal: bool = True,
                      window: Optional[int] = None,
                      q_chunk: int = 1024,
                      kv_chunk: int = 1024,
                      q_offset: int = 0,
                      seq_shard_fallback: bool = False) -> Array:
    """Streaming-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H a multiple of KV (GQA).
    Returns (B, Sq, H, hd). Never materializes (Sq, Skv).
    """
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    assert sq % q_chunk == 0 and skv % kv_chunk == 0

    qg = q.reshape(b, nq, q_chunk, kv, g, hd)
    kg = k.reshape(b, nkv, kv_chunk, kv, hd)
    vg = v.reshape(b, nkv, kv_chunk, kv, hd)
    # q-dim sharding of the streaming softmax (scores/probs/acc get the
    # same q-sharding by propagation): the zero-communication layout when
    # heads can't shard — each device owns a q-token slice vs all KV.
    # ONLY applied on the fallback path: for heads-shardable archs this
    # constraint conflicts with the (kv x g) head tiling GSPMD derives from
    # the projections and forces a per-layer reshard storm (measured on
    # qwen3/command-r; EXPERIMENTS.md §Perf A-iterations).
    if seq_shard_fallback:
        qg = shard_ann(qg, ("batch", None, "seq_fb", "kv_heads", None,
                            "head_dim"))

    q_pos = (jnp.arange(sq) + q_offset).reshape(nq, q_chunk)
    k_pos = jnp.arange(skv).reshape(nkv, kv_chunk)

    def one_q_chunk(args):
        qc, qp = args                      # (b, q_chunk, kv, g, hd), (q_chunk,)

        def kv_step_inner(carry, xs):
            m, l, acc = carry
            kc, vc, kp = xs                # (b, kv_chunk, kv, hd), ..., (kv_chunk,)
            s = jnp.einsum("bqkgh,bckh->bkgqc", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= (qp[:, None] - kp[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = corr * l + jnp.sum(p, axis=-1)
            acc2 = corr[..., None] * acc + jnp.einsum(
                "bkgqc,bckh->bkgqh", p, vc.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m2, l2, acc2), None

        # flash-attention memory semantics: remat the kv-chunk body so the
        # (q_chunk, kv_chunk) score/prob tiles are NOT stacked as scan
        # residuals for backward — they are recomputed per chunk. Without
        # this, backward materializes the full (S, S) probabilities
        # (measured: ~9 GB/device residuals at 4k train; see EXPERIMENTS.md).
        kv_step = jax.checkpoint(
            kv_step_inner, policy=jax.checkpoint_policies.nothing_saveable)
        m0 = jnp.full((b, kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kg.transpose(1, 0, 2, 3, 4), vg.transpose(1, 0, 2, 3, 4), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (b, q_chunk, kv, g, hd)

    outs = jax.lax.map(one_q_chunk, (qg.transpose(1, 0, 2, 3, 4, 5), q_pos))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def _heads_shardable(cfg: ModelConfig) -> bool:
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return True
    return cfg.n_heads % mesh.shape["model"] == 0


def _out_proj(p: dict, out: Array, dt, sparse: Optional[dict]) -> Array:
    """Output projection; sparse["wo"] is stored (d, heads*hd) BCSR."""
    if sparse and "wo" in sparse:
        b, s = out.shape[0], out.shape[1]
        y = sparse_ops.sparse_matmul(out.reshape(b * s, -1), sparse["wo"])
        return y.reshape(b, s, -1).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def apply_attention(p: dict, x: Array, cfg: ModelConfig,
                    positions: Array, sparse: Optional[dict] = None) -> Array:
    """Training / prefill self-attention over a full sequence."""
    # under the seq-parallel residual stream, attention is the only block
    # needing cross-token data: materialize full-seq ONCE here (one gather
    # per layer instead of GSPMD re-gathering at every projection). When
    # the head count cannot shard (seq_fb path), projections stay
    # seq-sharded and only K/V (a few heads) are gathered — skip the pin.
    shardable = _heads_shardable(cfg)
    if shardable:
        x = shard_ann(x, ("batch", "seq", "embed"))
    q, k, v = _project_qkv(p, x, cfg, positions, sparse)
    out = chunked_attention(q, k, v, causal=True, window=cfg.attn_window,
                            seq_shard_fallback=not shardable)
    y = _out_proj(p, out, x.dtype, sparse)
    return shard_ann(y, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> dict:
    """Ring-buffer cache: window-bounded for local attention.

    kv_cache_dtype='int8': k/v stored int8 with one f32 scale per
    (batch, slot, head) — halves cache HBM vs bf16 (the lever that brings
    the 104B 32k-decode cell under 16 GB/device on the single pod)."""
    size = seq_len if cfg.attn_window is None else min(cfg.attn_window, seq_len)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, size, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, size, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, size, kv, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, size, kv, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, size, kv, hd), dtype),
        "v": jnp.zeros((batch, size, kv, hd), dtype),
    }


def _quantize_heads(x: Array):
    """Per-(batch, pos, head) symmetric int8 quantization."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def decode_attention(p: dict, x: Array, cache: dict, pos: Array,
                     cfg: ModelConfig,
                     sparse: Optional[dict] = None) -> tuple[Array, dict]:
    """x: (B, 1, d); pos: scalar int32 position of the new token."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, sparse)

    size = cache["k"].shape[1]
    slot = pos % size
    new_cache = {}
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_heads(k_new)
        vq, vs = _quantize_heads(v_new)
        upd = jax.lax.dynamic_update_slice
        new_cache["k"] = upd(cache["k"], kq, (0, slot, 0, 0))
        new_cache["v"] = upd(cache["v"], vq, (0, slot, 0, 0))
        new_cache["k_scale"] = upd(cache["k_scale"], ks, (0, slot, 0, 0))
        new_cache["v_scale"] = upd(cache["v_scale"], vs, (0, slot, 0, 0))
        k = (new_cache["k"].astype(jnp.float32) * new_cache["k_scale"]
             ).astype(x.dtype)
        v = (new_cache["v"].astype(jnp.float32) * new_cache["v_scale"]
             ).astype(x.dtype)
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
        k, v = new_cache["k"], new_cache["v"]
    k = shard_ann(k, ("batch", "cache_seq", "kv_heads", "head_dim"))
    v = shard_ann(v, ("batch", "cache_seq", "kv_heads", "head_dim"))

    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    s = jnp.einsum("bkgh,bckh->bkgc", qg, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5

    # validity of each ring slot at time `pos`
    idx = jnp.arange(size)
    written = jnp.where(pos + 1 >= size, size, pos + 1)
    valid = idx < written
    if cfg.attn_window is not None:
        # ring semantics: every surviving slot is within the window by
        # construction once the ring has wrapped
        age = (slot - idx) % size
        valid &= age < cfg.attn_window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)

    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckh->bkgh", pattn, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h, hd).astype(x.dtype)
    y = _out_proj(p, out, x.dtype, sparse)
    return shard_ann(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# Prefill (full prompt in one forward, cache populated in one write)
# ---------------------------------------------------------------------------

def _write_prefill_cache(cache: dict, k: Array, v: Array,
                         cfg: ModelConfig) -> dict:
    """Scatter the prompt's K/V into the ring cache in one shot.

    Slot for position p is ``p % size`` (decode_attention's ring rule). When
    the prompt is longer than the ring, only the last ``size`` positions
    survive — exactly what stepwise decode would have left behind.
    """
    size = cache["k"].shape[1]
    s = k.shape[1]
    n_keep = min(s, size)
    slots = (jnp.arange(n_keep) + s - n_keep) % size
    kk, vv = k[:, s - n_keep:], v[:, s - n_keep:]
    new = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_heads(kk)
        vq, vs = _quantize_heads(vv)
        new["k"] = cache["k"].at[:, slots].set(kq)
        new["v"] = cache["v"].at[:, slots].set(vq)
        new["k_scale"] = cache["k_scale"].at[:, slots].set(ks)
        new["v_scale"] = cache["v_scale"].at[:, slots].set(vs)
    else:
        new["k"] = cache["k"].at[:, slots].set(kk.astype(cache["k"].dtype))
        new["v"] = cache["v"].at[:, slots].set(vv.astype(cache["v"].dtype))
    return new


def prefill_attention(p: dict, x: Array, cache: dict, positions: Array,
                      cfg: ModelConfig,
                      sparse: Optional[dict] = None) -> tuple[Array, dict]:
    """Full-sequence attention over the prompt that also fills the KV cache.

    One chunked-attention forward replaces S single-token decode dispatches;
    returns (y, new_cache) with the cache ready for decode at pos = S.
    """
    shardable = _heads_shardable(cfg)
    if shardable:
        x = shard_ann(x, ("batch", "seq", "embed"))
    q, k, v = _project_qkv(p, x, cfg, positions, sparse)
    out = chunked_attention(q, k, v, causal=True, window=cfg.attn_window,
                            seq_shard_fallback=not shardable)
    y = _out_proj(p, out, x.dtype, sparse)
    new_cache = _write_prefill_cache(cache, k, v, cfg)
    return shard_ann(y, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# Paged attention (continuous-batching engine — serve/paged_kv.py)
# ---------------------------------------------------------------------------

def init_paged_kv(cfg: ModelConfig, n_pages: int, page_size: int,
                  dtype) -> dict:
    """Per-layer block-paged KV pool: K/V stored as head-major (n_pages, kv,
    page_size, hd) pages shared by every request slot — one (page_size, hd)
    slab per (page, head) is the tile the Pallas kernel DMAs (Mosaic's
    tiling rule), whatever the head count. Page 0 is the engine's trash
    page — never allocated to a request, so masked-out token writes can
    land there harmlessly. Slot-to-page ownership lives in the engine's
    page table, not here.

    kv_cache_dtype='int8': pages store int8 K/V plus one f32 scale per
    (page, head, offset) — the same static symmetric scheme as the ring
    cache (``_quantize_heads``), halving page-pool HBM."""
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((n_pages, kv, page_size, hd), jnp.int8),
            "v": jnp.zeros((n_pages, kv, page_size, hd), jnp.int8),
            "k_scale": jnp.zeros((n_pages, kv, page_size, 1), jnp.float32),
            "v_scale": jnp.zeros((n_pages, kv, page_size, 1), jnp.float32),
        }
    return {"k": jnp.zeros((n_pages, kv, page_size, hd), dtype),
            "v": jnp.zeros((n_pages, kv, page_size, hd), dtype)}


def paged_attention(p: dict, x: Array, cache: dict, page_table: Array,
                    positions: Array, n_tokens: Array, cfg: ModelConfig,
                    sparse: Optional[dict] = None,
                    backend: Optional[str] = None,
                    kv_splits: int = 1) -> tuple[Array, dict]:
    """Mixed prefill/decode attention against a block-paged KV pool.

    x: (B, C, d) — B engine slots, up to C new tokens each; slot i carries
    ``n_tokens[i]`` valid tokens at absolute positions ``positions[i, :]``
    (decode slots have 1 valid token, prefill slots a chunk, inactive slots
    0). cache: {"k", "v"} (n_pages, kv, page_size, hd) pools; page_table:
    (B, P) physical page of each slot's logical page p (covering positions
    [p*page_size, (p+1)*page_size)), 0 for unallocated entries.

    The new K/V are scattered into each slot's pages first, then every
    query attends over its slot's pages under a causal-by-absolute-position
    mask — so one dispatch serves any mix of prefill chunks and
    single-token decodes (the engine's mixed step). Invalid queries read
    finite garbage that is discarded downstream; causality guarantees they
    never contaminate a valid position.

    ``backend`` dispatches the attention product (same semantics as
    ``sparse.ops.resolve_backend``): 'pallas' runs the fused page-gather
    flash-decode kernel (``kernels/paged_attention``) — the gathered
    ``(B, P*page_size, ...)`` context is never materialized, and
    ``kv_splits`` cuts the page walk into that many flash-decode lanes;
    'ref' keeps the gather-then-softmax jnp path below as the parity
    oracle; None/'auto' picks pallas on TPU, ref elsewhere.
    """
    b, c = x.shape[0], x.shape[1]
    ps = cache["k"].shape[2]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, sparse)

    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < n_tokens[:, None]
    logical = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, logical, axis=1)     # (B, C)
    phys = jnp.where(valid, phys, 0)                            # trash page
    offs = positions % ps
    # (page, :, offset) scatter: advanced indices split by the head slice
    # put the B*C token axis first, matching the (B*C, kv, ...) updates
    at = (phys.reshape(-1), slice(None), offs.reshape(-1))
    new_cache = {}
    if cfg.kv_cache_dtype == "int8":
        # Same static symmetric scheme as the ring cache: quantize the new
        # chunk per (slot, position, head), scatter codes + scales. The
        # attention product runs on a pool dequantized once per dispatch
        # (fusing the dequant into the page-gather kernel is future work).
        for name, new in (("k", k_new), ("v", v_new)):
            qn, sc = _quantize_heads(new)
            new_cache[name] = cache[name].at[at].set(
                qn.reshape(b * c, *qn.shape[2:]))
            new_cache[name + "_scale"] = cache[name + "_scale"].at[at].set(
                sc.reshape(b * c, *sc.shape[2:]))
        k_pool = (new_cache["k"].astype(jnp.float32)
                  * new_cache["k_scale"]).astype(x.dtype)
        v_pool = (new_cache["v"].astype(jnp.float32)
                  * new_cache["v_scale"]).astype(x.dtype)
    else:
        for name, new in (("k", k_new), ("v", v_new)):
            pool = cache[name]
            flat = new.reshape(b * c, *new.shape[2:]).astype(pool.dtype)
            new_cache[name] = pool.at[at].set(flat)
        k_pool, v_pool = new_cache["k"], new_cache["v"]

    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kv
    if sparse_ops.resolve_backend(backend or "auto") == "pallas":
        out = run_replicated(functools.partial(
            paged_kops.paged_flash_attention, window=cfg.attn_window,
            kv_splits=kv_splits), q, k_pool, v_pool, page_table, positions)
        out = out.astype(x.dtype)
        y = _out_proj(p, out, x.dtype, sparse)
        return shard_ann(y, ("batch", "seq", "embed")), new_cache

    P = page_table.shape[1]
    k_ctx = paged_kops.gather_pages(k_pool, page_table)
    v_ctx = paged_kops.gather_pages(v_pool, page_table)
    k_ctx = shard_ann(k_ctx, ("batch", "cache_seq", "kv_heads", "head_dim"))
    v_ctx = shard_ann(v_ctx, ("batch", "cache_seq", "kv_heads", "head_dim"))

    qg = q.reshape(b, c, kv, g, hd)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg, k_ctx,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    k_pos = jnp.arange(P * ps, dtype=jnp.int32)
    mask = k_pos[None, None, :] <= positions[:, :, None]        # (B, C, K)
    if cfg.attn_window is not None:
        mask &= (positions[:, :, None] - k_pos[None, None, :]) < cfg.attn_window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)

    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckh->bkgqh", pattn, v_ctx.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd).astype(x.dtype)
    y = _out_proj(p, out, x.dtype, sparse)
    return shard_ann(y, ("batch", "seq", "embed")), new_cache
