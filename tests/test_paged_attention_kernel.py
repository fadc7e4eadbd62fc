"""Paged-attention kernel suite (kernels/paged_attention): parity of the
fused page-gather flash-decode Pallas kernel (interpret mode) against the
jnp gather-the-whole-pool reference, across

  * GQA group sizes (g = 1 and g = 4),
  * sliding-window configs (None and a window smaller than the context),
  * odd ``n_tokens`` mixes — decode slots (1 token), prefill chunks and
    inactive slots (0 tokens, page table all trash) in one tick,
  * trash-page rows (invalid tokens write/read page 0 harmlessly),
  * the flash-decode KV-split combine identity (1 split == N splits),
  * multi-page blocks at shapes where the kernel derives 16 pages a step:
    ragged and idle slots, lengths off the block grid, a block running
    past the table, window floors inside a block, split lanes, 256- and
    512-row prefill chunks, bf16 and int8 pools,

plus the jaxpr-level guarantee that the pallas backend of
``models/attention.paged_attention`` never materializes the gathered
``(B, P*page_size, kv, hd)`` context, and model-level backend symmetry of
``Model.paged_step``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention import paged_attention as paged_kernel
from repro.kernels.paged_attention import ref as paged_ref
from repro.models import attention
from repro.models.model_zoo import build
from repro.serve.paged_kv import init_paged_cache


def _scenario(rng, *, b=3, c=8, kv=2, g=3, hd=16, ps=4, p_log=6,
              starts=(0, 5, 13), n_tok=(8, 1, 5)):
    """Mixed tick: slot 0 a full chunk, slot 1 a decode, slot 2 a partial
    chunk (or whatever ``n_tok`` says). Pools hold garbage everywhere —
    including the trash page — so masking bugs show up as real diffs."""
    h = kv * g
    n_pages = 1 + b * p_log
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, kv, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, kv, ps, hd)), jnp.float32)
    table = jnp.asarray(
        1 + np.arange(b * p_log, dtype=np.int32).reshape(b, p_log))
    starts = np.asarray(starts, np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(c)[None], jnp.int32)
    valid = np.arange(c)[None, :] < np.asarray(n_tok)[:, None]
    return q, kp, vp, table, positions, valid


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("window", [None, 8])
def test_kernel_matches_ref_gqa_and_window(g, window):
    rng = np.random.default_rng(0)
    q, kp, vp, table, positions, valid = _scenario(rng, g=g)
    out = paged_ops.paged_flash_attention(q, kp, vp, table, positions,
                                          window=window, interpret=True)
    ref = paged_ref.paged_attention_ref(q, kp, vp, table, positions,
                                        window=window)
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4, (g, window, err)
    assert np.isfinite(np.asarray(out)).all()   # invalid rows: finite junk


def test_kernel_mixed_ticks_with_inactive_and_trash_slots():
    """Odd n_tokens mix incl. an inactive slot whose page-table row is all
    zeros (every lookup hits the trash page): valid rows still match the
    reference exactly, and nothing goes non-finite."""
    rng = np.random.default_rng(1)
    q, kp, vp, table, positions, valid = _scenario(
        rng, b=4, c=6, starts=(0, 9, 2, 0), n_tok=(6, 1, 3, 0))
    table = table.at[3].set(0)                   # inactive slot: all trash
    out = paged_ops.paged_flash_attention(q, kp, vp, table, positions,
                                          interpret=True)
    ref = paged_ref.paged_attention_ref(q, kp, vp, table, positions)
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4, err
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("window", [None, 6])
def test_kv_split_combine_identity(window):
    """Flash-decode cross-split combine: N split lanes reduce to the same
    output as the unsplit walk (to float rounding), including lanes whose
    pages are all causally skipped (empty partials drop out)."""
    rng = np.random.default_rng(2)
    q, kp, vp, table, positions, valid = _scenario(rng, starts=(0, 3, 20),
                                                   p_log=8, n_tok=(8, 1, 4))
    one = paged_ops.paged_flash_attention(q, kp, vp, table, positions,
                                          window=window, kv_splits=1,
                                          interpret=True)
    for splits in (2, 4, 8):
        many = paged_ops.paged_flash_attention(q, kp, vp, table, positions,
                                               window=window,
                                               kv_splits=splits,
                                               interpret=True)
        err = np.abs(np.asarray(one) - np.asarray(many))[valid].max()
        assert err < 1e-5, (splits, err)


# -- multi-page blocks --------------------------------------------------------
#
# At KV 4, hd 128, page 16 and f32 pools one page of every head is 64 KiB,
# so the block the kernel derives is 16 pages (256 tokens): a 40-page
# (640-token) table is three blocks, the last overhanging the table by 8
# pages. Slots are placed so that lengths fall inside, at the edge of and
# past block boundaries.

BLK_KV, BLK_G, BLK_HD, BLK_PS, BLK_P = 4, 2, 128, 16, 40
FULL = BLK_P * BLK_PS                      # 640 tokens: the whole table


def _blocked(rng, starts, n_tok, c, dtype=jnp.float32):
    b, h = len(starts), BLK_KV * BLK_G
    n_pages = 1 + b * BLK_P
    shape = (n_pages, BLK_KV, BLK_PS, BLK_HD)
    q = jnp.asarray(rng.normal(size=(b, c, h, BLK_HD)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=shape), dtype)
    vp = jnp.asarray(rng.normal(size=shape), dtype)
    table = 1 + rng.permutation(b * BLK_P).astype(np.int32).reshape(b, BLK_P)
    n_tok = np.asarray(n_tok)
    table[n_tok == 0] = 0                  # idle slot: every entry is trash
    starts = np.asarray(starts, np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(c)[None], jnp.int32)
    valid = np.arange(c)[None, :] < n_tok[:, None]
    return q, kp, vp, jnp.asarray(table), positions, valid


def test_block_is_many_pages_at_these_shapes():
    """The cases below test multi-page blocks only if the derivation gives
    them; at the long-context cell's shapes it gives 16 pages (256 tokens,
    1 MiB of K+V a step) for the decode tick and the 256-token chunk."""
    ppb = paged_kernel.pages_per_block
    assert ppb(1 * BLK_G, BLK_KV, BLK_PS, BLK_HD, 4, BLK_P) == 16
    assert ppb(128 * BLK_G, BLK_KV, BLK_PS, BLK_HD, 4, BLK_P) == 16
    assert ppb(1 * BLK_G, BLK_KV, BLK_PS, BLK_HD, 4, BLK_P, 4) == 10
    assert ppb(2, 8, 16, 128, 2, 256) == 16
    assert ppb(512, 8, 16, 128, 2, 256) == 16


@pytest.mark.parametrize("case", [
    # decode tick, ragged: an idle slot, lengths off the block grid, the
    # last position of a block, the first of the next, the full table
    dict(starts=(0, 37, 255, 256, 300, FULL - 1),
         n_tok=(0, 1, 1, 1, 1, 1), c=1),
    # a partial chunk whose rows straddle two blocks, and the last block
    # running past P (keys 640..767 do not exist)
    dict(starts=(250, 600, 0), n_tok=(12, 9, 0), c=16),
    # sliding-window floors inside a block (201 in block 0, 301 in block 1)
    dict(starts=(300, 600, 130, FULL - 1), n_tok=(1, 1, 1, 1), c=1,
         window=100),
    dict(starts=(600, 500, 0), n_tok=(1, 8, 0), c=8, window=300),
    # flash-decode lanes over multi-page blocks (16, 14 and 10 pages)
    dict(starts=(0, 255, 300, FULL - 1), n_tok=(1, 1, 1, 1), c=1,
         kv_splits=2),
    dict(starts=(0, 255, 300, FULL - 1), n_tok=(1, 1, 1, 1), c=1,
         kv_splits=3, window=200),
    dict(starts=(40, 511, 512, FULL - 1), n_tok=(1, 1, 1, 1), c=1,
         kv_splits=4),
    # a 128-token prefill chunk (256 rows at g 2) and the cell's 256-token
    # chunk (512 rows), beside a decode slot and an idle one
    dict(starts=(200, 500, FULL - 1, 0), n_tok=(128, 100, 1, 0), c=128),
    dict(starts=(0, 384, FULL - 1, 0), n_tok=(256, 256, 1, 0), c=256),
    # bf16 pools, as served
    dict(starts=(0, 37, 300, FULL - 1), n_tok=(0, 1, 1, 1), c=1,
         dtype=jnp.bfloat16),
], ids=["decode-ragged", "chunk-past-P", "window-decode", "window-chunk",
        "splits2", "splits3-window", "splits4", "prefill-256-rows",
        "prefill-512-rows", "bf16-pools"])
def test_multi_page_blocks_match_ref(case):
    case = dict(case)
    window, splits = case.pop("window", None), case.pop("kv_splits", 1)
    rng = np.random.default_rng(5)
    q, kp, vp, table, positions, valid = _blocked(rng, **case)
    out = paged_ops.paged_flash_attention(q, kp, vp, table, positions,
                                          window=window, kv_splits=splits,
                                          interpret=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_ref.paged_attention_ref(q, kp, vp, table, positions,
                                            window=window)
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4, err
    assert np.isfinite(np.asarray(out)).all()


def test_int8_pools_multi_page_blocks():
    """int8 pools: the layer dequantizes the codes before the kernel, as
    served; with multi-page blocks the pallas backend still matches the
    gather-then-softmax reference backend on the same pools."""
    cfg = _tiny_cfg(d_model=64, n_heads=BLK_KV * BLK_G, n_kv_heads=BLK_KV,
                    head_dim=BLK_HD, kv_cache_dtype="int8")
    rng = np.random.default_rng(6)
    b, c = 3, 4
    n_pages = 1 + b * BLK_P
    p = attention.init_attention(jax.random.PRNGKey(2), cfg)
    x = jnp.asarray(rng.normal(size=(b, c, cfg.d_model)) * 0.1, jnp.float32)
    shape = (n_pages, BLK_KV, BLK_PS, BLK_HD)
    cache = {name: jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
             for name in ("k", "v")}
    for name in ("k_scale", "v_scale"):
        cache[name] = jnp.asarray(
            rng.uniform(0.001, 0.02, size=shape[:3] + (1,)), jnp.float32)
    table = jnp.asarray(
        1 + rng.permutation(b * BLK_P).astype(np.int32).reshape(b, BLK_P))
    starts = np.asarray([255, 500, FULL - c], np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(c)[None], jnp.int32)
    n_tokens = jnp.asarray([4, 1, 4], np.int32)

    y_ref, _ = attention.paged_attention(
        p, x, cache, table, positions, n_tokens, cfg, backend="ref")
    y_pal, _ = attention.paged_attention(
        p, x, cache, table, positions, n_tokens, cfg, backend="pallas")
    valid = np.arange(c)[None, :] < np.asarray(n_tokens)[:, None]
    err = np.abs(np.asarray(y_ref) - np.asarray(y_pal))[valid].max()
    assert err < 1e-4, err


# -- models/attention dispatch ----------------------------------------------


def _tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", n_layers=1, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, head_dim=8,
                qk_norm=False, rope_theta=10000.0)
    base.update(kw)
    return ModelConfig(**base)


def _subjaxprs_of(p):
    if isinstance(p, jax.extend.core.ClosedJaxpr):
        yield p.jaxpr
    elif isinstance(p, jax.extend.core.Jaxpr):
        yield p
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from _subjaxprs_of(q)


def _all_avals(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            acc.append(tuple(getattr(v.aval, "shape", ())))
        for p in eqn.params.values():
            for sub in _subjaxprs_of(p):
                _all_avals(sub, acc)
    return acc


def test_pallas_path_never_materializes_gathered_context():
    """Jaxpr-level acceptance: with backend='pallas' the attention never
    builds the (B, P*page_size, kv, hd) gathered context (nor its (B, C)-
    scored full tensor); the ref backend (oracle) still does."""
    cfg = _tiny_cfg()
    b, c, ps, p_log = 2, 4, 4, 5
    p = attention.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((b, c, cfg.d_model), jnp.float32)
    cache = {"k": jnp.zeros((1 + b * p_log, 2, ps, 8), jnp.float32),
             "v": jnp.zeros((1 + b * p_log, 2, ps, 8), jnp.float32)}
    table = jnp.zeros((b, p_log), jnp.int32)
    positions = jnp.zeros((b, c), jnp.int32)
    n_tokens = jnp.zeros((b,), jnp.int32)
    gathered = (b, p_log * ps, 2, 8)

    def shapes(backend):
        jx = jax.make_jaxpr(
            lambda *a: attention.paged_attention(*a, cfg, backend=backend))(
                p, x, cache, table, positions, n_tokens)
        return _all_avals(jx.jaxpr, [])

    assert gathered in shapes("ref")          # the oracle gathers
    assert gathered not in shapes("pallas")   # the kernel never does


@pytest.mark.parametrize("window", [None, 8])
def test_attention_backends_agree(window):
    cfg = _tiny_cfg(attn_window=window)
    rng = np.random.default_rng(3)
    b, c, ps, p_log = 2, 4, 4, 5
    p = attention.init_attention(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.normal(size=(b, c, cfg.d_model)) * 0.1, jnp.float32)
    cache = {"k": jnp.asarray(rng.normal(size=(1 + b * p_log, 2, ps, 8)),
                              jnp.float32),
             "v": jnp.asarray(rng.normal(size=(1 + b * p_log, 2, ps, 8)),
                              jnp.float32)}
    table = jnp.asarray(
        1 + np.arange(b * p_log, dtype=np.int32).reshape(b, p_log))
    starts = np.asarray([7, 0], np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(c)[None], jnp.int32)
    n_tokens = jnp.asarray([4, 2], np.int32)

    y_ref, cache_ref = attention.paged_attention(
        p, x, cache, table, positions, n_tokens, cfg, backend="ref")
    y_pal, cache_pal = attention.paged_attention(
        p, x, cache, table, positions, n_tokens, cfg, backend="pallas",
        kv_splits=2)
    valid = np.arange(c)[None, :] < np.asarray(n_tokens)[:, None]
    err = np.abs(np.asarray(y_ref) - np.asarray(y_pal))[valid].max()
    assert err < 1e-4, err
    for k in ("k", "v"):   # the scatter is shared — pools must be identical
        np.testing.assert_array_equal(np.asarray(cache_ref[k]),
                                      np.asarray(cache_pal[k]))


def test_paged_step_backend_symmetry():
    """Model-level: one mixed paged_step tick produces the same last-valid-
    token logits on the pallas (interpret) and ref backends."""
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    b, c, ps = 2, 8, 4
    cfg = model.cfg
    p_log = 4
    pools = init_paged_cache(model, 1 + b * p_log, ps)
    table = jnp.asarray(
        1 + np.arange(b * p_log, dtype=np.int32).reshape(b, p_log))
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab, size=(b, c)),
        jnp.int32)
    start = jnp.asarray([0, 0], jnp.int32)
    n_tok = jnp.asarray([8, 3], jnp.int32)

    logits_ref, _ = model.paged_step(params, tokens, pools, table, start,
                                     n_tok, backend="ref")
    logits_pal, _ = model.paged_step(params, tokens, pools, table, start,
                                     n_tok, backend="pallas", kv_splits=2)
    np.testing.assert_allclose(np.asarray(logits_ref),
                               np.asarray(logits_pal), atol=2e-4, rtol=1e-4)
