"""SpC-Retrain: training directly into BlockCSR.

Covers the compressed-training tentpole:
  * the plan-aligned group-l1 prox shrinks exactly the (out, in) blocks
    ``compress_params`` tiles, across stored layouts (2D / attn 3D / stacked),
  * ``sparse_matmul``'s custom VJP: dw equals the densified autodiff oracle
    at resident slots across block sizes, odd (non-multiple) shapes and
    stacked layers — and the jaxpr contains NO dense (out, in) intermediate,
  * backend dispatch symmetry: 'pallas' and 'ref' agree on forward and both
    gradients (sparse_matmul / sparse_matmul_t share the 'auto' resolution),
  * mask-frozen debias retraining from a ``CompressedParams``: only
    BlockCSR.data moves, and debiased compressed logits match the densified
    (mask-frozen) reference to 1e-4,
  * zero-slot regression: all-zero / fully-pruned layers compress to valid
    empty BCSRs that serve, checkpoint, stack and backprop (zero grads).
"""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import masks as masks_lib
from repro.core.optimizers import prox_adam
from repro.core.prox import prox_group_l1_blocks
from repro.models.model_zoo import build
from repro.sparse import ops as sparse_ops
from repro.sparse.compress import (CompressedParams, CompressionPlan,
                                   _as_out_in, compress_params,
                                   densify_compressed, iter_bcsr,
                                   make_plan_prox, prune_blocks_for_plan,
                                   split_trainable)
from repro.sparse.formats import bcsr_to_dense, dense_to_bcsr, pad_bcsr
from repro.train.state import TrainState
from repro.train.step import make_train_step

PLAN = CompressionPlan(block=(8, 64), min_sparsity=0.3, min_size=4096)


def _block_sparse(rng, n, k, block, density):
    br, bc = block
    w = np.zeros((n, k), np.float32)
    for i in range(-(-n // br)):
        for j in range(-(-k // bc)):
            if rng.random() < density:
                w[i * br:(i + 1) * br, j * bc:(j + 1) * bc] = rng.normal(
                    size=(min(br, n - i * br), min(bc, k - j * bc)))
    return w


# ---------------------------------------------------------------------------
# Plan-aligned prox
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,shape", [
    ("['rem']['r0_attn']['mlp']['wi']", (64, 128)),        # 2D (in, out)
    ("['rem']['r0_attn']['attn']['wq']", (64, 4, 16)),     # (d, h, hd)
    ("['rem']['r0_attn']['attn']['wo']", (4, 16, 64)),     # (h, hd, d)
    ("['head']", (64, 128)),
])
def test_plan_prox_matches_out_in_group_l1(path, shape):
    """prox on the stored layout == group-l1 on the (out, in) view with the
    plan's block — the grid compress_params uses, so zeros line up."""
    plan = CompressionPlan(block=(8, 32), min_sparsity=0.3, min_size=512)
    prox = make_plan_prox(plan)
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    tau = 16.0
    got = prox(z, tau, path=path)

    slash = path.replace("']['", "/").strip("[']")
    view = _as_out_in(slash, np.asarray(z))
    want_view = prox_group_l1_blocks(jnp.asarray(view), tau, block=(8, 32))
    got_view = _as_out_in(slash, np.asarray(got))
    # The two sides sum each block norm in a different order (stored layout
    # vs (out, in) view), so the norms can differ by a few float32 ulps. The
    # scale 1 - tau/norm carries that error to every element of the block,
    # multiplied by |z|: allow a few ulps of the largest |z|.
    atol = 4 * np.finfo(np.float32).eps * float(np.abs(np.asarray(z)).max())
    np.testing.assert_allclose(got_view, np.asarray(want_view),
                               atol=atol, rtol=1e-6)
    # must produce whole zero blocks on that grid
    m = dense_to_bcsr(np.asarray(got_view), (8, 32))
    grid = int(np.prod(m.block_grid))
    assert m.n_blocks < grid, "no block hit exact zero"


def test_plan_prox_stacked_layers_and_fallback():
    plan = CompressionPlan(block=(8, 32), min_sparsity=0.3, min_size=512)
    prox = make_plan_prox(plan)
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.normal(size=(3, 64, 4, 16)).astype(np.float32))
    got = prox(z, 16.0, path="['layers']['b0_attn']['attn']['wq']")
    for layer in range(3):
        want = prox(z[layer], 16.0, path="['rem']['r0_attn']['attn']['wq']")
        np.testing.assert_allclose(np.asarray(got[layer]), np.asarray(want))
    # non-eligible leaves are left untouched: the group-l1 lambda is block-
    # norm-scaled, so an elementwise fallback would wipe out the (tied)
    # embedding in one step
    e = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(prox(e, 0.5, path="['embed']['embedding']")), np.asarray(e))


def test_spc_training_compresses_without_prune_step():
    """A few prox-opt steps with the plan prox must yield BCSR entries from
    compress_params directly — no pruning pass in between."""
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    plan = CompressionPlan(block=(8, 64), min_sparsity=0.3, min_size=4096)
    opt = prox_adam(3e-3, lam=100.0, prox_fn=make_plan_prox(plan))
    state = TrainState.create(params, opt)
    step = jax.jit(make_train_step(model, opt))
    batch = {"inputs": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    for _ in range(12):
        state, _ = step(state, batch)
    cp = compress_params(state.params, plan)
    assert cp.sparse, "group-l1 training produced no compressible layer"


# ---------------------------------------------------------------------------
# sparse_matmul custom VJP: dw via SDDMM
# ---------------------------------------------------------------------------

def _dw_against_oracle(n, k, block, density, m_rows, backend):
    rng = np.random.default_rng(hash((n, k, block, m_rows)) % 2**31)
    w = _block_sparse(rng, n, k, block, density)
    mat = dense_to_bcsr(w, block)
    x = jnp.asarray(rng.normal(size=(m_rows, k)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(m_rows, n)), jnp.float32)

    def loss(data):
        y = sparse_ops.sparse_matmul(
            x, dataclasses.replace(mat, data=data), backend=backend)
        return 0.5 * jnp.sum((y - t) ** 2)

    gd = jax.jit(jax.grad(loss))(mat.data)

    wd = jnp.asarray(np.pad(w, ((0, (-n) % block[0]), (0, (-k) % block[1]))))
    xp = jnp.pad(x, ((0, 0), (0, wd.shape[1] - k)))

    def dense_loss(wd):
        return 0.5 * jnp.sum(((xp @ wd.T)[:, :n] - t) ** 2)

    ogw = np.asarray(jax.grad(dense_loss)(wd))
    br, bc = block
    rows, cols = np.nonzero(np.any(
        np.asarray(bcsr_to_dense(mat)).reshape(
            mat.block_grid[0], br, mat.block_grid[1], bc
        ).transpose(0, 2, 1, 3) != 0, (2, 3)))
    assert np.all(np.asarray(mat.data[0]) == 0)
    got = np.asarray(gd)
    for s, (r, c) in enumerate(zip(rows, cols), start=1):
        np.testing.assert_allclose(
            got[s], ogw[r * br:(r + 1) * br, c * bc:(c + 1) * bc],
            atol=1e-3, rtol=1e-4)
    np.testing.assert_array_equal(got[0], 0)


@pytest.mark.parametrize("n,k,block,m_rows", [
    (64, 96, (16, 16), 32),
    (64, 64, (8, 64), 48),
    (96, 64, (32, 32), 16),
    (60, 90, (16, 16), 23),      # odd: shapes not block multiples, odd M
    (72, 100, (8, 64), 17),
])
def test_dw_matches_densified_autodiff(n, k, block, m_rows):
    _dw_against_oracle(n, k, block, 0.5, m_rows, backend="ref")


def test_dw_matches_densified_autodiff_pallas_backend():
    _dw_against_oracle(64, 96, (16, 16), 0.5, 32, backend="pallas")


def test_dw_stacked_layers_through_scan():
    """Per-layer dw of a scanned compressed stack equals the dense oracle."""
    rng = np.random.default_rng(7)
    block = (16, 16)
    ws = [_block_sparse(rng, 64, 64, block, d) for d in (0.5, 0.25, 0.75)]
    ms = [dense_to_bcsr(w, block) for w in ws]
    ns = max(m.data.shape[0] for m in ms)
    jm = max(m.gather_idx.shape[1] for m in ms)
    jt = max(m.gather_t_idx.shape[1] for m in ms)
    stk = jax.tree.map(lambda *xs: jnp.stack(xs),
                       *[pad_bcsr(m, ns, jm, jt) for m in ms])
    x0 = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)

    def loss(data_stk):
        st = dataclasses.replace(stk, data=data_stk)

        def body(h, wl):
            return jnp.tanh(sparse_ops.sparse_matmul(h, wl)), None

        h, _ = jax.lax.scan(body, x0, st)
        return jnp.sum(h ** 2)

    gd = np.asarray(jax.jit(jax.grad(loss))(stk.data))

    wds = [jnp.asarray(w) for w in ws]

    def dense_loss(wds):
        h = x0
        for wd in wds:
            h = jnp.tanh(h @ wd.T)
        return jnp.sum(h ** 2)

    ogs = jax.grad(dense_loss)(wds)
    for layer, (w, og) in enumerate(zip(ws, ogs)):
        wb = w.reshape(4, 16, 4, 16).transpose(0, 2, 1, 3)
        rows, cols = np.nonzero(np.any(wb != 0, axis=(2, 3)))
        og = np.asarray(og)
        for s, (r, c) in enumerate(zip(rows, cols), start=1):
            np.testing.assert_allclose(
                gd[layer, s], og[r * 16:(r + 1) * 16, c * 16:(c + 1) * 16],
                atol=1e-3, rtol=1e-4)
        # pad_bcsr padding slots carry exactly zero gradient
        np.testing.assert_array_equal(gd[layer, len(rows) + 1:], 0)
        np.testing.assert_array_equal(gd[layer, 0], 0)


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


def test_dw_jaxpr_has_no_dense_out_in_intermediate():
    """Jaxpr-level guarantee: the compressed dw path never materializes a
    dense (out, in) — or padded (out, in) — array. Run on the pallas
    (interpret) backend, where forward, dx and dw all stay in BCSR-land."""
    rng = np.random.default_rng(3)
    n, k, block = 64, 96, (16, 16)
    w = _block_sparse(rng, n, k, block, 0.4)
    mat = dense_to_bcsr(w, block)
    x = jnp.asarray(rng.normal(size=(32, k)), jnp.float32)

    def loss(x, data):
        y = sparse_ops.sparse_matmul(
            x, dataclasses.replace(mat, data=data), backend="pallas")
        return jnp.sum(y ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, mat.data)
    shapes = set(_all_avals(jaxpr.jaxpr, []))
    forbidden = {(n, k), (k, n),
                 (mat.block_grid[0] * block[0], mat.block_grid[1] * block[1])}
    assert not (shapes & forbidden), (
        f"dense (out, in) intermediate in the compressed grad path: "
        f"{shapes & forbidden}")


# ---------------------------------------------------------------------------
# Backend dispatch symmetry
# ---------------------------------------------------------------------------

def test_backend_dispatch_symmetry():
    assert sparse_ops.resolve_backend("auto") in ("pallas", "ref")
    with pytest.raises(ValueError):
        sparse_ops.resolve_backend("tpu")
    rng = np.random.default_rng(5)
    w = _block_sparse(rng, 64, 96, (16, 16), 0.5)
    mat = dense_to_bcsr(w, (16, 16))
    x = jnp.asarray(rng.normal(size=(32, 96)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)

    y_p = sparse_ops.sparse_matmul(x, mat, backend="pallas")
    y_r = sparse_ops.sparse_matmul(x, mat, backend="ref")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    t_p = sparse_ops.sparse_matmul_t(dy, mat, backend="pallas")
    t_r = sparse_ops.sparse_matmul_t(dy, mat, backend="ref")
    np.testing.assert_allclose(np.asarray(t_p), np.asarray(t_r),
                               atol=1e-4, rtol=1e-4)

    def loss(x, data, backend):
        y = sparse_ops.sparse_matmul(
            x, dataclasses.replace(mat, data=data), backend=backend)
        return jnp.sum(jnp.tanh(y))

    gx_p, gd_p = jax.grad(loss, argnums=(0, 1))(x, mat.data, "pallas")
    gx_r, gd_r = jax.grad(loss, argnums=(0, 1))(x, mat.data, "ref")
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gd_p), np.asarray(gd_r),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Mask-frozen debias retraining from CompressedParams
# ---------------------------------------------------------------------------

def test_debias_from_compressed_matches_dense_mask_reference():
    """Retrain from a compressed model (only BlockCSR.data + dense residue
    update, masks frozen); debiased compressed logits must match the
    densified mask-frozen reference to 1e-4 and keep the zero pattern."""
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    pruned = prune_blocks_for_plan(params, PLAN, 0.75)
    cp = compress_params(pruned, PLAN)

    trainable, rebuild = split_trainable(cp)
    assert trainable["bcsr_data"], "nothing compressed"
    mask = masks_lib.zero_mask(trainable)
    opt = prox_adam(1e-3, lam=0.0)
    st = TrainState(params=trainable, opt_state=opt.init(trainable),
                    mask=mask, step=jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(model, opt, param_transform=rebuild))
    batch = {"inputs": jnp.ones((2, 8), jnp.int32),
             "labels": jnp.ones((2, 8), jnp.int32)}
    losses = []
    for _ in range(5):
        st, metrics = step(st, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], "debias retraining is not learning"

    cp2 = rebuild(st.params)
    moved = any(
        np.any(np.asarray(a) != np.asarray(b))
        for a, b in zip(jax.tree.leaves(trainable["bcsr_data"]),
                        jax.tree.leaves(st.params["bcsr_data"])))
    assert moved, "debias never updated BlockCSR.data"

    dense_ref = densify_compressed(cp2, like=pruned)
    # frozen zero pattern: wherever the pruned reference was zero, the
    # debiased dense reference is still zero
    for a, b in zip(jax.tree.leaves(pruned), jax.tree.leaves(dense_ref)):
        za = np.asarray(a) == 0
        assert np.all(np.asarray(b)[za] == 0)

    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                model.cfg.vocab)
    lc, _ = jax.jit(model.prefill)(cp2, prompt, model.init_cache(2, 8))
    ld, _ = jax.jit(model.prefill)(dense_ref, prompt, model.init_cache(2, 8))
    np.testing.assert_allclose(np.asarray(lc), np.asarray(ld),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Zero-slot / empty-BCSR regression
# ---------------------------------------------------------------------------

def test_fully_pruned_model_compresses_serves_and_checkpoints(tmp_path):
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    pruned = prune_blocks_for_plan(params, PLAN, 1.0)       # kill everything
    cp = compress_params(pruned, PLAN)
    # empty BCSRs exist (only the pad slot)
    empties = [m for _, m in iter_bcsr(cp)
               if not np.any(np.asarray(m.data))]
    assert empties, "expected empty BCSRs at sparsity 1.0"

    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                model.cfg.vocab)
    logits, _ = jax.jit(model.prefill)(cp, prompt, model.init_cache(2, 8))
    assert np.all(np.isfinite(np.asarray(logits)))

    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(1, cp, extra={"plan": dataclasses.asdict(PLAN)})
    back = ckpt.restore_compressed(1)
    la, _ = jax.jit(model.prefill)(back, prompt, model.init_cache(2, 8))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(logits))


def test_mixed_empty_and_nonempty_stacked_slices_grads_are_zero():
    """One layer slice fully zero, others not: the stacked BCSR must serve
    the same logits as dense AND give exactly-zero dw for the empty slice
    (pad-slot validity masking in bsr_sddmm)."""
    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    pruned = prune_blocks_for_plan(params, PLAN, 0.6)
    wi = np.asarray(pruned["layers"]["b0_attn"]["mlp"]["wi"]).copy()
    wi[0] = 0.0                               # layer 0: fully pruned
    pruned["layers"]["b0_attn"]["mlp"]["wi"] = jnp.asarray(wi)
    cp = compress_params(pruned, PLAN)
    m = cp.sparse["layers"]["b0_attn"]["mlp"]["wi"]
    assert not np.any(np.asarray(m.data[0])), "slice 0 should be empty"

    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0,
                                model.cfg.vocab)
    ld, _ = jax.jit(model.prefill)(pruned, prompt, model.init_cache(2, 8))
    lc, _ = jax.jit(model.prefill)(cp, prompt, model.init_cache(2, 8))
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lc),
                               atol=1e-4, rtol=1e-4)

    trainable, rebuild = split_trainable(cp)

    def loss(tr):
        l, _ = model.prefill(rebuild(tr), prompt, model.init_cache(2, 8))
        return jnp.sum(l ** 2)

    g = jax.jit(jax.grad(loss))(trainable)
    g_wi = np.asarray(g["bcsr_data"]["layers/b0_attn/mlp/wi"])
    assert g_wi.shape == np.asarray(m.data).shape
    np.testing.assert_array_equal(g_wi[0], 0)          # empty slice: no grad
    assert np.any(g_wi[1] != 0), "non-empty slice lost its gradient"


# ---------------------------------------------------------------------------
# End-to-end CLI: train --sparse -> compressed checkpoint -> serve --sparse
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_train_sparse_to_serve_sparse(tmp_path, capsys):
    from repro.launch import serve as serve_launch
    from repro.launch import train as train_launch

    cp, hist_spc, hist_db, report = train_launch.main(
        ["--arch", "smollm-360m", "--reduced", "--sparse",
         "--steps", "12", "--debias-steps", "3", "--batch", "2",
         "--seq", "16", "--lr", "3e-3", "--compress", "group_l1:100",
         "--block", "8", "64", "--ckpt-dir", str(tmp_path),
         "--log-every", "4"])
    assert isinstance(cp, CompressedParams)
    assert cp.sparse, "SpC training compressed nothing"
    assert report["bcsr_bytes"] < report["dense_bytes"]

    out = serve_launch.main(
        ["--arch", "smollm-360m", "--reduced", "--sparse",
         "--ckpt-dir", str(tmp_path), "--batch", "2",
         "--prompt-len", "4", "--gen", "4"])
    assert out.shape == (2, 4)
    printed = capsys.readouterr().out
    assert "bcsr=" in printed and "compressed checkpoint" in printed
