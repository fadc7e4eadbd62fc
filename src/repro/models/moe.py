"""Top-k MoE layer with capacity-based dispatch, two implementations:

1. ``gspmd``: single-program dispatch (scatter into (E, C, d) buffers under
   sharding constraints, XLA chooses the collectives). Baseline; measured
   collective-bound on the production mesh — GSPMD lowers the token scatter
   to repeated (T*k, d) all-reduces (EXPERIMENTS.md §Perf B-iterations).

2. ``shard_map``: real expert parallelism. The batch is data-sharded and
   replicated over 'model'; each model column owns E/TP experts, locally
   selects + buffers the tokens routed to ITS experts (zero-communication
   dispatch), runs its expert FFNs, and one psum over 'model' combines the
   top-k contributions. FSDP-sharded expert weights are all-gathered once
   inside the region. This is the TPU-native analogue of switch-style
   all-to-all EP: because x is already replicated over the TP axis, the
   dispatch needs NO collective at all.

``apply_moe`` auto-selects: shard_map under a mesh whose 'model' axis
divides the expert count, gspmd otherwise (including meshless CPU tests).
Capacity semantics: gspmd enforces a global capacity; shard_map enforces a
per-data-shard capacity (what a real EP deployment does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.sharding import current_mesh, shard_ann
from repro.models.layers import activation, truncated_normal_init
from repro.sparse import ops as sparse_ops

Array = jax.Array


def init_moe(key, cfg: ModelConfig) -> dict:
    e = cfg.moe
    d, ff = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(key, 7)
    p = {
        "router": truncated_normal_init(ks[0], (d, e.n_experts), 1.0),
        "ewi": truncated_normal_init(ks[1], (e.n_experts, d, ff), 2.0),
        "ewg": truncated_normal_init(ks[2], (e.n_experts, d, ff), 2.0),
        "ewo": truncated_normal_init(ks[3], (e.n_experts, ff, d), 2.0),
    }
    if e.n_shared_experts:
        sff = ff * e.n_shared_experts
        p["shared"] = {
            "wi": truncated_normal_init(ks[4], (d, sff), 2.0),
            "wg": truncated_normal_init(ks[5], (d, sff), 2.0),
            "wo": truncated_normal_init(ks[6], (sff, d), 2.0),
        }
    return p


def _capacity(n_tokens: int, e: MoEConfig) -> int:
    c = int(e.capacity_factor * n_tokens * e.top_k / e.n_experts)
    return max(8, -(-c // 8) * 8)          # pad to 8 for TPU-friendly tiles


def apply_moe(p: dict, x: Array, cfg: ModelConfig,
              impl: str = "auto",
              sparse: dict | None = None) -> tuple[Array, dict]:
    """x: (B, S, d) -> (B, S, d), aux losses {load_balance, z_loss}.

    ``sparse`` maps expert projection names ({"ewi"|"ewg"|"ewo"}) to
    E-stacked BlockCSR/PaletteBCSR weights (per-expert (out, in) slices,
    built by ``sparse.compress.compress_params``); present entries run the
    compressed kernel path via a ``lax.map`` over experts. Compressed
    experts always take the single-program (gspmd) dispatch — under a mesh
    GSPMD partitions the mapped expert FFNs like any other scanned
    computation, while the shard_map EP path would need per-column BCSR
    re-chunking (open ROADMAP item)."""
    mesh = current_mesh()
    if sparse:
        if impl == "shard_map":
            raise ValueError("compressed (BCSR) experts serve through the "
                             "gspmd dispatch; shard_map EP does not support "
                             "sparse expert weights")
        return _apply_moe_gspmd(p, x, cfg, sparse)
    if impl == "auto":
        use_sm = (mesh is not None and "model" in mesh.shape
                  and cfg.moe.n_experts % mesh.shape["model"] == 0)
        impl = "shard_map" if use_sm else "gspmd"
    if impl == "shard_map":
        return _apply_moe_shard_map(p, x, cfg, mesh)
    return _apply_moe_gspmd(p, x, cfg)


def _shared_expert(p: dict, xt: Array, cfg: ModelConfig) -> Array:
    sp = p["shared"]
    dt = xt.dtype
    f = activation(cfg.act)
    hs = f(jnp.einsum("td,df->tf", xt, sp["wg"].astype(dt))) * \
        jnp.einsum("td,df->tf", xt, sp["wi"].astype(dt))
    return jnp.einsum("tf,fd->td", hs, sp["wo"].astype(dt))


def _router_and_aux(router_w, xt, e: MoEConfig):
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, e.top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e.n_experts), axis=0)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gate, expert_idx, me, ce, z


def _apply_moe_shard_map(p: dict, x: Array, cfg: ModelConfig,
                         mesh) -> tuple[Array, dict]:
    e = cfg.moe
    b, s, d = x.shape
    dt = x.dtype
    f = activation(cfg.act)
    tp = mesh.shape["model"]
    e_loc = e.n_experts // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    t_loc = (b // dp if b % dp == 0 else b) * s
    cap = _capacity(t_loc, e)

    def body(x_loc, router_w, ewi, ewg, ewo):
        bl = x_loc.shape[0]
        xt = x_loc.reshape(bl * s, d)
        gate, expert_idx, me, ce, z = _router_and_aux(router_w, xt, e)

        col = jax.lax.axis_index("model")
        # FSDP: gather this column's expert weights over 'data' (bf16)
        wi = jax.lax.all_gather(ewi.astype(dt), "data", axis=1, tiled=True)
        wg = jax.lax.all_gather(ewg.astype(dt), "data", axis=1, tiled=True)
        wo = jax.lax.all_gather(ewo.astype(dt), "data", axis=2, tiled=True)

        # local dispatch: only choices routed to THIS column's experts
        flat_e = expert_idx.reshape(-1)                      # (t*k,)
        flat_g = gate.reshape(-1)
        is_local = (flat_e // e_loc) == col
        le = jnp.where(is_local, flat_e % e_loc, e_loc)      # e_loc = trash
        eoh = jax.nn.one_hot(le, e_loc + 1, dtype=jnp.int32)
        pos = jnp.sum(jnp.cumsum(eoh, axis=0) * eoh, axis=-1) - 1
        keep = is_local & (pos < cap)
        slot = jnp.where(keep, pos, cap)
        le = jnp.where(keep, le, e_loc)

        buf = jnp.zeros((e_loc + 1, cap + 1, d), dt)
        tok_rep = jnp.repeat(jnp.arange(bl * s), e.top_k)
        buf = buf.at[le, slot].set(xt[tok_rep])
        buf = buf[:e_loc, :cap]

        h = f(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

        out_pad = jnp.concatenate(
            [out_buf, jnp.zeros((1, cap, d), dt)], axis=0)
        out_pad = jnp.concatenate(
            [out_pad, jnp.zeros((e_loc + 1, 1, d), dt)], axis=1)
        gathered = out_pad[le, slot]
        weighted = gathered * (flat_g * keep).astype(dt)[:, None]
        y = jax.ops.segment_sum(weighted, tok_rep, num_segments=bl * s)
        # combine top-k contributions across expert columns
        y = jax.lax.psum(y, "model")
        # aux stats: average over data shards (tokens), model-replicated
        me = jax.lax.pmean(me, batch_axes) if batch_axes else me
        ce = jax.lax.pmean(ce, batch_axes) if batch_axes else ce
        z = jax.lax.pmean(z, batch_axes) if batch_axes else z
        return y.reshape(bl, s, d), me, ce, z

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0]
                                                    if batch_axes else None)
    xspec = P(bspec if b % dp == 0 else None, None, None)
    y, me, ce, z = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(xspec, P(None), P(None), P()),
        check_vma=False,
    )(x, p["router"], p["ewi"], p["ewg"], p["ewo"])

    aux = {"load_balance": e.n_experts * jnp.sum(me * ce),
           "z_loss": e.router_z_loss * z}
    if "shared" in p:
        xt = x.reshape(b * s, d)
        y = y + _shared_expert(p, xt, cfg).reshape(b, s, d)
    return shard_ann(y, ("batch", "seq", "embed")), aux


def _apply_moe_gspmd(p: dict, x: Array, cfg: ModelConfig,
                     sparse: dict | None = None) -> tuple[Array, dict]:
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = e.top_k
    cap = _capacity(t, e)
    dt = x.dtype
    f = activation(cfg.act)

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)          # (t, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # aux losses (Switch-style load balance + router z-loss)
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], e.n_experts)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = {
        "load_balance": e.n_experts * jnp.sum(me * ce),
        "z_loss": e.router_z_loss * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }

    # --- dispatch: position of each (token, choice) within its expert ------
    flat_e = expert_idx.reshape(-1)                      # (t*k,)
    eoh = jax.nn.one_hot(flat_e, e.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(eoh, axis=0) * eoh                  # running count
    pos_in_e = jnp.sum(pos, axis=-1) - 1                 # (t*k,)
    keep = pos_in_e < cap
    slot = jnp.where(keep, pos_in_e, cap)                # cap = overflow bin

    buf = jnp.zeros((e.n_experts, cap + 1, d), dt)
    tok_rep = jnp.repeat(jnp.arange(t), k)
    buf = buf.at[flat_e, slot].set(xt[tok_rep])
    buf = buf[:, :cap]
    buf = shard_ann(buf, ("experts", "capacity", "embed"))

    # --- expert FFN (grouped einsum, experts sharded over 'model') ---------
    # Compressed experts: lax.map slices the E-stacked BCSR (same mechanism
    # as the layer-stack scan) and runs sparse_matmul per expert — the
    # custom_vjp still applies, so SpC-Retrain's SDDMM weight gradient
    # reaches MoE expert data at resident slots only.
    def emm(name, inp):
        """(E, cap, in) x per-expert (in, out) -> (E, cap, out)."""
        if sparse and name in sparse:
            y = jax.lax.map(
                lambda wx: sparse_ops.sparse_matmul(wx[1], wx[0]),
                (sparse[name], inp))
            return y.astype(dt)
        return jnp.einsum("eci,eio->eco", inp, p[name].astype(dt))

    h = f(emm("ewg", buf)) * emm("ewi", buf)
    h = shard_ann(h, ("experts", "capacity", "mlp"))
    out_buf = emm("ewo", h)
    out_buf = shard_ann(out_buf, ("experts", "capacity", "embed"))

    # --- combine ------------------------------------------------------------
    out_pad = jnp.concatenate(
        [out_buf, jnp.zeros((e.n_experts, 1, d), dt)], axis=1)
    gathered = out_pad[flat_e, slot]                     # (t*k, d); dropped -> 0
    weighted = gathered * gate.reshape(-1, 1).astype(dt)
    y = jax.ops.segment_sum(weighted, tok_rep, num_segments=t)

    if "shared" in p:
        sp = p["shared"]
        hs = f(jnp.einsum("td,df->tf", xt, sp["wg"].astype(dt))) * \
            jnp.einsum("td,df->tf", xt, sp["wi"].astype(dt))
        y = y + jnp.einsum("tf,fd->td", hs, sp["wo"].astype(dt))

    y = y.reshape(b, s, d)
    return shard_ann(y, ("batch", "seq", "embed")), aux
