"""Plain float32 reference of a dense GQA decoder (Llama / Qwen3 family).

Written from the published architecture, not from the program: RMSNorm
pre-norm blocks, rotary embedding on the two halves of each head
(``rotate_half``), optional per-head RMSNorm of q and k before the rotation
(Qwen3), causal grouped-query attention, a SiLU-gated MLP and a head tied
to the input embedding. No kernel, cache or batching: one sequence at a
time, layer by layer under ``lax.scan``, every matmul at
``precision="highest"``.

``quant="fp8"`` is the benchmark's control: every matmul operand is
rounded to float8 e4m3 with a per-tensor scale, one precision below the
configuration's bfloat16. A check that cannot tell this control from the
program is too loose.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn


def _q(x, quant: Optional[str]):
    """Round a matmul operand to float8 under a per-tensor scale, or not
    at all."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown operand precision {quant!r}")
    scale = jax.lax.stop_gradient(
        F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, pos, theta):
    """x: (S, heads, hd); rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, pos, valid, eps, theta, quant):
    a = lp["attn"]
    S = x.shape[0]
    H, KV = a["wq"].shape[1], a["wk"].shape[1]
    hd = a["wq"].shape[2]
    h = rms_norm(x, lp["pre_norm"]["scale"], eps)
    q = _mm("sd,dhk->shk", h, a["wq"], quant)
    k = _mm("sd,dhk->shk", h, a["wk"], quant)
    v = _mm("sd,dhk->shk", h, a["wv"], quant)
    if "q_norm" in a:
        q = rms_norm(q, a["q_norm"]["scale"], eps)
        k = rms_norm(k, a["k_norm"]["scale"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = H // KV
    qg = q.reshape(S, KV, g, hd)
    s = _mm("qkgh,ckh->kgqc", qg, k, quant) * hd ** -0.5
    causal = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("kgqc,ckh->qkgh", p, v, quant).reshape(S, H, hd)
    x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
    h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
    mlp = lp["mlp"]
    gate = jax.nn.silu(_mm("sd,df->sf", h, mlp["wg"], quant))
    up = _mm("sd,df->sf", h, mlp["wi"], quant)
    return x + _mm("sf,fd->sd", gate * up, mlp["wo"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _logits_at(weights, tokens, n_valid, at, *, eps, theta, quant):
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    valid = pos < n_valid
    x = weights["embed"]["embedding"][tokens]

    def body(x, lp):
        return _layer(x, lp, pos, valid, eps, theta, quant), None

    x, _ = jax.lax.scan(body, x, weights["layers"]["b0_attn"])
    x = rms_norm(x[at], weights["final_norm"]["scale"], eps)
    return _mm("sd,vd->sv", x, weights["embed"]["embedding"], quant)


def logits_at(weights, cfg: dict, tokens, n_valid: int, at,
              quant: Optional[str] = None):
    """Logits (len(at), vocab) after the prefix ending at each position in
    ``at`` of one sequence. ``tokens`` may be padded past ``n_valid``: keys
    there are masked, so a fixed padded length keeps one compiled program
    for every sequence of a cell."""
    m = cfg["model"]
    return _logits_at(weights, jnp.asarray(tokens, jnp.int32),
                      jnp.int32(n_valid), jnp.asarray(at, jnp.int32),
                      eps=float(m["rms_norm_eps"]),
                      theta=float(m["rope_theta"]), quant=quant)


def _row_loss(weights, inputs, labels, eps, theta, z_weight, quant):
    """Next-token cross entropy of one row, plus ``z_weight`` times the
    mean squared log-partition (the configured z-loss)."""
    S = inputs.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    valid = jnp.ones((S,), bool)
    x = weights["embed"]["embedding"][inputs]

    @jax.checkpoint
    def body(x, lp):
        return _layer(x, lp, pos, valid, eps, theta, quant), None

    x, _ = jax.lax.scan(body, x, weights["layers"]["b0_attn"])
    x = rms_norm(x, weights["final_norm"]["scale"], eps)
    logits = _mm("sd,vd->sv", x, weights["embed"]["embedding"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll) + z_weight * jnp.mean(lse * lse)


@functools.partial(jax.jit,
                   static_argnames=("eps", "theta", "z_weight", "quant"))
def _row_grad(weights, inputs, labels, *, eps, theta, z_weight, quant):
    return jax.value_and_grad(_row_loss)(weights, inputs, labels, eps,
                                         theta, z_weight, quant)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, g):
    return jax.tree.map(jnp.add, total, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(total, s):
    return jax.tree.map(lambda x: x * s, total)


def loss_and_grad(weights, cfg: dict, inputs, labels, z_weight: float,
                  quant: Optional[str] = None):
    """Mean loss and gradient over a batch, one row at a time (the sum of
    gradients is accumulated in place)."""
    m = cfg["model"]
    kw = dict(eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]),
              z_weight=float(z_weight), quant=quant)
    total, grads = 0.0, None
    for r in range(inputs.shape[0]):
        loss, g = _row_grad(weights, jnp.asarray(inputs[r]),
                            jnp.asarray(labels[r]), **kw)
        total = total + loss
        grads = g if grads is None else _accumulate(grads, g)
        del g
    b = inputs.shape[0]
    return total / b, _scale(grads, 1.0 / b)
