"""train_step / grad-accumulated train_step factories.

The step is a pure function (state, batch) -> (state, metrics) suitable for
jax.jit with in/out shardings from distributed/sharding.py. Compression is
first-class: the optimizer IS a prox optimizer, so every step ends with the
paper's soft-thresholding (or runs debiased with a frozen mask).

Microbatching (gradient accumulation) splits the batch on the leading axis
and lax.scan's over microbatches — used when the per-device activation
footprint of the full global batch exceeds HBM.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.optimizers import ProxOptimizer
from repro.train.losses import next_token_loss
from repro.train.state import TrainState

PyTree = Any


def make_loss_fn(model, aux_weight: float = 1e-2,
                 loss_seq_chunk: int = 0,
                 param_transform: Optional[Callable] = None) -> Callable:
    """loss_seq_chunk > 0: compute head+loss in sequence chunks so the
    (B, S, vocab) logits tensor is never materialized (decisive for the
    256k-vocab archs — see EXPERIMENTS.md §Perf iteration C1). Each chunk is
    rematted so backward recomputes its logits instead of saving them.

    ``param_transform`` maps the *trainable* pytree to what the model
    consumes before the forward (pure restructuring; grads flow through).
    Used by SpC-Retrain's debias phase, where the trainable tree is
    ``sparse.compress.split_trainable``'s {dense residue, BlockCSR.data}
    view and the transform rebuilds the ``CompressedParams``."""

    def loss_fn(params, batch):
        if param_transform is not None:
            params = param_transform(params)
        if not loss_seq_chunk or batch["labels"].shape[1] <= loss_seq_chunk:
            logits, aux = model.apply_train(params, batch)
            with jax.named_scope("loss"):
                loss = next_token_loss(logits, batch["labels"])
        else:
            hidden, aux = model.apply_hidden(params, batch)
            b, s = batch["labels"].shape
            n = s // loss_seq_chunk
            assert s % loss_seq_chunk == 0, (s, loss_seq_chunk)
            hc = hidden.reshape(b, n, loss_seq_chunk, -1).transpose(1, 0, 2, 3)
            lc = batch["labels"].reshape(b, n, loss_seq_chunk).transpose(1, 0, 2)

            def chunk_loss(carry, xs):
                h, l = xs
                logits = model.head(params, h)
                with jax.named_scope("loss"):
                    return carry + next_token_loss(logits, l), None

            body = jax.checkpoint(
                chunk_loss, policy=jax.checkpoint_policies.nothing_saveable)
            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
            loss = total / n
        loss = loss + aux_weight * aux["load_balance"] + aux["z_loss"]
        return loss, {"loss": loss, "load_balance": aux["load_balance"]}

    return loss_fn


def make_train_step(model, opt: ProxOptimizer,
                    microbatches: int = 1,
                    aux_weight: float = 1e-2,
                    loss_seq_chunk: int = 0,
                    param_transform: Optional[Callable] = None) -> Callable:
    loss_fn = make_loss_fn(model, aux_weight, loss_seq_chunk=loss_seq_chunk,
                           param_transform=param_transform)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    cdt = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
           "float32": jnp.float32}[model.cfg.compute_dtype] \
        if hasattr(model, "cfg") else jnp.float32

    def cast_compute(params):
        """Mixed precision: one hoisted cast of the master fp32 params to
        the compute dtype, so every FSDP weight all-gather inside the
        microbatch/layer loops moves bf16, not fp32 (§Perf iteration C4).
        Grads w.r.t. the cast copy apply to the fp32 master unchanged."""
        return jax.tree.map(
            lambda p: p.astype(cdt)
            if (p.dtype == jnp.float32 and p.ndim >= 2) else p, params)

    def compute_grads(params, batch):
        params = cast_compute(params)
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return grads, metrics
        split = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                + x.shape[1:]), batch)

        def body(acc, mb):
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return acc, metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        acc, metrics = jax.lax.scan(body, zeros, split)
        grads = jax.tree.map(lambda g: g / microbatches, acc)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return grads, metrics

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        grads, metrics = compute_grads(state.params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt = opt.update(grads, state.opt_state,
                                             state.params, mask=state.mask)
        grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, grad_norm=grad_norm)
        return TrainState(params=new_params, opt_state=new_opt,
                          mask=state.mask, step=state.step + 1), metrics

    return train_step


def make_eval_step(model) -> Callable:
    loss_fn = make_loss_fn(model)

    def eval_step(params, batch):
        _, metrics = loss_fn(params, batch)
        return metrics

    return eval_step
