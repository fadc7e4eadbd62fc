"""The seam between the harness and a model architecture.

A configuration names its architecture (``"architecture"`` in
``bench/configs/<name>.json``). Everything the harness knows of that
architecture it asks of the module of the same name in one of
``PACKAGES`` (``bench/arch/<architecture>.py``), and of the plain reference
that module names (``bench/reference/<architecture>.py``, which imports
nothing of the program). A module answers:

``program_model(cfg, remat=False)``
    The program's ``(ModelConfig, Model)`` for the configuration.
``weights(cfg, seed)``
    The dense parameter tree in the program's layout, drawn from the seed,
    block-sparse on every compression target. The program's compression
    and the reference both take it. Its leaves may be host arrays, made one
    layer group at a time, so that no architecture needs its whole dense
    float32 tree on the device at once.
``projections(cfg)``
    ``{name: Projection}``: every projection the program compresses.
``attention(cfg)``
    ``[Attention]``: the layers that call paged attention.
``vocab(cfg)``
    The number of token ids.
``serve_tick_flops(cfg, start_pos, n_tokens, stored_weights)``,
``train_step_flops(cfg, batch, seq_len, stored_weights)``
    The model's work in one engine tick (per-slot start positions and token
    counts) and in one training step, for model FLOP utilization: every
    operation the model requires, stored-weight matmuls or not (attention,
    a state update, a router); ``stored_weights`` is summed over layers.
``reference``
    The plain reference: ``logits_at(weights, cfg, tokens, n_valid, at,
    quant=None)`` and ``loss_and_grad(weights, cfg, inputs, labels,
    z_weight, quant=None)``, where ``quant="fp8"`` is the control.
"""
from __future__ import annotations

import importlib
import pkgutil
from typing import NamedTuple, Optional

# packages searched for ``<architecture>.py``, in order
PACKAGES = ("bench.arch",)


class Projection(NamedTuple):
    """One compressed projection."""
    path: tuple        # keys from the parameter tree's root to the leaf
    out: int           # (out, in): the orientation of the program's BCSR view
    inp: int
    layers: int        # layers the leaf is stacked over (its leading axis)


class Attention(NamedTuple):
    """Layers that call paged attention with one geometry."""
    layers: int
    heads: int
    kv_heads: int
    hd: int
    window: Optional[int]    # sliding window in tokens; None = causal only


def known() -> dict:
    """{architecture: module name} over ``PACKAGES``."""
    out = {}
    for pkg in PACKAGES:
        path = importlib.import_module(pkg).__path__
        for m in pkgutil.iter_modules(path):
            if not m.name.startswith("_"):
                out.setdefault(m.name, f"{pkg}.{m.name}")
    return out


def of(cfg: dict):
    """The architecture module of a configuration. An unknown or missing
    architecture exits non-zero, naming the known ones."""
    name = cfg.get("architecture")
    mods = known()
    if name not in mods:
        raise SystemExit(
            f"bench: configuration {cfg.get('name')!r} names architecture "
            f"{name!r}; known architectures: {sorted(mods)}")
    return importlib.import_module(mods[name])
