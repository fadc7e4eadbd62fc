"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Model code annotates activations with *logical* axis names via
``shard_ann(x, axes)``; params get logical axes from path-based rules
(``param_logical_axes``). A mesh context (``use_mesh``) maps logical axes to
physical mesh axes with divisibility checks — an axis that doesn't divide is
silently replicated, which is what makes e.g. MQA (kv=1) work on a model=16
mesh while GQA (kv=16) shards.

Physical mesh axes: ("pod", "data", "model").
  batch            -> ("pod", "data")      data parallelism across pods
  heads/kv/mlp/
  vocab/experts/lru-> "model"              tensor / expert parallelism
  embed (params)   -> "data"               FSDP (ZeRO-3) weight sharding
  cache_seq        -> "model"              sequence-parallel decode
  capacity         -> "data"               MoE buffer sharding
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sparse.formats import BlockCSR, PaletteBCSR

_ctx = threading.local()

ACT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "capacity": "data",
    "cache_seq": "model",
    "lru": "model",
    "rwkv_heads": "model",
    "conv": None,
    # sequence-parallel residual stream (Megatron-SP style): the scan carry
    # between layers is seq-sharded over 'model', shrinking the per-layer
    # bwd residual stack by the TP degree (§Perf iteration C3). Norms/MLP/
    # projections are per-token so this adds no collectives there; XLA
    # re-shards at attention (KV gather) where cross-token work happens.
    "res_seq": "model",
    # FALLBACK sequence sharding for attention internals: claims 'model'
    # only when no primary axis (heads/kv) could — e.g. smollm's 15 heads
    # on a 16-way axis replicate attention 16x without it (§Perf A1).
    "seq_fb": "model",
}

_FALLBACK_AXES = {"seq_fb"}

PARAM_RULES: dict[str, Any] = {
    "layers": None,
    "embed": "data",          # FSDP axis for weight matrices
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "lru": "model",
    "rwkv_heads": "model",
    "lora": None,
    "conv": None,
    "none": None,
}


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], act_rules: Optional[dict] = None):
    """Context under which shard_ann applies with_sharding_constraint."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, act_rules or ACT_RULES) if mesh is not None else None
    try:
        yield
    finally:
        _ctx.state = prev


def current_mesh() -> Optional[Mesh]:
    st = getattr(_ctx, "state", None)
    return st[0] if st else None


def run_replicated(fn, *args):
    """Call ``fn(*args)``, a Pallas kernel, under the ambient mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under ``use_mesh`` the call runs inside
    a ``shard_map`` with every operand and result replicated: the compiler
    all-gathers sharded operands in front of the kernel and every device
    runs it on the whole problem. Without a mesh this is a plain call."""
    mesh = current_mesh()
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def _axes_to_spec(logical: Sequence[Optional[str]], shape, mesh: Mesh,
                  rules: dict) -> P:
    """Two-pass assignment: primary logical axes claim mesh axes first;
    fallback axes (seq_fb) only take what remains unclaimed."""
    taken: set[str] = set()
    spec: list = [None] * len(logical)

    def assign(i, dim, ax):
        phys = rules.get(ax) if ax else None
        if phys is None:
            return
        phys_t = (phys,) if isinstance(phys, str) else tuple(phys)
        phys_t = tuple(a for a in phys_t if a in mesh.shape and a not in taken)
        size = 1
        for a in phys_t:
            size *= mesh.shape[a]
        if phys_t and dim % size == 0 and dim > 0:
            spec[i] = phys_t if len(phys_t) > 1 else phys_t[0]
            taken.update(phys_t)

    for i, (dim, ax) in enumerate(zip(shape, logical)):
        if ax not in _FALLBACK_AXES:
            assign(i, dim, ax)
    for i, (dim, ax) in enumerate(zip(shape, logical)):
        if ax in _FALLBACK_AXES:
            assign(i, dim, ax)
    return P(*spec)


def shard_ann(x, logical: Sequence[Optional[str]]):
    """Annotate an activation with a sharding constraint (no-op w/o mesh)."""
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    if x.ndim != len(logical):
        return x
    spec = _axes_to_spec(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param logical axes by path pattern
# ---------------------------------------------------------------------------
# Matched in order against jax.tree_util.keystr paths; first hit wins.
# Leading "layers" axis is added automatically for scan-stacked leaves.

_PARAM_PATTERNS: list[tuple[str, tuple]] = [
    (r"embedding",                ("vocab", "embed")),
    (r"head",                     ("embed", "vocab")),
    (r"\bwq\b|'wq'",              ("embed", "heads", "head_dim")),
    (r"'wk'|'wv'",                ("embed", "kv_heads", "head_dim")),
    (r"'wo'",                     ("heads", "head_dim", "embed")),
    (r"'wi'|'wg'",                ("embed", "mlp")),
    (r"'w_down'",                 ("mlp", "embed")),
    (r"router",                   ("embed", "experts")),
    (r"experts.*'wi'|'ewi'|'ewg'", ("experts", "embed", "mlp")),
    (r"'ewo'",                    ("experts", "mlp", "embed")),
    (r"conv1d",                   ("conv", "lru")),
    (r"lru_in|lru_gate",          ("embed", "lru")),
    (r"lru_out",                  ("lru", "embed")),
    (r"rwkv_(r|k|v|g)",           ("embed", "embed2")),
    (r"rwkv_o",                   ("embed2", "embed")),
    (r"cm_(k)",                   ("embed", "mlp")),
    (r"cm_(v)",                   ("mlp", "embed")),
    (r"cm_(r)",                   ("embed", "embed2")),
    (r"lora_(a|b)",               ("lora", "lora")),
]

# 'embed2' lets square (d, d) matrices shard their *output* dim over model
PARAM_RULES["embed2"] = "model"


def _leaf_axes(path: str, leaf) -> tuple:
    for pat, axes in _PARAM_PATTERNS:
        if re.search(pat, path):
            if len(axes) == leaf.ndim:
                return axes
            if len(axes) == leaf.ndim - 1:
                return ("layers",) + axes      # scan-stacked
    # vectors / scalars / unknowns: replicate
    return tuple([None] * leaf.ndim)


def param_logical_axes(params) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    axes = [_leaf_axes(jax.tree_util.keystr(p), l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, axes)


# ---------------------------------------------------------------------------
# Compressed (BlockCSR / PaletteBCSR) leaves
# ---------------------------------------------------------------------------
# A compressed projection shards like its dense counterpart's OUTPUT dim:
# the BCSR slot axis stores nonzero blocks row-major over block rows of the
# (out, in) view, so splitting slots across devices splits block rows —
# the compressed analogue of sharding the dense out dimension. Everything
# that describes the sparsity pattern (col_idx/row_ptr/gather tables) and
# the palette is replicated: the tables are tiny, every device needs the
# full pattern to interpret its slots, and replication keeps the scalar-
# prefetch index maps host-local.
#
# Leaf-name -> logical axis of the (out, in) row dim (mapped to a physical
# mesh axis through PARAM_RULES, with the usual divisibility fallback to
# replication). Matched against keystr paths AND split_trainable's
# "bcsr_data/<path>" keys, so shardings survive the SpC-Retrain debias
# phase unchanged.

_BCSR_ROW_PATTERNS: list[tuple[str, str]] = [
    (r"\bwq\b|\bwk\b|\bwv\b",                         "heads"),
    (r"\bewi\b|\bewg\b|\bwi\b|\bwg\b|\bcm_k\b",       "mlp"),
    (r"\bewo\b|\bwo\b|\bcm_v\b|\brwkv_o\b|\blru_out\b", "embed"),
    (r"\brwkv_[rkvg]\b|\bcm_r\b",                     "embed2"),
    (r"\blru_in\b|\blru_gate\b",                      "lru"),
    (r"\bhead\b",                                     "vocab"),
]

_BCSR_META = ("shape", "block", "n_blocks", "bits")


def _is_bcsr(x) -> bool:
    return isinstance(x, (BlockCSR, PaletteBCSR))


def _bcsr_row_spec(path: str, arr, mesh: Mesh, rules: dict) -> P:
    """Spec for a BCSR block store (lead..., n_slots, br, bc): slot axis
    sharded by the dense out-dim rule, lead (layer/expert) axes replicated."""
    logical = None
    for pat, ax in _BCSR_ROW_PATTERNS:
        if re.search(pat, path):
            logical = ax
            break
    axes: list = [None] * arr.ndim
    slot_axis = arr.ndim - 3
    if slot_axis >= 0:
        axes[slot_axis] = logical
    return _axes_to_spec(axes, arr.shape, mesh, rules)


def _bcsr_leaf_shardings(path: str, leaf, mesh: Mesh, rules: dict):
    """Mirror a BlockCSR/PaletteBCSR with NamedShardings per array field:
    data/codes row-sharded, indices + gather tables + palette replicated."""
    repl = NamedSharding(mesh, P())
    fields = {f.name: repl for f in dataclasses.fields(leaf)
              if f.name not in _BCSR_META}
    store = "codes" if isinstance(leaf, PaletteBCSR) else "data"
    fields[store] = NamedSharding(
        mesh, _bcsr_row_spec(path, getattr(leaf, store), mesh, rules))
    return dataclasses.replace(leaf, **fields)


def param_shardings(params, mesh: Mesh, rules: Optional[dict] = None):
    """NamedSharding pytree for params (or any state with param-like paths).

    Handles compressed pytrees (``CompressedParams`` / trees holding
    ``BlockCSR``/``PaletteBCSR`` leaves, and ``split_trainable``'s
    ``bcsr_data`` view): index/palette arrays replicate, block stores shard
    along the slot (block-row) axis per the dense rule for that path."""
    rules = rules or PARAM_RULES
    flat, treedef = jax.tree_util.tree_flatten_with_path(params,
                                                         is_leaf=_is_bcsr)
    out = []
    for path, leaf in flat:
        ps = jax.tree_util.keystr(path)
        if _is_bcsr(leaf):
            out.append(_bcsr_leaf_shardings(ps, leaf, mesh, rules))
            continue
        if "bcsr_data" in ps:               # split_trainable's data view
            out.append(NamedSharding(mesh,
                                     _bcsr_row_spec(ps, leaf, mesh, rules)))
            continue
        axes = _leaf_axes(ps, leaf)
        spec = _axes_to_spec(axes, leaf.shape, mesh, rules)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def activation_sharding(mesh: Mesh, logical: Sequence[Optional[str]], shape):
    return NamedSharding(mesh, _axes_to_spec(logical, shape, mesh, ACT_RULES))


# ---------------------------------------------------------------------------
# Decode-cache logical axes by path pattern
# ---------------------------------------------------------------------------

_CACHE_PATTERNS: list[tuple[str, tuple]] = [
    (r"'k_scale'|'v_scale'",
     ("batch", "cache_seq", "kv_heads", "head_dim")),
    (r"'k'|'v'",   ("batch", "cache_seq", "kv_heads", "head_dim")),
    (r"'S'",       ("batch", "rwkv_heads", "head_dim", "head_dim2")),
    (r"'shift'",   ("batch", "embed")),
    (r"'h'",       ("batch", "lru")),
    (r"'conv'",    ("batch", "conv", "lru")),
]


def _cache_leaf_axes(path: str, leaf) -> tuple:
    for pat, axes in _CACHE_PATTERNS:
        if re.search(pat, path):
            if len(axes) == leaf.ndim:
                return axes
            if len(axes) == leaf.ndim - 1:
                return ("layers",) + axes       # scan-stacked
    return tuple([None] * leaf.ndim)


def cache_shardings(cache, mesh: Mesh):
    rules = dict(ACT_RULES)
    rules.update({"layers": None, "head_dim2": None})
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat:
        axes = _cache_leaf_axes(jax.tree_util.keystr(path), leaf)
        out.append(NamedSharding(mesh, _axes_to_spec(axes, leaf.shape, mesh,
                                                     rules)))
    return jax.tree_util.tree_unflatten(treedef, out)
