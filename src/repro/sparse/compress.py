"""Whole-model compression: dense params -> ``CompressedParams``.

This is the serving-side half of the paper's pipeline: after sparse-coding
training (or block magnitude pruning) has produced weights with whole zero
blocks, ``compress_params`` converts every compressible projection to
BlockCSR and returns a registered pytree that the model's apply functions
consume directly — the forward pass runs on the compressed representation
(EIE-style), and the checkpoint stores it (Deep-Compression-style).

Layout knowledge lives here, not in the model code: each target weight is
viewed as a 2D ``(out, in)`` matrix (the orientation ``sparse_matmul``
expects, ``y = x @ W'``):

    attention wq/wk/wv  (d, h, hd)  -> (h*hd, d)
    attention wo        (h, hd, d)  -> (d, h*hd)
    mlp wi/wg           (d, ff)     -> (ff, d)
    mlp wo              (ff, d)     -> (d, ff)
    moe ewi/ewg         (E, d, ff)  -> per-expert (ff, d)
    moe ewo             (E, ff, d)  -> per-expert (d, ff)
    rwkv tm r/k/v/g/o   (d, e)      -> (e, d)
    rwkv cm_k/cm_v/cm_r (d, ff)...  -> 2D transpose
    rg-lru in/gate/out  (d, w)...   -> 2D transpose
    head                (d, vocab)  -> (vocab, d)

Weights inside the scanned layer stack carry a leading ``n_super`` axis, and
MoE expert projections an additional per-expert axis — every leading stack
axis is treated the same way: each 2D slice is compressed separately, padded
to a uniform slot count (``formats.pad_bcsr``) and stacked, so the compressed
stack rides through ``lax.scan`` (layer axis) and ``lax.map`` (expert axis,
inside ``apply_moe``) exactly like the dense one. Matrices that don't
compress (too small, too dense, or BCSR bytes >= dense bytes) stay dense in
the residue — the ``CompressionPlan`` dense fallback.

When the plan sets ``quantize_bits`` (8 or 4, with per-layer overrides),
the emitted leaves are ``PaletteBCSR``: block data k-means-clustered to a
per-layer palette and stored as uint8 codes (Deep Compression stage 2) —
``quantize_compressed`` is also callable standalone as the last pipeline
stage, after debias retraining.
"""
from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prox as prox_lib
from repro.core import quantize as quantize_lib
from repro.sparse.formats import (BlockCSR, PaletteBCSR, dense_to_bcsr,
                                  pack_uint4, pad_bcsr)

PyTree = Any

# per-layer sub-dicts and the projection names eligible for compression
_LAYER_TARGETS = {"attn": ("wq", "wk", "wv", "wo"),
                  "mlp": ("wi", "wg", "wo"),
                  "moe": ("ewi", "ewg", "ewo"),          # per-expert stacks
                  "tm": ("rwkv_r", "rwkv_k", "rwkv_v", "rwkv_g", "rwkv_o"),
                  "cm": ("cm_k", "cm_v", "cm_r"),
                  "rec": ("lru_in", "lru_gate", "lru_out")}

# MoE expert projections: the leading expert axis is a stack axis (compressed
# per expert, padded uniformly, stacked), exactly like the scanned layer axis
_PER_EXPERT = ("ewi", "ewg", "ewo")


def _lead_axes(name: str, stacked: bool) -> int:
    """Leading stack axes ahead of the per-matrix layout: the scanned layer
    axis (when inside ``layers/``), plus the per-expert axis for MoE."""
    return int(stacked) + int(name in _PER_EXPERT)


@dataclasses.dataclass(frozen=True)
class CompressionPlan:
    """What to compress and how.

    block:        default (br, bc) BCSR tile, on the (out, in) view. The
                  compiled TPU kernels need both dims to be multiples of
                  128 (``kernels.bsr_spmm.check_block_tiling``); smaller
                  tiles run only in interpret mode or on the ref backend.
    min_sparsity: minimum fraction of all-zero blocks; below it the matrix
                  stays dense (fallback). For stacked layers the *worst*
                  slice must clear the bar (the stack compresses uniformly).
    min_size:     matrices with fewer elements stay dense.
    overrides:    ((path_substring, (br, bc)), ...) per-layer block sizes;
                  first match wins.
    quantize_bits: None keeps fp BlockCSR; 8 or 4 palette-quantizes the
                  block data (Deep Compression stage 2 — k-means palette,
                  code 0 reserved for exact zero) so ``compress_params``
                  emits ``PaletteBCSR`` leaves the runtime serves directly.
    quantize_overrides: ((path_substring, bits), ...) per-layer bit widths;
                  first match wins, bits 0 keeps that layer fp.
    slot_multiple: pad every BCSR slot count (pad slot 0 included) up to a
                  multiple of this, so the block store's slot axis divides a
                  mesh axis and shards instead of silently replicating
                  (small models easily land on odd slot counts). None =
                  auto: the lcm of the active mesh's axis sizes when
                  ``compress_params`` runs under ``use_mesh`` (or the
                  explicit value the launchers pass from ``--mesh``),
                  1 otherwise. Padding slots are zero blocks — output- and
                  gradient-invariant (``pad_bcsr``).
    """
    block: tuple[int, int] = (128, 128)
    min_sparsity: float = 0.5
    min_size: int = 4096
    overrides: tuple = ()
    quantize_bits: Optional[int] = None
    quantize_overrides: tuple = ()
    slot_multiple: Optional[int] = None

    def block_for(self, path: str) -> tuple[int, int]:
        for sub, blk in self.overrides:
            if sub in path:
                return tuple(blk)
        return self.block

    def bits_for(self, path: str) -> Optional[int]:
        """Palette bit width for a layer path (None = keep fp BlockCSR)."""
        for sub, bits in self.quantize_overrides:
            if sub in path:
                return int(bits) or None
        return self.quantize_bits


@partial(jax.tree_util.register_dataclass,
         data_fields=["dense", "sparse"], meta_fields=["plan"])
@dataclasses.dataclass
class CompressedParams:
    """Dense residue + {mirrored subtree: BlockCSR} sparse map.

    ``dense`` keeps the original tree structure; compressed leaves are
    replaced by zero-size placeholders (so the layer-stack scan still sees a
    leaf with the right leading axis). ``sparse`` mirrors the params nesting
    ("layers"/<layer>/("attn"|"mlp")/<name>, "rem"/..., "head") with BlockCSR
    leaves — stacked over ``n_super`` for the scanned layers.
    """
    dense: PyTree
    sparse: PyTree
    plan: CompressionPlan


def _is_bcsr(x) -> bool:
    return isinstance(x, (BlockCSR, PaletteBCSR))


# ---------------------------------------------------------------------------
# (out, in) orientation
# ---------------------------------------------------------------------------

def _as_out_in(path: str, arr: np.ndarray) -> Optional[np.ndarray]:
    """View a stored weight as the 2D (out, in) matrix the kernel consumes."""
    leaf = path.rsplit("/", 1)[-1]
    if arr.ndim == 2:
        return np.ascontiguousarray(arr.T)
    if arr.ndim == 3 and "/attn/" in f"/{path}/":
        if leaf in ("wq", "wk", "wv"):          # (d, heads, hd)
            return np.ascontiguousarray(arr.reshape(arr.shape[0], -1).T)
        if leaf == "wo":                        # (heads, hd, d)
            return np.ascontiguousarray(arr.reshape(-1, arr.shape[-1]).T)
    return None


def _from_out_in(path: str, mat: np.ndarray, orig_shape) -> np.ndarray:
    """Inverse of ``_as_out_in``: back to the stored layout."""
    return np.ascontiguousarray(mat.T).reshape(orig_shape)


# ---------------------------------------------------------------------------
# Block pruning aligned to the plan (serving-side Pru baseline)
# ---------------------------------------------------------------------------

def _prune_blocks_2d(mat: np.ndarray, block: tuple[int, int],
                     sparsity: float) -> np.ndarray:
    """Zero the lowest-L2 fraction of (br, bc) blocks of a (out, in) view."""
    br, bc = block
    r, c = mat.shape
    pr, pc = (-r) % br, (-c) % bc
    mp = np.pad(mat, ((0, pr), (0, pc)))
    R, C = mp.shape[0] // br, mp.shape[1] // bc
    blocks = mp.reshape(R, br, C, bc).transpose(0, 2, 1, 3).copy()
    norms = np.sqrt((blocks.astype(np.float64) ** 2).sum(axis=(2, 3)))
    k = int(round(sparsity * norms.size))
    if k > 0:
        flat = norms.ravel()
        kill = np.zeros(flat.size, bool)
        kill[np.argsort(flat, kind="stable")[:k]] = True
        blocks[kill.reshape(R, C)] = 0
    mp = blocks.transpose(0, 2, 1, 3).reshape(R * br, C * bc)
    return mp[:r, :c]


def prune_blocks_for_plan(params: PyTree, plan: CompressionPlan,
                          sparsity: float) -> PyTree:
    """Magnitude-prune whole blocks on the plan's (out, in) BCSR grid.

    Unstructured magnitude pruning leaves ~every MXU-sized block occupied,
    so nothing would compress; this is the block-aligned variant that makes
    the compressed runtime real for a Pru-style serving flow.
    """
    def handle(path, arr):
        view = _as_out_in(path, arr)
        if view is None or view.size < plan.min_size:
            return arr
        pruned = _prune_blocks_2d(view, plan.block_for(path), sparsity)
        return jnp.asarray(_from_out_in(path, pruned, arr.shape),
                           dtype=arr.dtype)

    return _walk_targets(params, handle)


def _walk_targets(params: PyTree, handle) -> PyTree:
    """Apply ``handle(path, arr)`` to every compressible leaf, copying the
    tree. Leading stack axes (scanned layers, MoE experts) are handled
    slice-wise with a uniform outcome."""
    out = jax.tree.map(lambda x: x, params)   # structural copy

    def per_layer(layer, path, stacked):
        for sub, names in _LAYER_TARGETS.items():
            if sub not in layer:
                continue
            for name in names:
                if name not in layer[sub]:
                    continue
                arr = np.asarray(layer[sub][name])
                p = f"{path}/{sub}/{name}"
                lead = _lead_axes(name, stacked)
                if lead:
                    flat = arr.reshape((-1,) + arr.shape[lead:])
                    slices = [np.asarray(handle(p, s)) for s in flat]
                    layer[sub][name] = jnp.asarray(
                        np.stack(slices).reshape(arr.shape), dtype=arr.dtype)
                else:
                    layer[sub][name] = jnp.asarray(handle(p, arr),
                                                   dtype=arr.dtype)

    for lkey, layer in out.get("layers", {}).items():
        per_layer(layer, f"layers/{lkey}", stacked=True)
    for lkey, layer in out.get("rem", {}).items():
        per_layer(layer, f"rem/{lkey}", stacked=False)
    if "head" in out:
        out["head"] = jnp.asarray(handle("head", np.asarray(out["head"])),
                                  dtype=out["head"].dtype)
    return out


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

def _resolve_slot_multiple(plan: CompressionPlan) -> int:
    """Slot-axis packing multiple: the plan's explicit value, else the lcm
    of the ambient mesh's axis sizes (any axis the per-path row rule maps
    to then divides the slot count), else 1 (no packing)."""
    if plan.slot_multiple is not None:
        return max(int(plan.slot_multiple), 1)
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return 1
    return int(np.lcm.reduce([int(s) for s in mesh.shape.values()]))


def _try_compress(arr: np.ndarray, path: str, plan: CompressionPlan,
                  n_stack: int) -> Optional[BlockCSR]:
    """``n_stack`` leading axes of ``arr`` are stack axes (scanned layers
    and/or MoE experts); each remaining-slice is compressed separately,
    padded to uniform slot counts and stacked back field-wise."""
    slices = (list(arr.reshape((-1,) + arr.shape[n_stack:])) if n_stack
              else [arr])
    views = [_as_out_in(path, s) for s in slices]
    if views[0] is None or views[0].size < plan.min_size:
        return None
    block = plan.block_for(path)
    ms = [dense_to_bcsr(v, block) for v in views]
    grid = int(np.prod(ms[0].block_grid))
    if min(1.0 - m.n_blocks / max(grid, 1) for m in ms) < plan.min_sparsity:
        return None
    # Zero-slot edge case: an all-zero (fully pruned / fully group-l1'd)
    # slice yields n_blocks == 0 — only the pad slot 0 exists. That is a
    # VALID empty BCSR (gather tables are all-pad, the kernel returns 0),
    # and padding it up alongside non-empty slices is also fine because
    # pad_bcsr only appends zero blocks + pad gather entries. The one
    # hazard is gradient flow to pad slots, which bsr_sddmm masks via
    # slot_coordinates' validity vector.
    n_slots = max(m.data.shape[0] for m in ms)
    mult = _resolve_slot_multiple(plan)
    n_slots = -(-n_slots // mult) * mult     # mesh-divisible slot packing
    jmax = max(m.gather_idx.shape[1] for m in ms)
    jmax_t = max(m.gather_t_idx.shape[1] for m in ms)
    ms = [pad_bcsr(m, n_slots, jmax, jmax_t) for m in ms]
    if ms[0].nbytes >= views[0].size * views[0].dtype.itemsize:
        return None                           # dense fallback: no byte win
    if not n_stack:
        return ms[0]
    out = jax.tree.map(lambda *xs: jnp.stack(xs), *ms)
    if n_stack > 1:                           # e.g. (L, E, ...) MoE stacks
        out = jax.tree.map(
            lambda a: a.reshape(arr.shape[:n_stack] + a.shape[1:]), out)
    return out


def _placeholder(arr, n_stack: int):
    return jnp.zeros(arr.shape[:n_stack], arr.dtype)


def compress_params(params: PyTree,
                    plan: Optional[CompressionPlan] = None) -> CompressedParams:
    """Convert every plan-eligible projection to BlockCSR.

    Returns ``CompressedParams(dense=residue, sparse=bcsr_map, plan=plan)``.
    The residue keeps placeholders where weights were compressed; everything
    else (norms, embeddings, recurrent/MoE params) stays dense.
    """
    plan = plan or CompressionPlan()
    dense = jax.tree.map(lambda x: x, params)
    sparse: dict = {}

    def per_layer(layer, path, stacked, sp_out):
        for sub, names in _LAYER_TARGETS.items():
            if sub not in layer:
                continue
            for name in names:
                if name not in layer[sub]:
                    continue
                arr = np.asarray(layer[sub][name])
                lead = _lead_axes(name, stacked)
                m = _try_compress(arr, f"{path}/{sub}/{name}", plan, lead)
                if m is None:
                    continue
                sp_out.setdefault(sub, {})[name] = m
                layer[sub][name] = _placeholder(arr, lead)

    if "layers" in dense:
        for lkey, layer in dense["layers"].items():
            sp: dict = {}
            per_layer(layer, f"layers/{lkey}", True, sp)
            if sp:
                sparse.setdefault("layers", {})[lkey] = sp
    for lkey, layer in dense.get("rem", {}).items():
        sp = {}
        per_layer(layer, f"rem/{lkey}", False, sp)
        if sp:
            sparse.setdefault("rem", {})[lkey] = sp
    if "head" in dense:
        m = _try_compress(np.asarray(dense["head"]), "head", plan, 0)
        if m is not None:
            sparse["head"] = m
            dense["head"] = _placeholder(np.asarray(dense["head"]), 0)
    cp = CompressedParams(dense=dense, sparse=sparse, plan=plan)
    if plan.quantize_bits or plan.quantize_overrides:
        cp = quantize_compressed(cp)            # emit PaletteBCSR leaves
    return cp


# ---------------------------------------------------------------------------
# Palette quantization (Deep Compression stage 2: BlockCSR -> PaletteBCSR)
# ---------------------------------------------------------------------------

def quantize_bcsr(m: BlockCSR, bits: int, iters: int = 25) -> PaletteBCSR:
    """k-means palette-quantize a BlockCSR's block store (host-side).

    Per layer slice (stacked stores quantize each leading-axis slice — layer
    and, for MoE, each expert — with its own palette): cluster the NONZERO
    block entries to 2**bits - 1 values
    via ``core.quantize.kmeans_palette`` and reserve code 0 for exact zero —
    intra-block zeros, the pad slot 0, and ``pad_bcsr`` padding slots all
    map to code 0 and reproduce bit-exactly, so the sparsity pattern (and
    every index/gather table, shared by reference) is invariant. At 4 bits
    codes are nibble-packed two-per-byte.
    """
    if bits not in (4, 8):
        raise ValueError(f"palette bits must be 4 or 8, got {bits}")
    br, bc = m.block
    if bits == 4 and bc % 2:
        raise ValueError(f"bits=4 nibble packing needs even bc, got {m.block}")
    data = np.asarray(jax.device_get(m.data))
    lead = data.shape[:-3]                      # (L,) layers, (L, E) MoE, ()
    slices = data.reshape((-1,) + data.shape[-3:]) if lead else data[None]
    n_levels = (1 << bits) - 1                  # code 0 is reserved for 0.0
    codes_l, pal_l = [], []
    for sl in slices:
        palette, _, assign = quantize_lib.kmeans_palette(
            jnp.asarray(sl), n_levels, iters=iters)
        codes = np.where(sl.reshape(-1) != 0,
                         np.asarray(assign).astype(np.int64) + 1,
                         0).astype(np.uint8).reshape(sl.shape)
        pal = np.zeros((1 << bits,), np.float32)
        pal[1:] = np.asarray(palette)
        codes_l.append(codes)
        pal_l.append(pal)
    codes = np.stack(codes_l).reshape(data.shape) if lead else codes_l[0]
    pal = (np.stack(pal_l).reshape(lead + (1 << bits,)) if lead
           else pal_l[0])
    codes = jnp.asarray(codes)
    if bits == 4:
        codes = pack_uint4(codes)
    return PaletteBCSR(
        codes=codes, palette=jnp.asarray(pal),
        col_idx=m.col_idx, row_ptr=m.row_ptr,
        gather_idx=m.gather_idx, gather_blk=m.gather_blk,
        gather_nnz=m.gather_nnz,
        gather_t_idx=m.gather_t_idx, gather_t_blk=m.gather_t_blk,
        gather_t_nnz=m.gather_t_nnz,
        shape=m.shape, block=m.block, n_blocks=m.n_blocks, bits=bits)


def quantize_compressed(cp: CompressedParams,
                        bits: Optional[int] = None) -> CompressedParams:
    """Quantize every BlockCSR leaf of a ``CompressedParams`` to
    ``PaletteBCSR`` per the plan's ``bits_for`` (or a blanket ``bits``
    argument, which also updates the stored plan). The LAST pipeline stage:
    run after debias retraining — the quantized form is serving-only.
    Already-quantized leaves pass through unchanged."""
    plan = cp.plan
    if bits is not None:
        plan = dataclasses.replace(plan, quantize_bits=bits)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cp.sparse,
                                                         is_leaf=_is_bcsr)
    leaves = []
    for path, leaf in flat:
        b = plan.bits_for(_path_str(path)) if isinstance(leaf, BlockCSR) \
            else None
        leaves.append(quantize_bcsr(leaf, b) if b else leaf)
    return CompressedParams(dense=cp.dense,
                            sparse=jax.tree_util.tree_unflatten(treedef,
                                                                leaves),
                            plan=plan)


def dequantize_compressed(cp: CompressedParams) -> CompressedParams:
    """Inverse runtime conversion: expand every PaletteBCSR back to an fp
    BlockCSR (values are the palette entries — lossy vs the pre-quantization
    weights, lossless vs the quantized model). Use to resume mask-frozen
    retraining from a quantized checkpoint."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cp.sparse,
                                                         is_leaf=_is_bcsr)
    leaves = [leaf.dequantize() if isinstance(leaf, PaletteBCSR) else leaf
              for _, leaf in flat]
    return CompressedParams(dense=cp.dense,
                            sparse=jax.tree_util.tree_unflatten(treedef,
                                                                leaves),
                            plan=cp.plan)


# ---------------------------------------------------------------------------
# Plan-aligned training prox (SpC-Retrain: train *into* the BCSR grid)
# ---------------------------------------------------------------------------

_ATTN_QKV = ("wq", "wk", "wv")


def _norm_keystr(path: str) -> str:
    """jax keystr "['layers']['b0_attn']['mlp']['wi']" -> "layers/b0_attn/mlp/wi"
    (the path format ``CompressionPlan.block_for`` and this module use)."""
    parts = re.findall(r"\['([^']+)'\]", path)
    return "/".join(parts) if parts else path.strip("/").lstrip(".")


def make_plan_prox(plan: CompressionPlan) -> Callable:
    """Path-aware block group-l1 prox on the SAME (out, in) grid
    ``compress_params`` tiles.

    The optimizer's prox sees weights in their *stored* layouts (stacked
    (L, d, ff) MLPs, (L, d, h, hd) attention, ...) while the BCSR grid lives
    on the 2D (out, in) view. Block partitions map through transpose, so
    shrinking (bc, br) tiles of the flattened (in, out) view is exactly the
    plan's (br, bc) group-l1 on (out, in): whole blocks of the serving grid
    hit exact zero during training and ``compress_params`` then needs no
    prune step. Non-plan-eligible leaves (embeddings, leaves under
    ``min_size``, ...) are left UNTOUCHED: the group-l1 lambda is calibrated
    against block norms (~sqrt(block_size) larger than element magnitudes),
    so an elementwise-l1 fallback at the same lambda would annihilate e.g. a
    tied embedding/head in one step.

    Returned callable has signature ``prox_fn(z, tau, path="")`` — the
    ``path`` keyword is how ``ProxOptimizer`` detects path-awareness.
    """

    def prox_fn(z, tau, path: str = ""):
        p = _norm_keystr(path)
        leaf = p.rsplit("/", 1)[-1]
        stacked = p.startswith("layers/")
        nd = z.ndim - (1 if stacked else 0)     # per-layer rank
        wrapped = f"/{p}/"

        def _in(sub, rank) -> bool:
            return (f"/{sub}/" in wrapped and leaf in _LAYER_TARGETS[sub]
                    and nd == rank)

        eligible = (
            ("/attn/" in wrapped and leaf in _LAYER_TARGETS["attn"]
             and nd in (2, 3))
            or _in("mlp", 2)
            or _in("moe", 3)                    # per-expert (E, in, out)
            or _in("tm", 2) or _in("cm", 2) or _in("rec", 2)
            or (leaf == "head" and nd == 2))
        if not eligible:
            return z
        br, bc = plan.block_for(p)

        def prox2d(flat):
            if flat.size < plan.min_size:
                return flat
            # (in, out) view with transposed tiles == plan grid on (out, in)
            return prox_lib.prox_group_l1_blocks(flat, tau, block=(bc, br))

        def one(zi):
            shp = zi.shape
            if leaf in _PER_EXPERT:                    # (E, in, out) stack
                return jax.vmap(prox2d)(zi)
            if zi.ndim == 3 and leaf in _ATTN_QKV:     # (d, h, hd): in, out
                flat = zi.reshape(shp[0], -1)
            elif zi.ndim == 3:                         # attn wo (h, hd, d)
                flat = zi.reshape(-1, shp[-1])
            else:                                      # 2D stored (in, out)
                flat = zi
            return prox2d(flat).reshape(shp)

        return jax.vmap(one)(z) if stacked else one(z)

    return prox_fn


# ---------------------------------------------------------------------------
# Mask-frozen retraining from a compressed model (paper §2.4 debias)
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def split_trainable(cp: CompressedParams):
    """Split a ``CompressedParams`` into (trainable pytree, rebuild fn).

    ``trainable = {"dense": residue, "bcsr_data": {path: BlockCSR.data}}``
    contains only float arrays, so it can be handed straight to
    ``jax.value_and_grad`` / a ``ProxOptimizer``; ``rebuild(trainable)``
    plants the (possibly updated) data blocks back into the BlockCSR
    structures. Index/gather tables are closure constants: retraining *from*
    a compressed checkpoint updates only resident block data (+ the dense
    residue) — the sparsity pattern is frozen by construction, and
    ``masks.zero_mask(trainable)`` additionally freezes intra-block zeros
    and pad slots.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(cp.sparse,
                                                         is_leaf=_is_bcsr)
    for path, leaf in flat:
        if isinstance(leaf, PaletteBCSR):
            raise TypeError(
                f"split_trainable got a PaletteBCSR at {_path_str(path)}: "
                "quantized weights are serving-only — debias before "
                "quantize_compressed(), or dequantize_compressed() first")
    data = {_path_str(path): leaf.data for path, leaf in flat}
    trainable = {"dense": cp.dense, "bcsr_data": data}
    plan = cp.plan
    # keep only the index/gather structure in the closure (zero-size data
    # slice): rebuild always overwrites data, and retaining the original
    # blocks would pin a second full copy of the compressed weights for the
    # whole debias phase
    structs = [(path, dataclasses.replace(leaf, data=leaf.data[:0]))
               for path, leaf in flat]

    def rebuild(tr) -> CompressedParams:
        leaves = [dataclasses.replace(leaf, data=tr["bcsr_data"][_path_str(p)])
                  for p, leaf in structs]
        sparse = jax.tree_util.tree_unflatten(treedef, leaves)
        return CompressedParams(dense=tr["dense"], sparse=sparse, plan=plan)

    return trainable, rebuild


def densify_compressed(cp: CompressedParams, like: PyTree) -> PyTree:
    """Inverse of ``compress_params``: scatter BCSR blocks back into a dense
    param tree shaped like ``like`` (the mask-frozen dense reference used to
    validate debiased compressed logits; host-side, test/debug only).

    Values come from ``cp`` — the residue from ``cp.dense`` and the
    compressed projections from the BCSR blocks; ``like`` only supplies the
    stored shapes that the zero-size placeholders erased."""
    def merge(l, d):
        da = np.asarray(d)
        if da.shape != np.shape(np.asarray(l)):      # placeholder: use like
            return np.asarray(l).copy()
        return da.copy()

    out = jax.tree.map(merge, like, cp.dense)

    def to_stored(sl, path: str, orig_shape):
        mat = np.asarray(sl.to_dense())[:sl.shape[0], :sl.shape[1]]
        return _from_out_in(path, mat, orig_shape)

    for name, m in iter_bcsr(cp):
        keys = name.split("/")
        tgt = out
        for k in keys[:-1]:
            tgt = tgt[k]
        ref = np.asarray(tgt[keys[-1]])
        store = m.codes if isinstance(m, PaletteBCSR) else m.data
        lead = store.ndim - 3                   # layer and/or expert axes
        if lead:
            mats = [to_stored(jax.tree.map(lambda a, i=i: a[i], m),
                              name, ref.shape[lead:])
                    for i in np.ndindex(*store.shape[:lead])]
            tgt[keys[-1]] = np.stack(mats).reshape(ref.shape) \
                .astype(ref.dtype)
        else:
            tgt[keys[-1]] = to_stored(m, name, ref.shape).astype(ref.dtype)
    return jax.tree.map(jnp.asarray, out)


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def iter_bcsr(cp: CompressedParams):
    """Yield (path, BlockCSR) over the sparse map."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cp.sparse, is_leaf=_is_bcsr)
    for path, leaf in flat:
        if _is_bcsr(leaf):
            yield _path_str(path), leaf


def compressed_size_bytes(cp: CompressedParams) -> int:
    """Actual serving bytes: dense residue + real BCSR storage (data +
    col_idx + row_ptr), not a hypothetical CSR table."""
    total = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(cp.dense))
    total += sum(m.nbytes for _, m in iter_bcsr(cp))
    return int(total)


def bcsr_equiv_size_bytes(cp: CompressedParams) -> int:
    """What ``compressed_size_bytes`` would report with every palette leaf
    expanded back to fp32 BlockCSR — the stage-1 baseline the quantized
    total is compared against (docs/size_accounting.md)."""
    total = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(cp.dense))
    for _, m in iter_bcsr(cp):
        total += m.bcsr_equiv_nbytes if isinstance(m, PaletteBCSR) \
            else m.nbytes
    return int(total)


def format_size_report(dense_bytes: int, bcsr_bytes: int,
                       palette_bytes: Optional[int] = None) -> str:
    """One-line dense-vs-compressed byte report (shared by serve/train CLIs).

    ``bcsr_bytes`` is the fp BlockCSR total; pass ``palette_bytes`` after
    quantization to also report the stage-2 (palette) total and ratio.
    See docs/size_accounting.md for how each term is computed."""
    line = (f"model size dense={dense_bytes/2**20:.2f}MB "
            f"bcsr={bcsr_bytes/2**20:.2f}MB "
            f"({dense_bytes/max(bcsr_bytes, 1):.1f}x)")
    if palette_bytes is not None:
        line += (f" palette={palette_bytes/2**20:.2f}MB "
                 f"({dense_bytes/max(palette_bytes, 1):.1f}x)")
    return line


def compression_summary(cp: CompressedParams) -> str:
    """Per-layer breakdown: format, block occupancy and actual stored bytes
    per compressed matrix, plus a dense-residue / total footer. This is the
    table ``launch/serve --sparse`` prints; docs/size_accounting.md documents
    every column."""
    lines = [f"{'weight':44s} {'(out, in)':>14s} {'block':>10s} "
             f"{'fmt':>6s} {'blocks':>14s} {'bytes':>10s}"]
    sparse_total = 0
    for name, m in iter_bcsr(cp):
        grid = int(np.prod(m.block_grid))
        store = m.codes if isinstance(m, PaletteBCSR) else m.data
        lead = store.ndim - 3                   # layer and/or expert axes
        n = int(np.prod(store.shape[:lead])) if lead else 1
        fmt = f"pal{m.bits}" if isinstance(m, PaletteBCSR) else "bcsr"
        sparse_total += m.nbytes
        lines.append(
            f"{name:44s} {str(m.shape):>14s} {str(m.block):>10s} "
            f"{fmt:>6s} {m.n_blocks:>6d}/{grid:<7d} {m.nbytes:>10d}"
            + (f"  x{n} slices" if lead else ""))
    dense_residue = sum(int(l.size) * l.dtype.itemsize
                        for l in jax.tree.leaves(cp.dense))
    lines.append(f"{'dense residue (embeddings/norms/fallback)':92s} "
                 f"{dense_residue:>10d}")
    lines.append(f"{'total serving bytes':92s} "
                 f"{sparse_total + dense_residue:>10d}")
    return "\n".join(lines)
