"""Fault-tolerant checkpointing.

Properties required by the 1000+-node story (DESIGN.md §5):

* **Atomic**: writes go to ``step_NNN.tmp-<nonce>`` then ``os.replace`` into
  place — a preempted writer never corrupts the latest checkpoint.
* **Versioned + self-describing**: a manifest (JSON) stores the tree
  structure, shapes, dtypes and the *logical* sharding axes — never device
  layouts — so a checkpoint written on a (16,16) mesh restores onto (2,16,16)
  or any other mesh (**elastic re-shard**: restore is just pjit-placing the
  host arrays with the new mesh's shardings).
* **Compressed sparse storage**: regularized *dense* weight matrices whose
  sparsity exceeds a threshold are stored as elementwise CSR (one-way:
  densified on restore), cutting checkpoint bytes by the paper's compression
  factor — the paper's 'model size' win applied to the training artifact.
  Native **BlockCSR and PaletteBCSR leaves** (e.g. inside a
  ``CompressedParams`` serving tree) round-trip losslessly: their arrays +
  metas are stored verbatim and restore rebuilds the format without
  densifying (quantized stores stay uint8/nibble-packed on disk and at
  load), so a compressed checkpoint restores straight into the
  compressed-model runtime.
* **Retention + resume**: keep_n newest checkpoints; ``latest_step`` scans
  the directory so a restarted job resumes from the newest complete write.

Arrays move through numpy .npz (offline-friendly; no external deps).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.core.prox import default_regularized_predicate
from repro.sparse.formats import BlockCSR, PaletteBCSR, dense_to_csr

PyTree = Any
_SPARSE_THRESHOLD = 0.7      # store CSR when >= 70% zero

# BlockCSR / PaletteBCSR array fields persisted verbatim for the round-trip
# path (index/gather tables are shared between the two formats)
_INDEX_FIELDS = ("col_idx", "row_ptr",
                 "gather_idx", "gather_blk", "gather_nnz",
                 "gather_t_idx", "gather_t_blk", "gather_t_nnz")
_BCSR_FIELDS = ("data",) + _INDEX_FIELDS
_PBCSR_FIELDS = ("codes", "palette") + _INDEX_FIELDS
# 4-bit codes' nibble packing (formats.pack_uint4): "split" puts codes[k] and
# codes[k + n/2] in byte k. Entries without the marker were written with the
# older interleaved packing (codes[2k], codes[2k+1]) and are repacked on load.
_NIBBLES = "split"


def _is_bcsr(x) -> bool:
    return isinstance(x, (BlockCSR, PaletteBCSR))


def _key_name(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _flatten(tree: PyTree):
    """Flatten with BlockCSR treated as a single (compound) leaf, so
    compressed trees (e.g. ``CompressedParams``) round-trip losslessly."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=_is_bcsr)
    names = ["/".join(_key_name(k) for k in path) for path, _ in flat]
    return names, [l for _, l in flat], treedef


class Checkpointer:
    def __init__(self, directory: str, keep_n: int = 3,
                 sparse_storage: bool = True):
        self.dir = directory
        self.keep_n = keep_n
        self.sparse_storage = sparse_storage
        os.makedirs(directory, exist_ok=True)

    # -- write ---------------------------------------------------------------

    def save(self, step: int, tree: PyTree, extra: Optional[dict] = None):
        names, leaves, _ = _flatten(tree)
        arrays, manifest = {}, {"step": step, "time": time.time(),
                                "extra": extra or {}, "leaves": []}
        for name, leaf in zip(names, leaves):
            if _is_bcsr(leaf):
                # native compressed leaf: store the BCSR/PaletteBCSR arrays
                # verbatim — restore rebuilds the format without densifying
                # (quantized checkpoints stay quantized on disk AND at load)
                quant = isinstance(leaf, PaletteBCSR)
                fields = _PBCSR_FIELDS if quant else _BCSR_FIELDS
                entry = {"name": name,
                         "format": "palette_bcsr" if quant else "bcsr",
                         "shape": list(leaf.shape),
                         "block": list(leaf.block),
                         "n_blocks": int(leaf.n_blocks),
                         "dtype": str(np.asarray(
                             leaf.palette if quant else leaf.data).dtype)}
                if quant:
                    entry["bits"] = int(leaf.bits)
                    if leaf.bits == 4:
                        entry["nibbles"] = _NIBBLES
                for f in fields:
                    arrays[f"{name}__{f}"] = np.asarray(
                        jax.device_get(getattr(leaf, f)))
                manifest["leaves"].append(entry)
                continue
            arr = np.asarray(jax.device_get(leaf))
            entry = {"name": name, "shape": list(arr.shape),
                     "dtype": str(arr.dtype), "format": "dense"}
            if (self.sparse_storage and arr.ndim == 2
                    and default_regularized_predicate(name, arr)
                    and arr.size > 4096):
                sparsity = 1.0 - np.count_nonzero(arr) / arr.size
                if sparsity >= _SPARSE_THRESHOLD:
                    # storage format is elementwise CSR (the paper's own;
                    # BCSR is the *compute* format — unstructured sparsity
                    # does not compress under MXU-sized blocks)
                    c = dense_to_csr(arr)
                    entry["format"] = "csr"
                    arrays[f"{name}__data"] = np.asarray(c.data)
                    arrays[f"{name}__indices"] = np.asarray(c.indices)
                    arrays[f"{name}__indptr"] = np.asarray(c.indptr)
                    manifest["leaves"].append(entry)
                    continue
            arrays[name] = arr
            manifest["leaves"].append(entry)

        final = os.path.join(self.dir, f"step_{step:09d}")
        tmp = tempfile.mkdtemp(prefix=f".tmp_step_{step}_", dir=self.dir)
        try:
            np.savez(os.path.join(tmp, "arrays.npz"),
                     **{k.replace("/", "|"): v for k, v in arrays.items()})
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)          # atomic publish
        finally:
            if os.path.exists(tmp):
                shutil.rmtree(tmp, ignore_errors=True)
        self._gc()
        return final

    def _gc(self):
        steps = sorted(self.all_steps())
        for s in steps[:-self.keep_n] if self.keep_n else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:09d}"),
                          ignore_errors=True)

    # -- read ----------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and not d.startswith(".tmp"):
                if os.path.exists(os.path.join(self.dir, d, "manifest.json")):
                    out.append(int(d.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, like: PyTree,
                shardings: Optional[PyTree] = None) -> PyTree:
        """Restore into the structure of ``like``; optionally device_put with
        ``shardings`` (a pytree of NamedSharding for the *current* mesh —
        elastic restore onto any mesh)."""
        path = os.path.join(self.dir, f"step_{step:09d}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        npz = np.load(os.path.join(path, "arrays.npz"))
        by_name = {e["name"]: e for e in manifest["leaves"]}

        names, leaves, treedef = _flatten(like)
        out = []
        for name, leaf in zip(names, leaves):
            e = by_name[name]
            if e["format"] == "bcsr":
                out.append(_bcsr_restore(npz, name, e))
                continue
            if e["format"] == "palette_bcsr":
                out.append(_pbcsr_restore(npz, name, e))
                continue
            if e["format"] == "csr":
                arr = _csr_restore(npz, name, tuple(e["shape"]),
                                   np.dtype(e["dtype"]))
            else:
                arr = npz[name.replace("/", "|")]
            assert list(arr.shape) == e["shape"], (name, arr.shape, e["shape"])
            out.append(arr)
        tree = jax.tree_util.tree_unflatten(treedef, out)
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, s: jax.device_put(a, s), tree, shardings)
        return tree

    def manifest(self, step: int) -> dict:
        with open(os.path.join(self.dir, f"step_{step:09d}",
                               "manifest.json")) as f:
            return json.load(f)

    def restore_compressed(self, step: Optional[int] = None, mesh=None):
        """Template-free restore of a ``CompressedParams`` checkpoint.

        The manifest's leaf names ("dense/..." / "sparse/...") carry the
        full tree structure and the ``extra['plan']`` entry the
        ``CompressionPlan``, so a server can load a compressed model written
        by ``launch/train --sparse`` without re-deriving a template from the
        architecture (the sparsity pattern lives in the checkpoint, not the
        code). BlockCSR leaves rebuild without densifying.

        ``mesh``: optional ``jax.sharding.Mesh`` — the restored tree is
        device_put with ``distributed.sharding.param_shardings`` (block
        stores row-sharded along the slot axis, index/gather tables and
        palettes replicated). Elastic like the dense restore path: the
        checkpoint stores host arrays, so any mesh shape works.
        """
        from repro.sparse.compress import CompressedParams, CompressionPlan

        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        path = os.path.join(self.dir, f"step_{step:09d}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        npz = np.load(os.path.join(path, "arrays.npz"))

        import jax.numpy as jnp
        roots = {"dense": {}, "sparse": {}}
        for e in manifest["leaves"]:
            name = e["name"]
            root, _, rest = name.partition("/")
            if root not in roots or not rest:
                raise ValueError(
                    f"step {step} in {self.dir} is not a CompressedParams "
                    f"checkpoint (leaf {name!r}; was it written by "
                    f"launch/train --sparse?)")
            if e["format"] == "bcsr":
                leaf = _bcsr_restore(npz, name, e)
            elif e["format"] == "palette_bcsr":
                leaf = _pbcsr_restore(npz, name, e)
            elif e["format"] == "csr":
                leaf = jnp.asarray(_csr_restore(npz, name, tuple(e["shape"]),
                                                np.dtype(e["dtype"])))
            else:
                leaf = jnp.asarray(npz[name.replace("/", "|")])
            node = roots[root]
            keys = rest.split("/")
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = leaf

        spec = (manifest.get("extra") or {}).get("plan")
        plan = CompressionPlan()
        if spec:
            # .get defaults keep checkpoints written before the quantization
            # fields existed loadable
            plan = CompressionPlan(
                block=tuple(spec["block"]),
                min_sparsity=spec["min_sparsity"],
                min_size=spec["min_size"],
                overrides=tuple((s, tuple(b)) for s, b in spec["overrides"]),
                quantize_bits=spec.get("quantize_bits"),
                quantize_overrides=tuple(
                    (s, int(b))
                    for s, b in spec.get("quantize_overrides", ())),
                slot_multiple=spec.get("slot_multiple"))
        cp = CompressedParams(dense=roots["dense"], sparse=roots["sparse"],
                              plan=plan)
        if mesh is not None:
            from repro.distributed.sharding import param_shardings
            cp = jax.device_put(cp, param_shardings(cp, mesh))
        return cp


def _bcsr_restore(npz, name, entry) -> BlockCSR:
    """Rebuild a BlockCSR leaf from its stored arrays — no densification.

    The sparsity pattern (and therefore the array shapes) come from the
    checkpoint, not from the ``like`` template: a compressed checkpoint
    restores bit-exactly even when the template was compressed from
    different weights."""
    import jax.numpy as jnp
    arrs = {f: jnp.asarray(npz[f"{name}__{f}".replace("/", "|")])
            for f in _BCSR_FIELDS}
    return BlockCSR(shape=tuple(entry["shape"]), block=tuple(entry["block"]),
                    n_blocks=int(entry["n_blocks"]), **arrs)


def _pbcsr_restore(npz, name, entry) -> PaletteBCSR:
    """Rebuild a PaletteBCSR leaf from its stored arrays — codes stay
    quantized (and nibble-packed at 4 bits) from disk into serving memory."""
    import jax.numpy as jnp
    arrs = {f: npz[f"{name}__{f}".replace("/", "|")] for f in _PBCSR_FIELDS}
    if int(entry["bits"]) == 4:
        nibbles = entry.get("nibbles", "interleaved")
        if nibbles == "interleaved":
            arrs["codes"] = _split_nibbles(arrs["codes"])
        elif nibbles != _NIBBLES:
            raise ValueError(f"{name}: unknown 4-bit nibble packing "
                             f"{nibbles!r}")
    arrs = {f: jnp.asarray(a) for f, a in arrs.items()}
    return PaletteBCSR(shape=tuple(entry["shape"]),
                       block=tuple(entry["block"]),
                       n_blocks=int(entry["n_blocks"]),
                       bits=int(entry["bits"]), **arrs)


def _split_nibbles(packed: np.ndarray) -> np.ndarray:
    """Repack interleaved 4-bit codes (byte k = codes[2k] | codes[2k+1] << 4)
    into the split packing of ``formats.pack_uint4``."""
    codes = np.stack([packed & 0xF, packed >> 4], axis=-1).reshape(
        *packed.shape[:-1], 2 * packed.shape[-1])
    half = packed.shape[-1]
    return (codes[..., :half] | (codes[..., half:] << 4)).astype(np.uint8)


def _csr_restore(npz, name, shape, dtype):
    data = npz[f"{name}__data".replace("/", "|")]
    indices = npz[f"{name}__indices".replace("/", "|")]
    indptr = npz[f"{name}__indptr".replace("/", "|")]
    dense = np.zeros(shape, dtype)
    rows = np.repeat(np.arange(shape[0]), indptr[1:] - indptr[:-1])
    dense[rows, indices] = data
    return dense
