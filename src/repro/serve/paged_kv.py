"""Slot resource pools for the continuous-batching serving engine.

The one-ring-per-batch cache (``Model.init_cache``) allocates a dense
``(batch, seq_len, ...)`` buffer per layer: every request pays for the
longest request's context, and a finished request's memory can't be reused
without reallocating (= recompiling) the whole batch. The engine instead
gives every request slot a **slot resource pool** per layer, of which there
are two kinds, keyed by the layer kind:

* **Block-paged KV** (``attn`` layers): a pool of head-major
  ``(n_pages, kv_heads, page_size, head_dim)`` K and V pages shared by
  every request slot, mapped onto each request's logical context through a
  per-slot **page table** ``(capacity, max_pages)``: logical page ``p`` of
  a slot covers absolute positions ``[p*page_size, (p+1)*page_size)``.
  Int8-KV configs store int8 pages plus per-(page, head, offset) f32
  scales (``attention.init_paged_kv``).
* **Slot-indexed recurrent state** (``rglru``/``rwkv`` layers): fixed-size
  state arrays with a leading ``capacity`` axis — slot ``i``'s state lives
  at index ``i``. No paging: recurrent state is O(1) per slot regardless
  of context length, so these slots need no admission-time reservation.

Both kinds coexist in one pool tree for hybrid block patterns (e.g.
recurrentgemma's 2:1 RG-LRU:attention pattern), mirroring
``Model.init_cache``'s structure (scanned layers stacked over ``n_super``,
unrolled remainder under ``rem``) so the tree rides through the same
layer-stack ``lax.scan``.

Page allocation is host-side (a free list — pages are ints, allocation
never enters the jitted step); the jitted step only consumes the page
table, so admitting, finishing, and recycling requests changes *data*,
never shapes: no recompiles as traffic churns. Page 0 is reserved as the
trash page — masked-out token writes land there, and unallocated
page-table entries point at it (their reads are masked by the
causal-by-absolute-position mask in ``models.attention.paged_attention``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, rglru, rwkv6
from repro.models.transformer import Model
from repro.obs.metrics import NULL_REGISTRY

# pool-subtree keys holding slot-indexed recurrent state (vs "attn" pages)
_STATE_KEYS = ("rec", "tm", "cm")


def pages_for(n_tokens: int, page_size: int) -> int:
    """Number of pages covering a context of ``n_tokens`` positions."""
    return -(-int(n_tokens) // int(page_size))


def unsupported_kinds(model: Model) -> list[str]:
    """Layer kinds in the model outside the engine's pool coverage."""
    cfg = model.cfg
    kinds = tuple(cfg.block_pattern) + tuple(cfg.remainder_pattern)
    return sorted({k for k in kinds if k not in ("attn", "rglru", "rwkv")})


def _layer_pools(cfg, kind: str, n_pages: int, page_size: int, dtype,
                 capacity: int) -> dict:
    if kind == "attn":
        return {"attn": attention.init_paged_kv(cfg, n_pages, page_size,
                                                dtype)}
    if kind == "rglru":
        return {"rec": rglru.init_rglru_state(cfg, capacity, dtype)}
    if kind == "rwkv":
        st = rwkv6.init_rwkv_state(cfg, capacity)
        return {"tm": st["tm"], "cm": st["cm"]}
    raise NotImplementedError(
        f"layer kind {kind!r} has no slot resource pool — the engine "
        "covers attn/rglru/rwkv; use the sequential serving path "
        "(launch/serve without --engine)")


def init_paged_cache(model: Model, n_pages: int, page_size: int,
                     dtype=None, *, capacity: int = 1):
    """Slot resource pool pytree for any engine-served model.

    Mirrors ``Model.init_cache``'s tree (``{"layers": stacked, "rem": ...}``)
    with each attention layer's ring buffer replaced by a
    ``(n_pages, kv, page_size, hd)`` page pool and each recurrent layer's
    state replaced by a ``capacity``-slot state pool. One page table
    indexes every attention layer's pool identically (all layers cache the
    same positions), so the engine allocates pages once per request, not
    per layer. ``capacity`` is the engine's slot-batch size (the leading
    axis of every state-pool leaf).
    """
    cfg = model.cfg
    if model.paged_step is None:
        bad = unsupported_kinds(model)
        raise NotImplementedError(
            f"{cfg.name}: layer kind(s) {', '.join(map(repr, bad))} have no "
            "slot resource pool — the engine covers attn/rglru/rwkv; use "
            "the sequential serving path (launch/serve without --engine)")
    dtype = dtype or jnp.dtype(cfg.compute_dtype)

    def one_super():
        return {f"b{i}_{kind}": _layer_pools(cfg, kind, n_pages, page_size,
                                             dtype, capacity)
                for i, kind in enumerate(cfg.block_pattern)}

    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_super_blocks,) + x.shape).copy(),
        one_super())
    pools = {"layers": stacked}
    rem = cfg.remainder_pattern
    if rem:
        pools["rem"] = {f"r{i}_{kind}": _layer_pools(
                            cfg, kind, n_pages, page_size, dtype, capacity)
                        for i, kind in enumerate(rem)}
    return pools


def paged_cache_bytes(pools) -> int:
    """Total bytes of the slot resource pools (all layers, both kinds)."""
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(pools))


def slot_resource_bytes(pools) -> dict:
    """Byte split of the pool tree by resource kind.

    Returns ``{"kv_page_bytes": ..., "state_slot_bytes": ...}`` — paged KV
    pools (the ``"attn"`` subtrees, scales included) vs slot-indexed
    recurrent state pools (the ``"rec"``/``"tm"``/``"cm"`` subtrees). The
    two sum to ``paged_cache_bytes(pools)``.
    """
    split = {"kv_page_bytes": 0, "state_slot_bytes": 0}
    for group in ("layers", "rem"):
        for layer in (pools.get(group) or {}).values():
            for key, sub in layer.items():
                kind = "kv_page_bytes" if key == "attn" else "state_slot_bytes"
                split[kind] += sum(int(x.size) * x.dtype.itemsize
                                   for x in jax.tree.leaves(sub))
    return split


def zero_state_slots(pools, mask):
    """Zero the recurrent state of the slots selected by ``mask``.

    mask: (capacity,) bool. Touches only the state-pool subtrees
    (``rec``/``tm``/``cm``) — paged-KV pages are recycled through the page
    allocator instead. Slot hygiene on recycle: a finished request's state
    must not be readable by the slot's next occupant. (The in-step reset in
    ``transformer._apply_layer_paged`` re-zeroes on first prefill chunk
    regardless — this keeps the pool clean between occupants.)

    In the stacked ``"layers"`` group the slot axis is axis 1 (leaves are
    ``(n_super, capacity, ...)``); in ``"rem"`` it is axis 0.
    """
    mask = jnp.asarray(mask)

    def zero_group(group, lead):
        def zero_leaf(l):
            shape = (1,) * lead + (-1,) + (1,) * (l.ndim - lead - 1)
            return jnp.where(mask.reshape(shape), jnp.zeros_like(l), l)

        return {key: (jax.tree.map(zero_leaf, sub)
                      if key in _STATE_KEYS else sub)
                for key, sub in group.items()}

    out = {"layers": {name: zero_group(layer, 1)
                      for name, layer in pools["layers"].items()}}
    if "rem" in pools:
        out["rem"] = {name: zero_group(layer, 0)
                      for name, layer in pools["rem"].items()}
    return out


def copy_page(pools, src, dst):
    """Copy page ``src`` onto page ``dst`` in every attention pool leaf
    (K, V, and int8 scales) — the copy-on-write step behind prefix-cache
    hits that end mid-page: the new request maps the cached full pages
    read-only and gets a private copy of the partially-matching boundary
    page, which its own prefill then overwrites from the divergence point.
    ``src``/``dst`` are scalars, so this compiles exactly once regardless
    of which pages are copied.

    In the stacked ``"layers"`` group the page axis is axis 1 (leaves are
    ``(n_super, n_pages, kv, page_size, ...)``); in ``"rem"`` it is axis 0.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cp_group(group, lead):
        def cp_leaf(l):
            if lead == 0:
                return l.at[dst].set(l[src])
            return l.at[:, dst].set(l[:, src])

        return {key: (jax.tree.map(cp_leaf, sub) if key == "attn" else sub)
                for key, sub in group.items()}

    out = {"layers": {name: cp_group(layer, 1)
                      for name, layer in pools["layers"].items()}}
    if "rem" in pools:
        out["rem"] = {name: cp_group(layer, 0)
                      for name, layer in pools["rem"].items()}
    return out


class PageAllocator:
    """Host-side refcounted free-list page allocator. Page 0 is reserved
    (trash page) and can never be allocated, shared, or freed.

    ``alloc(n)`` pops ``n`` page ids (lowest-numbered first — keeps page
    tables dense and reproducible) at refcount 1, or raises ``MemoryError``
    without allocating anything. Pages are shared by ``incref`` (the prefix
    cache maps one physical page into many requests' page tables — and
    holds its own reference so cached pages survive their writer) and
    released by ``free``/``decref``: a page returns to the free list only
    when its last owner lets go. ``refcount`` is the test suite's invariant
    hook: at every tick it must equal the number of distinct owners (slot
    page tables + radix-tree nodes + in-flight COW sources).
    """

    def __init__(self, n_pages: int, metrics=NULL_REGISTRY):
        if n_pages < 2:
            raise ValueError(f"need >= 2 pages (page 0 is reserved), "
                             f"got {n_pages}")
        self.n_pages = int(n_pages)
        # descending so .pop() hands out the lowest id first
        self._free = list(range(self.n_pages - 1, 0, -1))
        self._rc: dict[int, int] = {}      # page -> refcount (allocated only)
        # occupancy + free-list churn instruments (obs/metrics.py)
        self._m_in_use = metrics.gauge(
            "repro_pages_in_use", "KV pages currently allocated")
        self._m_free = metrics.gauge(
            "repro_pages_free", "KV pages on the free list")
        self._m_allocs = metrics.counter(
            "repro_page_allocs_total", "pages handed out by alloc()")
        self._m_frees = metrics.counter(
            "repro_page_frees_total", "pages returned to the free list")
        self._m_free.set(len(self._free))
        self._m_in_use.set(0)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def _sync_gauges(self) -> None:
        self._m_free.set(len(self._free))
        self._m_in_use.set(self.n_pages - 1 - len(self._free))

    def refcount(self, page: int) -> int:
        return self._rc.get(int(page), 0)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(f"requested {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        if pages:
            self._m_allocs.inc(len(pages))
            self._sync_gauges()
        return pages

    def incref(self, page: int) -> None:
        p = int(page)
        assert p in self._rc, f"incref on unallocated page {p}"
        self._rc[p] += 1

    def free(self, pages) -> None:
        """Drop one reference per page; last owner returns it to the free
        list. Freeing an unallocated (or trash) page is a hard error — the
        double-free invariant the stress suite leans on."""
        returned = 0
        for p in pages:
            p = int(p)
            assert 0 < p < self.n_pages, p
            rc = self._rc.get(p)
            assert rc is not None, f"double free of page {p}"
            if rc == 1:
                del self._rc[p]
                self._free.append(p)
                returned += 1
            else:
                self._rc[p] = rc - 1
        if returned:
            self._m_frees.inc(returned)
            self._sync_gauges()

    decref = free
