"""Continuous-batching serving engine over compressed (or dense) weights.

``ServeEngine`` serves many concurrent, mixed-length requests from a single
fixed-capacity slot batch: each tick runs **one jitted mixed step**
(``Model.paged_step``) over all slots — any mix of prefill chunks and
single-token decodes, inactive slots masked by ``n_tokens == 0`` — then a
pluggable sampler, then host-side bookkeeping (admission, streaming
callbacks, slot recycling). ``params`` may be a raw tree or
``CompressedParams`` (BlockCSR / PaletteBCSR, sharded or not): the mixed
step dispatches the same ``sparse_matmul`` kernels as the sequential
serving path, so the engine is compression- and sharding-transparent.

Because the scheduler emits at most three tick widths (1,
``prefill_chunk`` and the optional ``first_chunk`` jumbo width), the step
compiles at most three times and then never again — request churn only
changes array *contents*. Per-request memory lives in the slot resource
pools of ``serve/paged_kv.py`` — block-paged KV for attention layers
(int8 pages + scales for int8-KV configs), slot-indexed recurrent state
for RWKV / RG-LRU layers, coexisting in one tree for hybrids — and pools
are donated back to the step each tick, so they update in place where the
backend supports donation. Recurrent slots are admission-free (pages are
reserved only when the model has attention layers), and a recycled slot's
recurrent state is zeroed before its next occupant. Attention inside the
step dispatches by ``EngineConfig.attn_backend``: the 'pallas' backend
walks page tables with the fused flash-decode kernel
(``kernels/paged_attention``) instead of gathering the whole pool.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import Model
from repro.obs.metrics import MetricsRegistry, slo_summary
from repro.obs.trace import NULL_TRACER
from repro.serve import api
from repro.serve.api import ApiValidationError, Completion, SamplingParams
from repro.serve.paged_kv import (PageAllocator, copy_page, init_paged_cache,
                                  pages_for, slot_resource_bytes,
                                  unsupported_kinds, zero_state_slots)
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import Scheduler
from repro.serve.scheduler import Request as _SchedRequest
from repro.serve.step import make_sampler


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs of the continuous-batching engine.

    max_batch:     fixed slot capacity of the jitted mixed step.
    prefill_chunk: prompt tokens consumed per slot per tick (long prompts
                   prefill across many ticks, interleaved with decode).
    page_size:     KV page length in tokens.
    max_seq_len:   per-request context cap (prompt + generated) — sets the
                   page-table width.
    n_pages:       total pages per layer pool; default sizes every slot for
                   ``max_seq_len`` (+1 for the reserved trash page 0).
    token_budget:  max tokens scheduled per tick (decode first, remainder
                   to prefill chunks); default ``max_batch + first_chunk``
                   (or ``+ prefill_chunk`` when no jumbo width is set).
    first_chunk:   optional jumbo width (> prefill_chunk) for the FIRST
                   chunk of a long prompt — a third compiled tick width
                   that keeps TTFT off the steady-state chunk pace.
    attn_backend:  paged-attention kernel dispatch: 'pallas' = fused
                   page-gather flash-decode kernel, 'ref' = jnp gather
                   oracle, 'auto' (default) = pallas on TPU, ref elsewhere.
    kv_splits:     flash-decode KV-split lanes per slot on the pallas
                   backend (1 = no split).
    prefix_cache:  radix-tree prefix caching: requests sharing a prompt
                   prefix share physical KV pages (refcounted, COW on the
                   first diverging page) — attention-layer models only
                   (recurrent state is not position-sliceable).
    class_shares:  optional ((class, weight), ...) pairs overriding the
                   per-priority-class prefill token-budget shares
                   (default: class c weighs 2^-c).
    sampling:      engine-wide ``SamplingParams`` — the sampler is part of
                   the compiled step, so it is a property of the engine,
                   not the request (a request carrying explicit sampling
                   must match it). Legacy loose ``temperature``/``top_k``/
                   ``top_p`` fields fold into it with a one-time warning.

    One ``EngineConfig`` value is everything needed to spawn an identical
    replica — the router serializes it (``to_json``/``from_json``) as its
    wire format and builds every replica from the same instance.
    """
    max_batch: int = 8
    prefill_chunk: int = 32
    page_size: int = 16
    max_seq_len: int = 256
    n_pages: Optional[int] = None
    token_budget: Optional[int] = None
    first_chunk: Optional[int] = None
    attn_backend: str = "auto"
    kv_splits: int = 1
    prefix_cache: bool = False
    class_shares: Optional[tuple] = None
    sampling: SamplingParams = SamplingParams()
    # deprecated loose spellings — fold into ``sampling`` (warn once)
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None

    def __post_init__(self):
        legacy = {k: getattr(self, k)
                  for k in ("temperature", "top_k", "top_p")
                  if getattr(self, k) is not None}
        if legacy:
            merged = api.merge_legacy_sampling(
                None if self.sampling == SamplingParams() else self.sampling,
                "serve.engine.EngineConfig", **legacy)
            object.__setattr__(self, "sampling", merged)
            for k in legacy:
                object.__setattr__(self, k, None)
        if self.class_shares is not None:
            object.__setattr__(self, "class_shares",
                               tuple((int(c), float(w))
                                     for c, w in self.class_shares))

    @property
    def pages_per_slot(self) -> int:
        return pages_for(self.max_seq_len, self.page_size)

    @property
    def total_pages(self) -> int:
        return (self.n_pages if self.n_pages is not None
                else self.max_batch * self.pages_per_slot + 1)

    def to_json(self) -> dict:
        """Plain-dict form (the router wire format / replica spawn spec)."""
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)
             if f.name not in ("temperature", "top_k", "top_p")
             and getattr(self, f.name) != f.default}
        if "sampling" in d:
            d["sampling"] = self.sampling.to_json()
        if self.class_shares is not None:
            d["class_shares"] = [list(p) for p in self.class_shares]
        return d

    @classmethod
    def from_json(cls, d: dict) -> "EngineConfig":
        allowed = tuple(f.name for f in dataclasses.fields(cls)
                        if f.name not in ("temperature", "top_k", "top_p"))
        api._check_keys(d, allowed, "engine_config")
        kw = dict(d)
        if kw.get("sampling") is not None:
            kw["sampling"] = SamplingParams.from_json(
                kw["sampling"], "engine_config.sampling")
        if kw.get("class_shares") is not None:
            kw["class_shares"] = tuple(tuple(p) for p in kw["class_shares"])
        return cls(**kw)


class ServeEngine:
    """The step loop. ``sampler(logits, rng) -> tokens`` runs inside the
    jitted step; default is built from ``config.sampling`` via
    ``serve.step.make_sampler`` (greedy when temperature == 0)."""

    def __init__(self, model: Model, params, config: EngineConfig,
                 sampler: Optional[Callable] = None, rng=None, *,
                 metrics=None, tracer=None):
        if model.paged_step is None:
            bad = unsupported_kinds(model)
            raise NotImplementedError(
                f"{model.cfg.name}: layer kind(s) {', '.join(map(repr, bad))}"
                " have no slot resource pool — the engine covers "
                "attn/rglru/rwkv; use the sequential serving path "
                "(launch/serve without --engine)")
        self.model = model
        self.params = params
        self.config = config
        kinds = (tuple(model.cfg.block_pattern)
                 + tuple(model.cfg.remainder_pattern))
        self.has_attn = "attn" in kinds
        self.has_state = any(k in ("rglru", "rwkv") for k in kinds)
        # telemetry: one registry shared by the allocator, prefix cache,
        # scheduler, and the engine's own tick instruments — the stats dict
        # and the Prometheus exposition read the same numbers. Default is a
        # live (cheap) registry; pass obs.NULL_REGISTRY to strip telemetry
        # entirely (stats counters then read 0), obs.Tracer for a lifecycle
        # trace. The tick's phase spans reach a jax.profiler trace either
        # way (obs.trace.Span).
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.pools = init_paged_cache(model, config.total_pages,
                                      config.page_size,
                                      capacity=config.max_batch)
        self.pool_bytes = slot_resource_bytes(self.pools)
        self.allocator = PageAllocator(config.total_pages,
                                       metrics=self.metrics)
        self.prefix_cache = None
        if config.prefix_cache:
            if self.has_state:
                raise NotImplementedError(
                    f"{model.cfg.name}: --prefix-cache shares paged KV, but "
                    "recurrent (rglru/rwkv) state is not position-sliceable "
                    "— prefix caching covers attention-only models")
            self.prefix_cache = PrefixCache(self.allocator, config.page_size,
                                            metrics=self.metrics)
        self.scheduler = Scheduler(
            capacity=config.max_batch, prefill_chunk=config.prefill_chunk,
            allocator=self.allocator, page_size=config.page_size,
            max_pages=config.pages_per_slot,
            token_budget=config.token_budget,
            first_chunk=config.first_chunk,
            paged=self.has_attn,
            prefix_cache=self.prefix_cache,
            class_shares=dict(config.class_shares or ()),
            metrics=self.metrics, tracer=self.tracer)
        self._m_ticks = self.metrics.counter(
            "repro_engine_ticks_total", "engine ticks, by compiled width",
            labelnames=("width",))
        self._m_tick_tokens = self.metrics.histogram(
            "repro_engine_tick_tokens",
            "tokens scheduled per tick (token-budget utilization)")
        self._m_sampler_batch = self.metrics.histogram(
            "repro_engine_sampler_batch",
            "slots consuming their sampled token per tick")
        self._m_occupancy = self.metrics.histogram(
            "repro_engine_page_occupancy", "allocated KV pages per tick")
        self._m_requests = self.metrics.counter(
            "repro_engine_requests_total", "requests submitted, by class",
            labelnames=("request_class",))
        self._m_finished = self.metrics.counter(
            "repro_engine_requests_finished_total",
            "requests finished, by class", labelnames=("request_class",))
        self._m_gen_tokens = self.metrics.counter(
            "repro_engine_generated_tokens_total", "tokens generated")
        sampler = sampler or make_sampler(config.sampling)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._next_rid = 0
        self._tick_no = 0                    # step() calls, the span's arg
        self.tick_widths: set[int] = set()   # distinct compiled step shapes

        def _step(params, pools, tokens, page_table, start_pos, n_tokens,
                  rng):
            logits, pools = model.paged_step(params, tokens, pools,
                                             page_table, start_pos, n_tokens,
                                             backend=config.attn_backend,
                                             kv_splits=config.kv_splits)
            with jax.named_scope("sample"):
                sampled = sampler(logits, rng)
            return sampled, logits, pools

        # donate the pools: the KV pages update in place instead of
        # copying the whole pool every tick (no-op on backends without
        # donation support)
        self._step = jax.jit(_step, donate_argnums=(1,))
        # slot hygiene: zero a recycled slot's recurrent state before its
        # next occupant (one compiled shape — the mask is (capacity,) bool)
        self._zero_slots = (jax.jit(zero_state_slots, donate_argnums=(0,))
                            if self.has_state else None)
        # COW boundary-page copy for mid-page prefix-cache hits (scalar
        # src/dst: one compiled shape no matter which pages are copied)
        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))

    def lower_step(self, width: int):
        """The jitted mixed step lowered for one tick of ``width`` tokens
        per slot (``jax.stages.Lowered``) — what the served program
        contains, e.g. which Pallas kernels (``tpu_custom_call``)."""
        b = self.config.max_batch
        return self._step.lower(
            self.params, self.pools, jnp.zeros((b, width), jnp.int32),
            jnp.asarray(self.scheduler.page_table()),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            self._rng)

    @property
    def n_ticks(self) -> int:
        """Total ticks run (registry-backed; all compiled widths)."""
        return int(self._m_ticks.total())

    # -- request intake -----------------------------------------------------

    def submit(self, request=None, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None,
               stream: Optional[Callable] = None, priority=None) -> int:
        """Queue one ``api.Request``; returns its request id.

        The typed call is ``submit(api.Request(...), stream=...)`` —
        ``stream(event: api.StreamEvent)`` fires for every generated token
        as it is produced. The legacy spelling
        ``submit(prompt, max_new_tokens, eos_id, stream, priority)`` keeps
        working through a once-warning shim (its callback keeps the old
        ``stream(rid, token, done)`` signature).

        A request carrying explicit ``sampling`` must match the engine's
        compiled ``config.sampling`` — the sampler is engine-wide.
        """
        if not isinstance(request, api.Request):
            # legacy path: first positional was the raw prompt
            api._warn_once(
                "serve.engine.ServeEngine.submit",
                "ServeEngine.submit(prompt, max_new_tokens, ...) is "
                "deprecated; pass serve.api.Request (stream callbacks "
                "then receive a StreamEvent)")
            if request is None or max_new_tokens is None:
                raise ApiValidationError(
                    "submit() needs an api.Request (or legacy prompt + "
                    "max_new_tokens)")
            legacy_stream = stream
            if stream is not None:
                def stream(ev, _cb=legacy_stream):
                    _cb(ev.request_id, ev.token, ev.done)
            request = api.Request(
                prompt=request, max_new_tokens=max_new_tokens,
                eos_id=eos_id,
                priority=1 if priority is None else priority)
        elif max_new_tokens is not None or eos_id is not None \
                or priority is not None:
            raise ApiValidationError(
                "submit(api.Request, ...) takes the request fields from "
                "the Request — don't also pass max_new_tokens/eos_id/"
                "priority kwargs")
        if request.sampling is not None \
                and request.sampling != self.config.sampling:
            raise ApiValidationError(
                f"request.sampling={request.sampling} != the engine's "
                f"compiled sampling={self.config.sampling} — the sampler "
                "is engine-wide (EngineConfig.sampling); route this "
                "request to a matching engine or drop request.sampling")
        if request.request_id is None:
            rid = self._next_rid
        else:
            rid = int(request.request_id)
        self._next_rid = max(self._next_rid, rid) + 1
        cb = None
        if stream is not None:
            def cb(_rid, token, done, _stream=stream, _n=[0]):
                _stream(api.StreamEvent(request_id=_rid, token=int(token),
                                        index=_n[0], done=bool(done)))
                _n[0] += 1
        req = _SchedRequest(rid=rid, prompt=request.prompt_ids,
                            max_new_tokens=request.max_new_tokens,
                            eos_id=request.eos_id, stream=cb,
                            priority=request.priority)
        self.scheduler.add(req, now=time.perf_counter())
        self._m_requests.inc(request_class=str(req.priority))
        return rid

    # -- the step loop ------------------------------------------------------

    def step(self) -> list[dict]:
        """Run one tick; returns the requests that finished during it.

        Each phase is a span (``obs.trace.Span``): a profiler annotation,
        and a Chrome event when a ``Tracer`` is attached. ``engine.tick``
        holds ``engine.schedule``, ``engine.prepare`` (COW page copies,
        the rng split, the uploads of the plan and the page table),
        ``engine.dispatch`` (the jitted step's call), ``engine.sync`` (the
        host waits for the sampled tokens) and ``engine.bookkeep``."""
        span = self.tracer.span
        self._tick_no += 1
        with span("engine.tick", tick=self._tick_no) as tick:
            with span("engine.schedule"):
                plan = self.scheduler.next_tick(now=time.perf_counter())
            if plan is None:
                return []
            n_tok = int(plan.n_tokens.sum())
            tick.set(width=plan.width, tokens=n_tok)
            self.tick_widths.add(plan.width)
            with span("engine.prepare"):
                # COW copies queued by this tick's admissions land BEFORE
                # the step (prefill may overwrite the copy from the
                # divergence point); the pinned source page is released
                # once the copy is issued — ops on the pools are ordered by
                # data dependency, re-allocation can only happen at the
                # next host-side tick
                for src, dst in self.scheduler.drain_copies():
                    self.pools = self._copy_page(self.pools, jnp.int32(src),
                                                 jnp.int32(dst))
                    self.allocator.free([src])
                self._rng, sub = jax.random.split(self._rng)
                step_args = (self.params, self.pools,
                             jnp.asarray(plan.tokens),
                             jnp.asarray(self.scheduler.page_table()),
                             jnp.asarray(plan.start_pos),
                             jnp.asarray(plan.n_tokens), sub)
            with span("engine.dispatch"):
                sampled, _, self.pools = self._step(*step_args)
            with span("engine.sync"):
                sampled = np.asarray(sampled)
            with span("engine.bookkeep"):
                finished = self._bookkeep(plan, sampled, n_tok)
        return finished

    def _bookkeep(self, plan, sampled, n_tok: int) -> list[dict]:
        """Registry observes, the scheduler's ``complete_tick`` (stream
        callbacks included) and zeroing of vacated recurrent slots."""
        self._m_ticks.inc(width=str(plan.width))
        self._m_tick_tokens.observe(n_tok)
        self._m_sampler_batch.observe(len(plan.samples))
        if self.has_attn:
            self._m_occupancy.observe(
                self.allocator.n_pages - 1 - self.allocator.n_free)
        finished = self.scheduler.complete_tick(plan, sampled,
                                                now=time.perf_counter())
        if self._zero_slots is not None:
            # zero the recurrent state of slots vacated this tick (finish
            # or preemption) unless a new occupant landed already — the
            # in-step position-0 reset covers that occupant regardless
            mask = np.zeros(self.config.max_batch, bool)
            for i in self.scheduler.drain_freed_slots():
                mask[i] = self.scheduler.slots[i] is None
            if mask.any():
                self.pools = self._zero_slots(self.pools, jnp.asarray(mask))
        for r in finished:
            self._m_finished.inc(request_class=str(r["priority"]))
            self._m_gen_tokens.inc(r["n_generated"])
        return finished

    def run(self, requests=None) -> dict:
        """Serve until the queue drains. ``requests``: optional iterable of
        ``api.Request`` values, ``(prompt, max_new_tokens)`` tuples (a
        documented convenience — converted without warning), or legacy
        ``submit``-kwarg dicts. Returns ``{"results": {rid: tokens},
        "completions": [api.Completion, ...], "stats": ...}``."""
        for r in (requests or []):
            if isinstance(r, api.Request):
                self.submit(r)
            elif isinstance(r, dict):
                kw = dict(r)
                stream = kw.pop("stream", None)
                self.submit(api.Request(**kw), stream=stream)
            else:
                prompt, gen = r
                self.submit(api.Request(prompt=prompt, max_new_tokens=gen))
        t0 = time.perf_counter()
        ticks0 = self.n_ticks
        chunks0 = self.scheduler.n_prefill_chunks
        tokens0 = self.scheduler.n_scheduled_tokens
        finished: list[dict] = []
        while self.scheduler.has_work():
            finished.extend(self.step())
        wall = time.perf_counter() - t0
        stats = self._stats(finished, wall)
        # per-run counters (the engine object is reusable across runs)
        stats["n_ticks"] = self.n_ticks - ticks0
        stats["n_prefill_chunks"] = \
            self.scheduler.n_prefill_chunks - chunks0
        stats["n_scheduled_tokens"] = \
            self.scheduler.n_scheduled_tokens - tokens0
        return {"results": {r["rid"]: r["tokens"] for r in finished},
                "completions": [Completion.from_record(r) for r in finished],
                "stats": stats}

    def _stats(self, finished: list[dict], wall: float) -> dict:
        """Throughput/latency summary of a drained run, with per-priority-
        class SLO accounting (p50/p95 TTFT + latency per class) and the
        prefix-cache hit rate. Percentiles over an empty record set are
        ``None`` (see ``obs.metrics.pct``), never a fabricated 0.0."""
        n_new = sum(r["n_generated"] for r in finished)

        def slo(records) -> dict:
            return slo_summary(
                (r["t_first"] - r["t_submit"] for r in records
                 if r["t_first"] is not None),
                (r["t_done"] - r["t_submit"] for r in records),
                len(records),
                n_preempted=sum(r["n_preempted"] for r in records))

        stats = {
            "n_requests": len(finished),
            "n_generated": int(n_new),
            "n_prompt": int(sum(r["n_prompt"] for r in finished)),
            "n_cached_tokens": int(sum(r["n_cached"] for r in finished)),
            "n_preemptions": self.scheduler.n_preemptions,
            "prefix_hit_rate": (self.prefix_cache.hit_rate
                                if self.prefix_cache is not None else 0.0),
            "kv_page_bytes": self.pool_bytes["kv_page_bytes"],
            "state_slot_bytes": self.pool_bytes["state_slot_bytes"],
            "wall_s": wall,
            "tok_s": n_new / wall if wall > 0 else 0.0,
            **slo(finished),
            "by_class": {c: slo([r for r in finished if r["priority"] == c])
                         for c in sorted({r["priority"] for r in finished})},
        }
        return stats
