"""Telemetry subsystem: metrics registry, tracer, engine spans and scopes.

The load-bearing guarantees:
  * percentile helpers: ``pct([])`` is ``None`` — an empty completions list
    must never crash ``np.percentile`` or fabricate a 0.0 SLO,
  * registry: get-or-create identity, labeled series, kind/label conflicts
    rejected, snapshot + Prometheus exposition round-trip through the
    strict reader (``parse_prometheus``),
  * tracer: span nesting/ordering invariants under a fake clock, a
    preempt-requeue produces a *resumed* span chain (never overlapping
    duplicates), Chrome schema validation (required keys, monotonic ts),
  * engine end-to-end: Prometheus counters match scheduler / allocator /
    prefix-cache ground truth, and per-request trace span durations
    reconcile with the stats dict's ttft/latency percentiles (± a tick),
  * engine spans: a ``jax.profiler`` trace of the engine holds each tick's
    phases in order (and ``launch/serve --profile DIR`` writes one), the
    Chrome export of the same run keeps its engine track, and a span with
    no profiler session stays cheap,
  * model scopes: the lowered steps carry them in their ``op_name``
    metadata, and they change no compiled operation.
"""
import contextlib
import glob
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model_zoo import build
from repro.obs.metrics import (MetricsRegistry, NULL_REGISTRY, parse_prometheus,
                               pct, prom_value, slo_summary)
from repro.obs.profile import TrainTelemetry, sparsity_telemetry_fn
from repro.obs.trace import (ENGINE_TID, NULL_TRACER, Tracer,
                             validate_chrome_trace)
from repro.serve import api
from repro.serve.engine import EngineConfig, ServeEngine

GEN = 5


@pytest.fixture(scope="module")
def model():
    return build("smollm-360m", reduced=True)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _prompts(lens, vocab, seed=7):
    key = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                          (L,), 0, vocab), np.int32)
            for i, L in enumerate(lens)]


# ---------------------------------------------------------------------------
# percentile / SLO helpers
# ---------------------------------------------------------------------------

def test_pct_empty_is_none():
    assert pct([], 50) is None
    assert pct([], 95) is None
    assert pct(iter([]), 50) is None


def test_pct_values():
    assert pct([3.0], 50) == 3.0
    assert pct([1.0, 2.0, 3.0], 50) == 2.0


def test_slo_summary_empty():
    s = slo_summary([], [], 0, n_preempted=0)
    assert s["n_requests"] == 0 and s["n_preempted"] == 0
    assert s["ttft_p50_s"] is None and s["latency_p95_s"] is None


def test_slo_summary_extra_keys():
    s = slo_summary([0.1], [0.5], 1, n_redispatched=2)
    assert s["n_redispatched"] == 2
    assert s["ttft_p50_s"] == pytest.approx(0.1)
    assert s["latency_p50_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_identity():
    m = MetricsRegistry()
    c1 = m.counter("repro_x_total", "x")
    c2 = m.counter("repro_x_total")
    assert c1 is c2
    c1.inc()
    c2.inc(2)
    assert c1.value() == 3


def test_registry_conflicts_rejected():
    m = MetricsRegistry()
    m.counter("repro_x_total")
    with pytest.raises(ValueError):
        m.gauge("repro_x_total")
    m.counter("repro_y_total", labelnames=("kind",))
    with pytest.raises(ValueError):
        m.counter("repro_y_total", labelnames=("other",))
    with pytest.raises(ValueError):
        m.counter("bad name!")


def test_counter_labels_and_total():
    m = MetricsRegistry()
    c = m.counter("repro_tok_total", labelnames=("kind",))
    c.inc(3, kind="prefill")
    c.inc(2, kind="decode")
    assert c.value(kind="prefill") == 3
    assert c.total() == 5
    with pytest.raises(ValueError):
        c.inc()                               # labeled counter needs labels
    with pytest.raises(ValueError):
        m.counter("repro_plain_total").inc(1, kind="x")   # and vice versa
    with pytest.raises(ValueError):
        c.inc(-1, kind="decode")              # counters only go up


def test_histogram_bounded_window():
    m = MetricsRegistry()
    h = m.histogram("repro_h", max_samples=8)
    for i in range(100):
        h.observe(float(i))
    assert h.count() == 100                    # exact count survives the ring
    assert h.sum() == sum(range(100))
    assert len(h._series[()].samples) == 8     # bounded reservoir
    assert h.percentile(50) >= 90              # window holds recent values


def test_snapshot_shape():
    m = MetricsRegistry()
    m.counter("repro_a_total").inc(2)
    m.gauge("repro_g").set(7)
    m.histogram("repro_h").observe(1.5)
    snap = m.snapshot()
    assert snap["repro_a_total"]["type"] == "counter"
    assert snap["repro_a_total"]["series"][0]["value"] == 2
    assert snap["repro_g"]["series"][0]["value"] == 7
    hs = snap["repro_h"]["series"][0]
    assert hs["count"] == 1 and hs["p50"] == 1.5
    json.dumps(snap)                           # JSON-safe by construction


def test_prometheus_round_trip():
    m = MetricsRegistry()
    m.counter("repro_a_total", "a counter").inc(3)
    c = m.counter("repro_b_total", labelnames=("kind",))
    c.inc(2, kind="prefill")
    c.inc(5, kind="decode")
    m.gauge("repro_g", "a gauge").set(1.25)
    h = m.histogram("repro_h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = m.to_prometheus()
    parsed = parse_prometheus(text)
    assert prom_value(parsed, "repro_a_total") == 3
    assert prom_value(parsed, "repro_b_total", kind="decode") == 5
    assert prom_value(parsed, "repro_b_total") == 7      # label-free sums
    assert prom_value(parsed, "repro_g") == 1.25
    assert prom_value(parsed, "repro_h_count") == 3
    assert prom_value(parsed, "repro_h_sum") == 6.0
    assert prom_value(parsed, "repro_h", quantile="0.5") == 2.0
    assert prom_value(parsed, "repro_missing") is None


def test_prometheus_extra_labels():
    m = MetricsRegistry()
    m.counter("repro_a_total").inc(4)
    parsed = parse_prometheus(m.to_prometheus({"replica": 1}))
    assert prom_value(parsed, "repro_a_total", replica="1") == 4


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("this is not { a sample\n")


def test_null_registry_is_inert():
    c = NULL_REGISTRY.counter("repro_x_total", labelnames=("kind",))
    c.inc(5, kind="prefill")
    assert c.value(kind="prefill") == 0 and c.total() == 0
    h = NULL_REGISTRY.histogram("repro_h")
    h.observe(1.0)
    assert h.count() == 0 and h.percentile(50) is None
    assert NULL_REGISTRY.snapshot() == {}
    assert NULL_REGISTRY.to_prometheus() == ""


# ---------------------------------------------------------------------------
# tracer (fake clock)
# ---------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    def advance(ds):
        t[0] += ds

    return clock, advance


def _spans(tr, tid=None, name=None):
    return [e for e in tr.events if e["ph"] == "X"
            and (tid is None or e["tid"] == tid)
            and (name is None or e["name"] == name)]


def test_tracer_lifecycle_spans():
    clock, advance = _fake_clock()
    tr = Tracer(clock=clock)
    tr.request_submit(0, priority=1, n_prompt=8)
    advance(0.010)
    tr.request_admit(0, resumed=False, n_cached=0)
    advance(0.020)
    tr.request_first_token(0)
    tr.request_decode(0)
    advance(0.030)
    tr.request_finish(0)

    tid = tr._tid(0)
    spans = _spans(tr, tid=tid)
    assert [s["name"] for s in sorted(spans, key=lambda s: s["ts"])] == \
        ["wait", "prefill", "decode"]
    # exactly one phase open at a time: spans tile the timeline
    spans.sort(key=lambda s: s["ts"])
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] == b["ts"]
    assert spans[0]["dur"] == 10_000 and spans[1]["dur"] == 20_000
    names = [e["name"] for e in tr.events if e["ph"] == "i"]
    assert names == ["submit", "first_token", "done"]


def test_tracer_preempt_resumed_chain_not_duplicate():
    clock, advance = _fake_clock()
    tr = Tracer(clock=clock)
    tr.request_submit(7, priority=2, n_prompt=4)
    advance(0.001)
    tr.request_admit(7, resumed=False, n_cached=0)
    advance(0.001)
    tr.request_decode(7)
    tr.request_decode(7)                       # per-token: idempotent
    advance(0.001)
    tr.request_preempt(7)
    advance(0.005)
    tr.request_admit(7, resumed=True, n_cached=0)
    advance(0.001)
    tr.request_decode(7)
    advance(0.001)
    tr.request_finish(7)

    tid = tr._tid(7)
    spans = sorted(_spans(tr, tid=tid), key=lambda s: s["ts"])
    assert [s["name"] for s in spans] == \
        ["wait", "prefill", "decode", "wait", "prefill", "decode"]
    # resumed chain, never overlapping duplicates
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert spans[3]["args"]["resumed"] is True
    assert spans[4]["args"]["resumed"] is True
    # idempotent phase(): only ONE decode span per admission
    assert sum(s["name"] == "decode" for s in spans) == 2


def test_tracer_engine_span_nesting():
    clock, advance = _fake_clock()
    tr = Tracer(clock=clock)
    with tr.span("engine.tick", tick=1) as tick:
        with tr.span("engine.schedule"):
            advance(0.002)
        tick.set(width=4, tokens=3)
        with tr.span("engine.dispatch"):
            advance(0.003)
    doc = tr.to_chrome()
    validate_chrome_trace(doc)
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # parent (longest) first at equal ts; children contained in the tick
    assert body[0]["name"] == "engine.tick"
    tick = body[0]
    assert tick["args"] == {"tick": 1, "width": 4, "tokens": 3}
    assert [e["name"] for e in body[1:]] == ["engine.schedule",
                                             "engine.dispatch"]
    for child in body[1:]:
        assert tick["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= tick["ts"] + tick["dur"]


def test_chrome_schema_and_metadata():
    clock, advance = _fake_clock()
    tr = Tracer(clock=clock)
    tr.request_submit(3, priority=0, n_prompt=2)
    advance(0.001)
    tr.request_finish(3)
    doc = tr.to_chrome(process_name="test-proc")
    events = validate_chrome_trace(doc)
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= \
        {"test-proc", "engine", "request 3"}
    for e in events:
        for k in ("name", "ph", "ts", "pid", "tid"):
            assert k in e


def test_validate_chrome_trace_rejects():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "traceEvents"})
    bad = {"traceEvents": [{"name": "a", "ph": "i", "ts": 5, "pid": 0,
                            "tid": 0},
                           {"name": "b", "ph": "i", "ts": 1, "pid": 0,
                            "tid": 0}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)             # ts not monotonic
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                                "ts": 0, "pid": 0,
                                                "tid": 0}]})  # X without dur


def test_null_tracer_inert():
    NULL_TRACER.request_submit(0, 0, 0)
    NULL_TRACER.request_finish(0)
    with NULL_TRACER.span("x"):
        pass
    assert NULL_TRACER.events == []
    assert NULL_TRACER.to_chrome()["traceEvents"] == []


# ---------------------------------------------------------------------------
# train telemetry
# ---------------------------------------------------------------------------

def test_train_telemetry_jsonl(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = TrainTelemetry(path)
    tel.emit({"phase": "spc", "step": 0, "loss": np.float32(1.5)})
    tel.emit({"phase": "debias", "step": 1, "loss": 1.0})
    tel.close()
    recs = [json.loads(l) for l in open(path)]
    assert tel.n_records == 2 and len(recs) == 2
    assert recs[0]["phase"] == "spc" and recs[0]["loss"] == 1.5
    assert recs[1]["phase"] == "debias"


def test_sparsity_telemetry_fn(model, params):
    fn = sparsity_telemetry_fn((8, 64), lam=0.5)
    rec = fn(params)
    assert 0.0 <= rec["block_sparsity"] <= 1.0
    assert rec["group_l1_penalty"] > 0
    assert rec["layer_block_sparsity"]          # at least one target layer
    for v in rec["layer_block_sparsity"].values():
        assert 0.0 <= v <= 1.0


# ---------------------------------------------------------------------------
# engine end-to-end: counters vs ground truth, trace vs stats
# ---------------------------------------------------------------------------

def test_engine_counters_match_ground_truth(model, params):
    tracer = Tracer()
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=4, prefill_chunk=8, page_size=4,
                                   max_seq_len=16 + GEN),
                      tracer=tracer)
    prompts = _prompts([5, 12, 3, 16, 9], model.cfg.vocab)
    out = eng.run([(p, GEN) for p in prompts])
    stats = out["stats"]

    parsed = parse_prometheus(eng.metrics.to_prometheus())
    assert prom_value(parsed, "repro_engine_ticks_total") == eng.n_ticks
    assert prom_value(parsed, "repro_sched_prefill_chunks_total") == \
        eng.scheduler.n_prefill_chunks
    assert prom_value(parsed, "repro_sched_tokens_total") == \
        eng.scheduler.n_scheduled_tokens
    assert prom_value(parsed, "repro_engine_requests_total") == len(prompts)
    assert prom_value(parsed, "repro_engine_requests_finished_total") == \
        len(prompts)
    assert prom_value(parsed, "repro_engine_generated_tokens_total") == \
        stats["n_generated"]
    # every admission was fresh (no preemption in this mix)
    assert prom_value(parsed, "repro_sched_admissions_total",
                      resumed="false") == len(prompts)
    # an untouched counter has no series -> absent from the exposition
    assert prom_value(parsed, "repro_sched_preemptions_total") is None
    assert eng.scheduler.n_preemptions == 0
    # allocator churn balances once every request released its pages
    allocs = prom_value(parsed, "repro_page_allocs_total")
    frees = prom_value(parsed, "repro_page_frees_total")
    assert allocs > 0 and allocs == frees
    assert prom_value(parsed, "repro_pages_in_use") == 0
    assert prom_value(parsed, "repro_pages_free") == eng.allocator.n_free
    # tick-width counts sum over the labeled series
    widths = {lab_d["width"]
              for (n, lab), _ in parsed.items() if n == "repro_engine_ticks_total"
              for lab_d in [dict(lab)]}
    assert widths <= {"1", "8"}                # decode + prefill_chunk widths

    # trace reconciles with stats: per-request done-submit vs latency p50
    doc = tracer.to_chrome()
    validate_chrome_trace(doc)
    by_rid_inst = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "i" and e.get("cat") == "request":
            by_rid_inst.setdefault(e["args"]["rid"], {})[e["name"]] = e["ts"]
    tick_durs = [e["dur"] for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "engine.tick"]
    tol_s = (max(tick_durs) / 1e6) * 1.5 + 0.05   # ± a tick (+ sched slack)
    lats = [(inst["done"] - inst["submit"]) / 1e6
            for inst in by_rid_inst.values()]
    ttfts = [(inst["first_token"] - inst["submit"]) / 1e6
             for inst in by_rid_inst.values()]
    assert len(lats) == len(prompts)
    assert abs(float(np.percentile(lats, 50)) - stats["latency_p50_s"]) \
        <= tol_s
    assert abs(float(np.percentile(ttfts, 50)) - stats["ttft_p50_s"]) \
        <= tol_s


def test_engine_preemption_resumed_spans_and_counters(model, params):
    tracer = Tracer()
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=2, prefill_chunk=8, page_size=4,
                                   max_seq_len=32),
                      tracer=tracer)
    prompts = _prompts([6, 6, 6, 6], model.cfg.vocab)
    finished = []
    for i in range(3):                         # batch requests occupy slots
        eng.submit(api.Request(prompt=prompts[i], max_new_tokens=16,
                               priority="batch"))
    for _ in range(4):
        finished.extend(eng.step())
    eng.submit(api.Request(prompt=prompts[3], max_new_tokens=4,
                           priority="interactive"))
    while eng.scheduler.has_work():
        finished.extend(eng.step())
    assert eng.scheduler.n_preemptions >= 1

    parsed = parse_prometheus(eng.metrics.to_prometheus())
    assert prom_value(parsed, "repro_sched_preemptions_total") == \
        eng.scheduler.n_preemptions
    resumed = prom_value(parsed, "repro_sched_admissions_total",
                         resumed="true")
    assert resumed is not None and resumed >= 1
    assert prom_value(parsed, "repro_engine_requests_total",
                      request_class="0") == 1      # the interactive arrival

    # preempted request: resumed span chain, never overlapping duplicates
    doc = tracer.to_chrome()
    validate_chrome_trace(doc)
    preempted_tids = {e["tid"] for e in doc["traceEvents"]
                      if e["ph"] == "i" and e["name"] == "preempt"}
    assert preempted_tids
    for tid in preempted_tids:
        spans = sorted([e for e in doc["traceEvents"]
                        if e["ph"] == "X" and e["tid"] == tid],
                       key=lambda e: e["ts"])
        assert sum(s["name"] == "wait" for s in spans) >= 2
        assert any(s["args"].get("resumed") for s in spans)
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]


def test_engine_prefix_cache_counters(model, params):
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=4, prefill_chunk=8, page_size=4,
                                   max_seq_len=32, prefix_cache=True))
    shared = _prompts([16], model.cfg.vocab)[0]
    tails = _prompts([4, 4], model.cfg.vocab, seed=11)
    wave = [(np.concatenate([shared, t]), GEN) for t in tails]
    eng.run(wave)                              # cold: populates the cache
    eng.run(wave)                              # warm: hits
    c = eng.prefix_cache
    parsed = parse_prometheus(eng.metrics.to_prometheus())
    assert prom_value(parsed, "repro_prefix_queries_total") == c.n_queries
    assert prom_value(parsed, "repro_prefix_hit_queries_total") == \
        c.n_hit_queries
    assert prom_value(parsed, "repro_prefix_tokens_hit_total") == \
        c.tokens_hit
    assert c.tokens_hit > 0                    # the warm wave actually hit
    assert prom_value(parsed, "repro_prefix_cached_pages") == c.n_cached_pages
    inserted = prom_value(parsed, "repro_prefix_inserted_pages_total")
    evicted = prom_value(parsed, "repro_prefix_evictions_total") or 0
    assert inserted - evicted == c.n_cached_pages


def test_engine_stats_read_from_registry(model, params):
    """`engine._stats` counters are registry-backed — zeroing the registry
    path (NULL) still yields a structurally complete stats dict."""
    from repro.obs.metrics import NullRegistry

    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=2, prefill_chunk=8, page_size=4,
                                   max_seq_len=16),
                      metrics=NullRegistry())
    out = eng.run([(p, GEN) for p in _prompts([4, 6], model.cfg.vocab)])
    s = out["stats"]
    assert s["n_generated"] == 2 * GEN         # records, not registry
    assert eng.n_ticks == 0                    # registry-backed -> inert
    assert eng.scheduler.n_prefill_chunks == 0
    assert eng.metrics.to_prometheus() == ""


def test_disabled_telemetry_overhead():
    """The no-op path must stay a constant-time method call per site."""
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        NULL_TRACER.request_decode(1)
        NULL_REGISTRY.counter("x").inc()
    dt = time.perf_counter() - t0
    assert dt < 2.0                            # generous: ~µs per call pair


def test_span_without_profiler_session_is_cheap():
    """An engine span with no profiler session and no Tracer is a TraceMe
    enter/exit: 200k of them fit the budget of the no-op paths above."""
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with NULL_TRACER.span("engine.sync"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0


# ---------------------------------------------------------------------------
# engine spans in a jax.profiler trace; model scopes in the compiled steps
# ---------------------------------------------------------------------------

PHASES = ["engine.schedule", "engine.prepare", "engine.dispatch",
          "engine.sync", "engine.bookkeep"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [ev for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for ev in line.events]


def test_engine_ticks_in_profiler_trace(model, params, tmp_path):
    tracer = Tracer()
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=2, prefill_chunk=8, page_size=4,
                                   max_seq_len=32), tracer=tracer)
    eng.submit(api.Request(prompt=_prompts([5], model.cfg.vocab)[0],
                           max_new_tokens=GEN))
    eng.step()                                 # compile outside the trace
    eng.step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
    evs = [ev for ev in _host_events(tmp_path)
           if ev.name.startswith("engine.")]
    ticks = sorted((ev for ev in evs if ev.name == "engine.tick"),
                   key=lambda ev: ev.start_ns)
    assert len(ticks) == 3
    for tick in ticks:
        end = tick.start_ns + tick.duration_ns
        kids = sorted((ev for ev in evs if ev.name != "engine.tick"
                       and tick.start_ns <= ev.start_ns
                       and ev.start_ns + ev.duration_ns <= end),
                      key=lambda ev: ev.start_ns)
        assert [ev.name for ev in kids] == PHASES
        for a, b in zip(kids, kids[1:]):
            assert a.start_ns + a.duration_ns <= b.start_ns
        args = dict(tick.stats)
        assert args["width"] == 1 and args["tokens"] == 1
    assert [dict(t.stats)["tick"] for t in ticks] == [3, 4, 5]
    # the Chrome export of the same run still has the engine track
    doc = tracer.to_chrome()
    validate_chrome_trace(doc)
    names = [e["name"] for e in doc["traceEvents"]
             if e["ph"] == "X" and e["tid"] == ENGINE_TID]
    assert names.count("engine.tick") == 5
    for phase in PHASES:
        assert names.count(phase) == 5


def test_serve_profile_dir_holds_engine_spans(tmp_path):
    from repro.launch import serve
    serve.main(["--arch", "smollm-360m", "--reduced", "--engine",
                "--batch", "2", "--prompt-len", "4", "--gen", "3",
                "--max-batch", "2", "--prefill-chunk", "8",
                "--profile", str(tmp_path)])
    names = {ev.name for ev in _host_events(tmp_path)}
    assert {"engine.tick", *PHASES} <= names


def _scopes(op_names) -> set:
    return {part for n in op_names for part in re.split(r"[/()]", n)}


def _paged_step(model, params):
    eng = ServeEngine(model, params,
                      EngineConfig(max_batch=2, prefill_chunk=8, page_size=4,
                                   max_seq_len=16))
    return eng.lower_step(8)


def _train_step(model, params):
    from repro.core.masks import zero_mask
    from repro.core.optimizers import prox_adam
    from repro.train.state import TrainState
    from repro.train.step import make_train_step
    opt = prox_adam(1e-3, lam=0.0)
    state = TrainState(params=params, opt_state=opt.init(params),
                       mask=zero_mask(params), step=jnp.zeros((), jnp.int32))
    batch = {"inputs": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    return jax.jit(make_train_step(model, opt)).lower(state, batch)


STEPS = {"paged": (_paged_step, {"embed", "layers", "attention", "mlp",
                                 "head", "sample"}),
         "train": (_train_step, {"embed", "layers", "attention", "mlp",
                                 "head", "loss", "optimizer"})}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_lowered_steps_carry_scopes(model, params, step):
    lower, want = STEPS[step]
    text = lower(model, params).as_text(debug_info=True)
    assert want <= _scopes(re.findall(r'loc\("([^"]*)"', text))


def _instructions(compiled_text: str) -> list[str]:
    """The compiled module's instructions with their metadata removed (the
    module's tables of source locations are no instructions)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line).strip()
            for line in compiled_text.splitlines() if " = " in line]


@pytest.mark.parametrize("step", sorted(STEPS))
def test_scopes_change_no_compiled_op(model, params, step, monkeypatch):
    lower, _ = STEPS[step]
    scoped = _instructions(lower(model, params).compile().as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _instructions(lower(model, params).compile().as_text())
    assert len(scoped) == len(plain) > 100
    assert scoped == plain
