"""The typed request API (serve/api.py): the single serving contract.

Covers JSON round-trips for ``SamplingParams`` / ``Request`` /
``StreamEvent`` / ``Completion`` (property sweeps under hypothesis when
installed, seeded parametrized fallbacks otherwise), actionable
validation errors (unknown key did-you-mean, bad priority type), the
request-file schema (``prompt_len`` / ``gen`` conveniences), the
``merge_legacy_sampling`` deprecation shim, ``EngineConfig`` as the
router's serializable replica spec, and new-API-vs-legacy-kwargs parity
on the sampler path.
"""
import warnings

import numpy as np
import pytest

from repro.serve import api
from repro.serve.api import (ApiValidationError, Completion, Request,
                             SamplingParams, StreamEvent,
                             merge_legacy_sampling, normalize_request_entry,
                             parse_request_file, resolve_priority)

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _seeded_requests(n=12):
    """Random Request values mirroring the hypothesis strategy."""
    out = []
    for seed in range(n):
        rng = np.random.default_rng(seed)
        sampling = None
        if seed % 3 == 0:
            sampling = SamplingParams(
                temperature=float(rng.uniform(0, 2)),
                top_k=int(rng.integers(0, 50)),
                top_p=float(rng.uniform(0.1, 1.0)))
        out.append(Request(
            prompt=rng.integers(0, 1000, size=rng.integers(1, 20)).tolist(),
            max_new_tokens=int(rng.integers(1, 100)),
            eos_id=int(rng.integers(0, 1000)) if seed % 2 else None,
            priority=int(rng.integers(0, 4)),
            sampling=sampling,
            request_id=int(rng.integers(0, 100)) if seed % 4 == 0 else None))
    return out


# -- SamplingParams ---------------------------------------------------------

def test_sampling_defaults_are_greedy():
    sp = SamplingParams()
    assert sp.greedy and sp.temperature == 0.0 and sp.top_k == 0 \
        and sp.top_p == 1.0
    assert not SamplingParams(temperature=0.5).greedy


def test_sampling_roundtrip():
    sp = SamplingParams(temperature=0.7, top_k=40, top_p=0.9)
    assert SamplingParams.from_json(sp.to_json()) == sp


@pytest.mark.parametrize("kw", [
    {"temperature": -0.1}, {"top_k": -1}, {"top_k": 1.5},
    {"top_p": 0.0}, {"top_p": 1.5},
])
def test_sampling_validation(kw):
    with pytest.raises(ApiValidationError):
        SamplingParams(**kw)


def test_sampling_from_json_rejects_unknown_key():
    with pytest.raises(ApiValidationError, match="did you mean 'top_k'"):
        SamplingParams.from_json({"topk": 5})


def test_merge_legacy_sampling_warns_once_per_site():
    api._warned.discard("test.site")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sp = merge_legacy_sampling(None, "test.site", temperature=0.5)
        merge_legacy_sampling(None, "test.site", temperature=0.5)
    assert sp == SamplingParams(temperature=0.5)
    assert len([x for x in w if issubclass(x.category,
                                           DeprecationWarning)]) == 1


def test_merge_legacy_sampling_rejects_both():
    with pytest.raises(ApiValidationError, match="both"):
        merge_legacy_sampling(SamplingParams(), "test.site2", top_k=3)


def test_merge_legacy_sampling_passthrough():
    sp = SamplingParams(temperature=0.3)
    assert merge_legacy_sampling(sp, "test.site3") is sp
    assert merge_legacy_sampling(None, "test.site3") == SamplingParams()


# -- Request ----------------------------------------------------------------

def test_request_normalizes_prompt_and_priority():
    r = Request(prompt=np.asarray([1, 2, 3], np.int32), max_new_tokens=4,
                priority="interactive")
    assert r.prompt == (1, 2, 3)
    assert r.priority == 0
    assert r.prompt_ids.dtype == np.int32


def test_request_roundtrip_defaults_omitted():
    r = Request(prompt=[1, 2], max_new_tokens=8)
    d = r.to_json()
    assert set(d) == {"prompt", "max_new_tokens"}   # defaults omitted
    assert Request.from_json(d) == r


@pytest.mark.parametrize("idx", range(12))
def test_request_roundtrip_seeded(idx):
    r = _seeded_requests()[idx]
    assert Request.from_json(r.to_json()) == r


# hypothesis needs float32-exact bounds with width=32; 0.01 is not one
TOP_P_MIN = float(np.float32(0.01))

if HAVE_HYPOTHESIS:
    @hypothesis.given(
        st.lists(st.integers(0, 10_000), min_size=1, max_size=64),
        st.integers(1, 1000), st.none() | st.integers(0, 10_000),
        st.integers(0, 5) | st.sampled_from(
            sorted(api.PRIORITY_CLASSES)),
        st.none() | st.builds(
            SamplingParams,
            temperature=st.floats(0, 4, allow_nan=False, width=32),
            top_k=st.integers(0, 100),
            top_p=st.floats(TOP_P_MIN, 1.0, allow_nan=False, width=32)))
    @hypothesis.settings(max_examples=60, deadline=None)
    def test_request_roundtrip_property(prompt, gen, eos, priority,
                                        sampling):
        r = Request(prompt=prompt, max_new_tokens=gen, eos_id=eos,
                    priority=priority, sampling=sampling)
        rt = Request.from_json(r.to_json())
        assert rt == r
        assert rt.priority == resolve_priority(priority)

    @hypothesis.given(st.builds(
        SamplingParams,
        temperature=st.floats(0, 4, allow_nan=False, width=32),
        top_k=st.integers(0, 100),
        top_p=st.floats(TOP_P_MIN, 1.0, allow_nan=False, width=32)))
    @hypothesis.settings(max_examples=60, deadline=None)
    def test_sampling_roundtrip_property(sp):
        assert SamplingParams.from_json(sp.to_json()) == sp


@pytest.mark.parametrize("d,match", [
    ({"max_new_tokens": 3}, "missing required key 'prompt'"),
    ({"prompt": [1]}, "missing required key 'max_new_tokens'"),
    ({"prompt": [1], "max_new_tokens": 3, "promt": 1}, "did you mean"),
    ({"prompt": [], "max_new_tokens": 3}, "non-empty"),
    ({"prompt": [1], "max_new_tokens": 0}, "max_new_tokens"),
    ({"prompt": [1], "max_new_tokens": 3, "priority": True}, "priority"),
    ({"prompt": [1], "max_new_tokens": 3, "priority": "urgent"},
     "unknown priority class"),
])
def test_request_validation_is_actionable(d, match):
    with pytest.raises(ApiValidationError, match=match):
        Request.from_json(d)


# -- StreamEvent / Completion -----------------------------------------------

def test_stream_event_roundtrip():
    ev = StreamEvent(request_id=3, token=17, index=0, done=False)
    assert StreamEvent.from_json(ev.to_json()) == ev
    ev2 = StreamEvent(request_id=3, token=17, index=5, done=True, replica=1)
    assert StreamEvent.from_json(ev2.to_json()) == ev2


def test_completion_roundtrip_and_derived():
    c = Completion(request_id=1, tokens=(5, 6, 7), n_prompt=4, priority=2,
                   n_cached=2, n_preempted=1, n_redispatched=1, replica=0,
                   t_submit=10.0, t_first=10.5, t_done=12.0)
    assert Completion.from_json(c.to_json()) == c
    assert c.n_generated == 3
    assert c.ttft_s == pytest.approx(0.5)
    assert c.latency_s == pytest.approx(2.0)
    assert c.token_ids.dtype == np.int32
    assert Completion(request_id=0, tokens=(), n_prompt=1).ttft_s is None


def test_completion_from_record():
    rec = {"rid": 7, "slot": 0, "tokens": [np.int32(3), np.int32(4)],
           "n_prompt": 5, "n_generated": 2, "priority": 1, "n_cached": 3,
           "n_preempted": 0, "t_submit": 1.0, "t_admit": 1.1,
           "t_first": 1.2, "t_done": 2.0}
    c = Completion.from_record(rec, replica=1)
    assert c.request_id == 7 and c.tokens == (3, 4) and c.replica == 1
    assert c.n_cached == 3 and c.t_first == 1.2


# -- request files ----------------------------------------------------------

def test_request_file_conveniences():
    entries = parse_request_file(
        [{"prompt_len": 16, "gen": 8},
         {"prompt": [1, 2, 3]},
         {"prompt_len": 4, "max_new_tokens": 2, "priority": "batch",
          "sampling": {"temperature": 0.5}}],
        default_gen=32, default_priority="standard")
    assert entries[0]["prompt_len"] == 16
    assert entries[0]["max_new_tokens"] == 8
    assert entries[1]["prompt"] == [1, 2, 3]
    assert entries[1]["max_new_tokens"] == 32        # default_gen
    assert entries[1]["priority"] == 1
    assert entries[2]["priority"] == 2
    assert entries[2]["sampling"] == SamplingParams(temperature=0.5)


@pytest.mark.parametrize("spec,match", [
    ({"not": "a list"}, "JSON list"),
    ([], "empty"),
    ([{"prompt_len": 4, "gen": 2, "max_new_tokens": 2}], "one, not both"),
    ([{"gen": 2}], "exactly one of 'prompt'"),
    ([{"prompt": [1], "prompt_len": 4}], "exactly one of 'prompt'"),
    ([{"prompt_len": 4, "gen": "two"}], "must be an int"),
    ([{"prompt_len": 16}, {"promt_len": 16}],
     r"requests\[1\].*did you mean 'prompt_len'"),
])
def test_request_file_validation(spec, match):
    with pytest.raises(ApiValidationError, match=match):
        parse_request_file(spec, default_gen=8)


def test_normalize_entry_indexes_errors():
    with pytest.raises(ApiValidationError, match=r"requests\[3\]"):
        normalize_request_entry("nope", 3, default_gen=8)


# -- EngineConfig: the router's replica spec --------------------------------

def test_engine_config_roundtrip():
    from repro.serve.engine import EngineConfig
    cfg = EngineConfig(max_batch=4, prefill_chunk=8, page_size=4,
                       max_seq_len=64, prefix_cache=True,
                       class_shares=((0, 1.0), (2, 0.25)),
                       sampling=SamplingParams(temperature=0.5, top_k=10))
    rt = EngineConfig.from_json(cfg.to_json())
    assert rt == cfg
    assert rt.sampling == cfg.sampling
    # defaults are omitted from the wire form
    assert "attn_backend" not in cfg.to_json()
    with pytest.raises(ApiValidationError, match="did you mean"):
        EngineConfig.from_json({"max_batc": 4})


def test_engine_config_legacy_sampling_folds():
    from repro.serve.engine import EngineConfig
    api._warned.discard("serve.engine.EngineConfig")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = EngineConfig(max_batch=2, temperature=0.7, top_k=5)
    assert cfg.sampling == SamplingParams(temperature=0.7, top_k=5)
    assert cfg.temperature is None and cfg.top_k is None
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


def test_scheduler_reexports_priority_api():
    # back-compat: the scheduler re-exports the priority vocabulary
    from repro.serve.scheduler import PRIORITY_CLASSES as SCHED_PC
    from repro.serve.scheduler import resolve_priority as sched_rp
    assert SCHED_PC is api.PRIORITY_CLASSES
    assert sched_rp is resolve_priority


# -- new-API vs legacy-kwargs parity (sampler path) -------------------------

def test_make_sampler_new_vs_legacy_parity():
    import jax
    from repro.serve.step import make_sampler

    logits = np.asarray(np.random.default_rng(0).normal(size=(3, 50)),
                        np.float32)
    rng = jax.random.PRNGKey(7)
    sp = SamplingParams(temperature=0.8, top_k=12, top_p=0.7)
    new = make_sampler(sp)(logits, rng)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = make_sampler(temperature=0.8, top_k=12, top_p=0.7)(logits,
                                                                    rng)
        positional = make_sampler(0.8, 12, 0.7)(logits, rng)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(legacy))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(positional))


def test_generate_new_vs_legacy_parity():
    """generate(sampling=SamplingParams(...)) == legacy kwargs spelling,
    token for token, on a tiny transformer."""
    import jax
    from repro.models.model_zoo import build
    from repro.serve.step import generate

    model = build("smollm-360m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0,
                           model.cfg.vocab), np.int32)
    sp = SamplingParams(temperature=0.9, top_k=8)
    rng = jax.random.PRNGKey(11)
    new = np.asarray(generate(model, params, prompt, 4, sampling=sp,
                              rng=rng))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = np.asarray(generate(model, params, prompt, 4,
                                     temperature=0.9, top_k=8, rng=rng))
    np.testing.assert_array_equal(new, legacy)
