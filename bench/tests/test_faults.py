"""A run with the timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip: it drives the rest of
a run (set-up, window, check) on the CPU at a test size, once sound and
once with one planted fault a cell can have, and reads ``correct``. The
limits here are set between the sound and faulted readings at this size;
the cells' own limits come from chip readings (PERF.md).
"""
import argparse
import json
import time

import pytest

from bench import cell as cell_lib, run as R
from bench.tests import tiny

SERVE_LIMITS = {"logit_gap": 0.12, "mean_logit_gap": 0.002}
TRAIN_LIMITS = {"loss_gap": 0.008, "grad_norm_gap": 0.05,
                "change_norm_gap": 0.05}


def drive(workload, cfg, mix, tmp_path, seed=2**31 + 17, seconds=2):
    import jax
    spec = R.load_json(R.CHECKOUT / "BENCHMARK.json")
    cell = {"name": workload, "chips": 1}
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    return cell_lib.run(spec, cell, cfg, mix, args, jax.devices()[:1], peak,
                        R.CompileCounter(), time.perf_counter(), tmp_path)


@pytest.fixture
def altered_tokens(monkeypatch):
    """Every sampled token replaced by its neighbour id."""
    import jax.numpy as jnp
    import repro.serve.engine as engine

    def make_sampler(sampling):
        def sample(logits, rng):
            return (jnp.argmax(logits, -1).astype(jnp.int32) + 1) \
                % logits.shape[-1]
        return sample
    monkeypatch.setattr(engine, "make_sampler", make_sampler)


@pytest.mark.parametrize("workload,traffic", [
    ("smollm-360m.bcsr90.chat", "chat"),
    ("qwen3-0.6b.bcsr90.longctx-decode", "longctx-decode")])
def test_serve_sound_then_token_altered(workload, traffic, tmp_path,
                                        request):
    cfg = tiny.cfg(SERVE_LIMITS)
    sound = drive(workload, cfg, tiny.mix(traffic), tmp_path)
    assert sound["correct"], json.dumps(sound["checks"])
    request.getfixturevalue("altered_tokens")
    bad = drive(workload, cfg, tiny.mix(traffic), tmp_path)
    assert not bad["correct"]
    assert bad["checks"]["mean_logit_gap"]["value"] > \
        SERVE_LIMITS["mean_logit_gap"]


def _wrap_step(monkeypatch, wrap):
    import repro.train.step as step_mod
    orig = step_mod.make_train_step

    def make_train_step(*a, **k):
        return wrap(orig(*a, **k))
    monkeypatch.setattr(step_mod, "make_train_step", make_train_step)


def test_train_sound(tmp_path):
    got = drive("smollm-360m.bcsr90.debias-train", tiny.cfg(TRAIN_LIMITS),
                tiny.mix("debias-train"), tmp_path)
    assert got["correct"], json.dumps(got["checks"])


def test_train_state_unchanged(tmp_path, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged
    _wrap_step(monkeypatch, wrap)
    got = drive("smollm-360m.bcsr90.debias-train", tiny.cfg(TRAIN_LIMITS),
                tiny.mix("debias-train"), tmp_path)
    assert not got["correct"]
    assert got["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch(tmp_path, monkeypatch):
    def wrap(step):
        def half(state, batch):
            return step(state, {k: v[: len(v) // 2]
                                for k, v in batch.items()})
        return half
    _wrap_step(monkeypatch, wrap)
    got = drive("smollm-360m.bcsr90.debias-train", tiny.cfg(TRAIN_LIMITS),
                tiny.mix("debias-train"), tmp_path)
    assert not got["correct"]
