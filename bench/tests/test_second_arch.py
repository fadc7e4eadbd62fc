"""A second architecture enters through the seam alone.

``bench/tests/archs/paired_window.py`` (two parameter groups, a sliding
window, weights made group by group on the host, a reference of its own)
is found by pointing ``bench.arch.PACKAGES`` at its package; no harness
file knows it. ``cell.run`` drives it on the CPU through a serving and a
training mix, sound and then with ``test_faults.py``'s planted faults.
"""
import json

import numpy as np
import pytest

from bench import arch, readings
from bench.serve import ServeRun
from bench.tests import tiny
from bench.tests.test_faults import (SERVE_LIMITS, TRAIN_LIMITS, _wrap_step,
                                     altered_tokens, drive)  # noqa: F401

NAME = "paired_window"
SERVE_CELL = "qwen3-0.6b.bcsr90.longctx-decode"
TRAIN_CELL = "smollm-360m.bcsr90.debias-train"


@pytest.fixture
def seam(monkeypatch):
    monkeypatch.setattr(arch, "PACKAGES",
                        arch.PACKAGES + ("bench.tests.archs",))


def cfg(limits=None):
    c = tiny.cfg(limits)
    c.update(name=NAME, architecture=NAME)
    c["model"].update(num_hidden_layers=4, sliding_window=16)
    return c


def test_found_only_through_the_seam(seam, monkeypatch):
    assert arch.of(cfg()).__name__ == f"bench.tests.archs.{NAME}"
    monkeypatch.setattr(arch, "PACKAGES", ("bench.arch",))
    with pytest.raises(SystemExit, match="known architectures"):
        arch.of(cfg())


def test_groups_and_windowed_attention(seam):
    c = cfg()
    r = ServeRun(c, tiny.mix("longctx-decode"), 1, 1)
    projs = r.arch.projections(c)
    assert {p.path[1] for p in projs.values()} == {"b0_attn", "b1_attn"}
    r.blocks = {n: np.array([3, 2]) for n in projs}
    # one slot decodes at position 40 (16 keys in the window), one idles
    r.plans = [(1, np.array([40, 7]), np.array([1, 0]))]
    calls = readings.serve_attention_calls(r)
    assert calls == [(4.0 * 4 * 32 * 16, 2.0 * 16 * 2 * 32 * 2
                      + 4 * 32 * 8)] * 4
    assert len(readings.serve_spmm_calls(r)) == 14 * 2


def test_serve_sound_then_token_altered(seam, tmp_path, request):
    c = cfg(SERVE_LIMITS)
    sound = drive(SERVE_CELL, c, tiny.mix("longctx-decode"), tmp_path)
    assert sound["correct"], json.dumps(sound["checks"])
    request.getfixturevalue("altered_tokens")
    bad = drive(SERVE_CELL, c, tiny.mix("longctx-decode"), tmp_path)
    assert not bad["correct"]
    assert bad["checks"]["mean_logit_gap"]["value"] > \
        SERVE_LIMITS["mean_logit_gap"]


def test_train_sound(seam, tmp_path):
    got = drive(TRAIN_CELL, cfg(TRAIN_LIMITS), tiny.mix("debias-train"),
                tmp_path)
    assert got["correct"], json.dumps(got["checks"])


def test_train_state_unchanged(seam, tmp_path, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged
    _wrap_step(monkeypatch, wrap)
    got = drive(TRAIN_CELL, cfg(TRAIN_LIMITS), tiny.mix("debias-train"),
                tmp_path)
    assert not got["correct"]
    assert got["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch(seam, tmp_path, monkeypatch):
    def wrap(step):
        def half(state, batch):
            return step(state, {k: v[: len(v) // 2]
                                for k, v in batch.items()})
        return half
    _wrap_step(monkeypatch, wrap)
    got = drive(TRAIN_CELL, cfg(TRAIN_LIMITS), tiny.mix("debias-train"),
                tmp_path)
    assert not got["correct"]
