"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the length distributions the mixes state."""
import json
from pathlib import Path

import numpy as np

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def test_open_poisson_deterministic_per_seed():
    a = loadgen.open_poisson(mix("chat"), 2**31 + 11, 30, 1000)
    b = loadgen.open_poisson(mix("chat"), 2**31 + 11, 30, 1000)
    assert [(j.due, j.max_new_tokens) for j in a] == \
        [(j.due, j.max_new_tokens) for j in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes():
    m = mix("chat")
    a = loadgen.open_poisson(m, 1, 30, 1000)
    b = loadgen.open_poisson(m, 2, 30, 1000)
    assert sorted(len(j.prompt) for j in a) == sorted(len(j.prompt) for j in b)
    assert sorted(j.max_new_tokens for j in a) == \
        sorted(j.max_new_tokens for j in b)
    assert [len(j.prompt) for j in a] != [len(j.prompt) for j in b]
    assert len(a) == len(b) == round(m["rate_rps"] * (m["warm_s"] + 30))


def test_chat_lengths_follow_the_mix():
    m = mix("chat")
    jobs = loadgen.open_poisson(m, 3, 400, 1000)
    p = np.array([len(j.prompt) for j in jobs])
    o = np.array([j.max_new_tokens for j in jobs])
    assert p.min() >= 64 and p.max() <= 512
    assert o.min() >= 16 and o.max() <= 256
    assert abs(np.median(p) - 160) <= 2 and abs(np.median(o) - 64) <= 2
    due = np.array([j.due for j in jobs])
    assert due[0] == -m["warm_s"] and (np.diff(due) > 0).all()
    rate = (len(due) - 1) / (due[-1] - due[0])
    assert abs(rate / m["rate_rps"] - 1) < 0.05


def test_closed_sessions_lengths():
    m = mix("longctx-decode")
    a = loadgen.closed_sessions(m, 7, 1000)
    b = loadgen.closed_sessions(m, 7, 1000)
    assert len(a) >= m["pool"]
    n = m["sessions"]
    first = sorted(len(j.prompt) for j in a[:n])
    other = loadgen.closed_sessions(m, 8, 1000)
    assert first == sorted(len(j.prompt) for j in other[:n])
    c = np.array([len(j.prompt) for j in a])
    assert c.min() >= 2048 and c.max() <= 3584
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    o = np.array([j.max_new_tokens for j in a])
    assert o.min() >= 384 and o.max() <= 512
    assert loadgen.engine_sizes(m)["max_seq_len"] >= c.max() + o.max()


def test_chat_engine_sized_like_the_launcher():
    assert loadgen.engine_sizes(mix("chat"))["max_seq_len"] == 512 + 256


def test_train_batch_is_the_programs_token_stream():
    from repro.data.synthetic import TokenStreamConfig, token_batch
    m = dict(mix("debias-train"), batch=4, seq_len=64)
    ours = loadgen.train_batch(m, 9, 3, 1000)
    theirs = token_batch(TokenStreamConfig(vocab=1000, seq_len=64,
                                           global_batch=4, seed=9), 3)
    assert (ours["inputs"] == np.asarray(theirs["inputs"])).all()
    assert (ours["labels"] == np.asarray(theirs["labels"])).all()
