"""The control comes out not correct: the reference one precision below the
configuration's (float8 e4m3 for bfloat16), put in the program's place,
reads above the limit that sound runs of the program stay under.

At a test size on the CPU, on three seeds: an open-loop serving window of
the chat mix's shape, and the training cell's first three steps. The
limits here sit between this size's sound and control readings; the
cells' own limits come from readings on the chip at the cells' sizes
(PERF.md).
"""
import pytest

from bench import calibrate
from bench.tests import tiny

SERVE_LIMITS = {"logit_gap": 0.12, "mean_logit_gap": 0.002}
TRAIN_LIMITS = {"loss_gap": 0.008, "grad_norm_gap": 0.05,
                "change_norm_gap": 0.05}


def serve_cfg():
    c = tiny.cfg()
    c["model"].update(hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, vocab_size=2048)
    return c


@pytest.mark.parametrize("seed", [3, 2**31 + 1, 77])
def test_serve_control_fails(seed):
    mix = tiny.mix("chat", rate_rps=6)
    mix["output_tokens"].update(median=48, min=32, max=64)
    mix["check"].update(served_tokens=150, all_served=True)
    got = calibrate.serve_seed(serve_cfg(), mix, seed, 3, control=True)
    assert got["n_tokens"] >= 150
    assert all(got[k] <= v for k, v in SERVE_LIMITS.items())
    assert not all(got["control_" + k] <= v for k, v in SERVE_LIMITS.items())


@pytest.mark.parametrize("seed", [3, 2**31 + 1, 77])
def test_train_control_fails(seed):
    got = calibrate.train_seed(tiny.cfg(), tiny.mix("debias-train"), seed,
                               control=True, fault=False)
    assert all(got["sound"][k] <= v for k, v in TRAIN_LIMITS.items())
    # a reading that is no number (NaN) fails its limit too
    assert not all(got["control"][k] <= v for k, v in TRAIN_LIMITS.items())
