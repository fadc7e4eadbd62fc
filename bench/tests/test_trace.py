"""The trace reduction: on hand-made events, and on a trace of two ticks of
the chat cell recorded on one v5e (``data/chat_ticks.xplane.pb.xz``)."""
import lzma
from collections import namedtuple
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
Ev = namedtuple("Ev", "name start_ns duration_ns stats")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")


def ev(name, start, dur):
    return Ev(name, start, dur, {})


def test_op_names():
    assert T.op_name("%copy.173.remat = bf16[2] copy(x)") == "copy"
    assert T.op_name("%paged_flash_attention.10 = (f32[1])") == \
        "paged_flash_attention"
    assert T.kernel_of("%bsr_spmm.3 = f32[128,128] custom-call()") == \
        "bsr_spmm"
    assert T.kernel_of("%fusion.2 = f32[2] fusion(bsr_spmm.3)") is None


def test_reduction_by_hand():
    ops = [ev("%while.1 = (s32[])", 100, 500),        # holds the next two
           ev("%bsr_spmm.1 = f32[1]", 120, 100),
           ev("%paged_flash_attention.2 = f32[1]", 250, 200),
           ev("%fusion.7 = f32[1]", 900, 50)]
    host = [ev("bench.step", 50, 700), ev("bench.wait", 760, 130)]
    planes = [Plane("/device:TPU:0", [Line("XLA Ops", ops)]),
              Plane("/host:CPU", [Line("python3", host)])]
    red = T.reduce_planes(planes, t0_ns=0, t1_ns=1000)
    assert red["busy_s"] == pytest.approx(550e-9)   # [100,600) + [900,950)
    assert red["kernel_s"] == {"bsr_spmm": pytest.approx(100e-9),
                               "paged_flash_attention": pytest.approx(200e-9)}
    assert red["kernel_calls"] == {"bsr_spmm": 1, "paged_flash_attention": 1}
    ops_s = dict(red["device_ops"])
    assert ops_s["while"] == pytest.approx(200e-9)  # 500 less its body
    assert sum(ops_s.values()) == pytest.approx(red["busy_s"])
    # each gap is named by the annotation open where it begins
    gaps = [(n, round(s * 1e9)) for n, s in red["idle_gaps"]]
    assert gaps == [("bench.step", 300), ("host (no annotation)", 100),
                    ("host (no annotation)", 50)]


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = DATA / "chat_ticks.xplane.pb.xz"
    out = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    out.write_bytes(lzma.decompress(path.read_bytes()))
    return T.reduce_file(out)


def test_recorded_trace(chip_trace):
    red = chip_trace
    assert red["n_devices"] == 1
    calls = red["kernel_calls"]
    ticks = calls["paged_flash_attention"] // 32       # one call per layer
    assert ticks >= 1
    assert calls["paged_flash_attention"] == 32 * ticks
    assert calls["bsr_spmm"] == 7 * 32 * ticks         # 7 projections
    assert 0 < red["kernel_s"]["bsr_spmm"] < red["busy_s"]
    assert sum(v for _, v in red["device_ops"]) == \
        pytest.approx(red["busy_s"], rel=1e-6)
    assert all(v >= 0 for _, v in red["device_ops"])
    assert red["idle_gaps"] and red["idle_gaps"][0][1] > 0
