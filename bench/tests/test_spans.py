"""The readers of the program's own spans and scopes (``bench/spans.py``):
on hand-made planes, on the trace of two chat ticks recorded on one v5e
before the spans existed (``data/chat_ticks.xplane.pb.xz``), and on a trace
of long-context ticks from the scoped program recorded on one v5e
(``data/longctx_ticks.xplane.pb.xz``)."""
import importlib.util
import lzma
from collections import namedtuple
from pathlib import Path

import pytest

from bench import spans as S
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
READERS = ("tick_host_ms.serve", "device_wait_host_ms.serve",
           "attention_ms.train", "head_loss_ms.train")
Ev = namedtuple("Ev", "name start_ns duration_ns stats")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")


def ev(name, start, end, **stats):
    return Ev(name, start, end - start, stats)


def reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name}",
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_scope_of():
    assert S.scope_of("jit(step)/transpose(jvp(layers))/while/body/"
                      "closed_call/checkpoint/attention/dot_general:") == \
        "attention"
    assert S.scope_of("jit(step)/transpose(jvp(loss))/log_softmax:") == "loss"
    assert S.scope_of("jit(step)/jvp(layers)/while/body/add:") == "layers"
    assert S.scope_of("jit(step)/convert_element_type:") == S.NO_SCOPE
    assert S.scope_of(None) == S.NO_SCOPE


def hand_made():
    """Two ticks of one program (id 7) on a device whose clock reads 12 ns
    behind the host's (launches give at least 10, completions at most 14):
    module 1 runs [100, 200), module 2 [300, 400) with a 3 ns hole at 350.
    The window is the two ``bench.step``s, [40, 460); a third tick starts
    before it, and nothing runs on the host in [250, 270)."""
    ops = [ev("%fusion.1 = f32[2] fusion(x)", 100, 200),
           ev("%bsr_spmm.2 = f32[2] custom-call(x)", 300, 350),
           ev("%fusion.3 = f32[2] fusion(y)", 353, 400)]
    mods = [ev("jit__step(7)", 100, 200, _c=1, run_id=11),
            ev("jit__step(7)", 300, 400, _c=2, run_id=12)]
    host = [ev("DoEnqueueProgram", 110, 111, _p=1),
            ev("CompleteCallbacks", 215, 216, _c=1),
            ev("DoEnqueueProgram", 310, 311, _p=2),
            ev("CompleteCallbacks", 414, 415, _c=2),
            ev("bench.step", 40, 250), ev("bench.step", 270, 460),
            ev("engine.tick", 20, 45, tick=0, width=1),
            ev("engine.schedule", 20, 45),
            ev("engine.tick", 50, 250, tick=1, width=1, tokens=2),
            ev("engine.schedule", 50, 90), ev("engine.prepare", 90, 108),
            ev("engine.dispatch", 108, 112), ev("engine.sync", 112, 214),
            ev("engine.bookkeep", 214, 240),
            ev("engine.tick", 270, 460, tick=2, width=1, tokens=2),
            ev("engine.schedule", 270, 290), ev("engine.prepare", 290, 306),
            ev("engine.dispatch", 306, 311), ev("engine.sync", 311, 413),
            ev("engine.bookkeep", 413, 450)]
    planes = [Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                      Line("XLA Ops", ops)]),
              Plane("/host:CPU", [Line("python3", host)])]
    meta = {(7, "%fusion.1 = f32[2] fusion(x)"):
            "jit(_step)/jvp(layers)/while/body/attention/mul:",
            (7, "%bsr_spmm.2 = f32[2] custom-call(x)"):
            "jit(_step)/jvp(layers)/while/body/mlp/pallas_call:",
            (7, "%fusion.3 = f32[2] fusion(y)"): "jit(_step)/head/dot:"}
    return S.Spans(planes, meta)


def test_alignment_and_window_by_hand():
    sp = hand_made()
    assert (sp.offset_ns, sp.width_ns) == (12, 4)
    assert sp.window == (40, 460)


def test_ticks_by_hand():
    ticks = hand_made().ticks()
    # the tick that began before the window is left out
    assert [t["args"]["tick"] for t in ticks] == [1, 2]
    assert ticks[0]["phases"] == {"engine.schedule": 40,
                                  "engine.prepare": 18,
                                  "engine.dispatch": 4, "engine.sync": 102,
                                  "engine.bookkeep": 26}


def test_idle_by_hand():
    sp = hand_made()
    assert sp.idle_gaps() == [(40, 112), (212, 312), (362, 365), (412, 460)]
    # each ns of idle device goes to the innermost host span open then; the
    # 3 ns hole is narrower than the bound, [250, 270) has no span at all
    assert sp.idle_by_span() == {
        "total": 223, "unattributed": 3 + 20,
        "engine.schedule": 5 + 40 + 20, "engine.prepare": 18 + 16,
        "engine.dispatch": 4 + 5, "engine.sync": 2 + 1 + 1,
        "engine.bookkeep": 26 + 37, "engine.tick": 10 + 10, "bench": 5}


def test_scopes_by_hand():
    assert hand_made().scope_ms_per_run() == {
        "attention": pytest.approx(50e-6), "mlp": pytest.approx(25e-6),
        "head": pytest.approx(23.5e-6)}


def test_readers_by_hand(monkeypatch):
    sp = hand_made()
    monkeypatch.setattr(S, "current", lambda: sp)
    got = {name: reader(name)({}) for name in READERS}
    assert got == {
        "tick_host_ms.serve": pytest.approx((98 + 88) / 2 * 1e-6),
        "device_wait_host_ms.serve":
            pytest.approx((65 + 34 + 9 + 63) / 2 * 1e-6),
        "attention_ms.train": pytest.approx(50e-6),
        "head_loss_ms.train": pytest.approx(23.5e-6)}


def test_readers_without_spans(monkeypatch):
    """A program without the spans and scopes reads as None."""
    plain = hand_made()
    plain.host = [h for h in plain.host if not h[2].startswith("engine.")]
    plain.metadata = {}
    for sp in (plain, None):
        monkeypatch.setattr(S, "current", lambda: sp)
        assert [reader(name)({}) for name in READERS] == [None] * 4


def unpack(name, tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    out.write_bytes(lzma.decompress((DATA / name).read_bytes()))
    return out


def test_alignment_on_chat_trace(tmp_path_factory):
    """The two clocks of the chat trace: the device's reads behind the
    host's by 1.0-2.2 ms, and the bound says so."""
    sp = S.load(str(unpack("chat_ticks.xplane.pb.xz", tmp_path_factory)))
    lo = sp.offset_ns - sp.width_ns / 2
    hi = sp.offset_ns + sp.width_ns / 2
    assert 1.0e6 <= lo < hi <= 2.2e6


@pytest.fixture(scope="module")
def longctx(tmp_path_factory):
    return unpack("longctx_ticks.xplane.pb.xz", tmp_path_factory)


def test_recorded_longctx_gaps_named(longctx):
    """Three long-context ticks: every idle gap wider than the alignment's
    bound lies in the host's spans (the benchmark's loop between two
    ``bench.step``s is a few microseconds), nine tenths of the idle time
    in the engine's."""
    sp = S.load(str(longctx))
    assert 0 < sp.width_ns < 1e6 and len(sp.ticks()) == 3
    cover = T._union((s, e) for s, e, _, _ in sp.host)
    wide = [(s, e) for s, e in sp.idle_gaps() if e - s >= sp.width_ns]
    assert len(wide) >= 6
    for s, e in wide:
        named, _ = S._cut([(s, e)], cover)
        assert named >= 0.99 * (e - s)
    idle = sp.idle_by_span()
    engine = sum(v for k, v in idle.items() if k.startswith("engine."))
    assert engine >= 0.9 * idle["total"]
    assert idle["engine.prepare"] == max(
        idle[p] for p in S.HOST_PHASES + (S.SYNC,))


def test_recorded_longctx_readers(longctx, monkeypatch):
    monkeypatch.setattr(S, "TRACE_ROOT", longctx.parent)
    got = {name: reader(name)({}) for name in READERS}
    assert 1 < got["device_wait_host_ms.serve"] \
        <= got["tick_host_ms.serve"] < 20
    # the tick's device time is paged attention's, under ``attention``
    assert 200 < got["attention_ms.train"] < 400
    assert 0 < got["head_loss_ms.train"] < 5
    times = S.load(str(longctx)).scope_ms_per_run()
    assert max(times, key=times.get) == "attention"
    assert times[S.NO_SCOPE] < 0.1 * sum(times.values())
