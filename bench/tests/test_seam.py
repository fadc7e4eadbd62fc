"""The architecture seam (``bench/arch/``) keeps the dense decoder as it was.

The pinned numbers were read from the harness before the seam existed, on
the CPU: the dense decoder's seeded weights at the test size, its
reference logits and the float8 control's, and the FLOP and byte counts
of the kernel calls and of MFU on fixed tick plans. The seam's answers for
the two benchmark configurations are held against the projections and
sizes the harness read from them then.
"""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import arch, readings, run as R, weights as W
from bench.serve import ServeRun
from bench.tests import tiny
from bench.train import TrainRun

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 5

# leaf: (shape, nonzeros, sum, sum of squares)
WEIGHTS = {
    "['embed']['embedding']": ((512, 128), 65536, 1.7864255026802454,
                               26.230603136428762),
    "['final_norm']['scale']": ((128,), 128, 128.0, 128.0),
    "['layers']['b0_attn']['attn']['wk']": ((2, 128, 2, 32), 2048,
                                            4.980275165027706,
                                            124.96000618146857),
    "['layers']['b0_attn']['attn']['wo']": ((2, 4, 32, 128), 3072,
                                            5.202068239130313,
                                            261.8302636954198),
    "['layers']['b0_attn']['attn']['wq']": ((2, 128, 4, 32), 3072,
                                            -10.940759318575147,
                                            268.88829201808255),
    "['layers']['b0_attn']['attn']['wv']": ((2, 128, 2, 32), 2048,
                                            -9.10734748755931,
                                            126.2970645917619),
    "['layers']['b0_attn']['ffn_norm']['scale']": ((2, 128), 256, 256.0,
                                                   256.0),
    "['layers']['b0_attn']['mlp']['wg']": ((2, 128, 256), 6144,
                                           -52.75025415624259,
                                           517.8191262436474),
    "['layers']['b0_attn']['mlp']['wi']": ((2, 128, 256), 6144,
                                           -4.367534827628333,
                                           519.0914538380068),
    "['layers']['b0_attn']['mlp']['wo']": ((2, 256, 128), 6144,
                                           -17.453647155602425,
                                           260.24354728186336),
    "['layers']['b0_attn']['pre_norm']['scale']": ((2, 128), 256, 256.0,
                                                   256.0),
}

# quant: (sum, sum of |x|, argmax per row, column 0) of the logits
LOGITS = {
    None: (-9.97577197665305, 358.0286290633194, [102, 328, 117, 315],
           [0.24077469110488892, 0.4411165416240692, 0.3687475919723511,
            0.33339476585388184]),
    "fp8": (-11.712646461837721, 358.498627425357, [102, 328, 117, 315],
            [0.2278873175382614, 0.42883503437042236, 0.33878493309020996,
             0.3596533238887787]),
}

# (calls, FLOPs, bytes) and readings on PLANS with blocks_for()
COUNTS = {
    "tiny": {
        "spmm": [56, 3963904.0, 1372160.0],
        "attention": [8, 1122304.0, 282624.0],
        "train_spmm": [126, 924844032.0, 226944000.0],
        "train_sddmm": [42, 308281344.0, 50632704.0],
        "spmm_roofline": 0.0003350818070818071,
        "attention_roofline": 6.901684981684982e-05,
        "step_mfu.serve": 1.1267465313028765e-07,
        "step_mfu.train": 0.0001384439683248731},
    "smollm-360m.bcsr90": {
        "spmm": [672, 31199068160.0, 1211957248.0],
        "attention": [96, 18574909440.0, 961249280.0],
        "train_spmm": [2016, 1105886969856.0, 30613143552.0],
        "train_sddmm": [672, 368628989952.0, 7028932608.0],
        "spmm_roofline": 0.29596025592185593,
        "attention_roofline": 0.2347373089133089,
        "step_mfu.serve": 0.0008581674450084603,
        "step_mfu.train": 0.05797464926862944},
    "qwen3-0.6b.bcsr90": {
        "spmm": [588, 42104258560.0, 1499660288.0],
        "attention": [84, 34673164288.0, 2572222464.0],
        "train_spmm": [1764, 1492434026496.0, 35960881152.0],
        "train_sddmm": [588, 497478008832.0, 8236892160.0],
        "spmm_roofline": 0.36621740854700857,
        "attention_roofline": 0.6281373538461538,
        "step_mfu.serve": 0.001351760949712352,
        "step_mfu.train": 0.14064757474111675},
}
PLANS = {  # (width, start_pos, n_tokens) of four slots
    "short": [(16, [0, 0, 0, 0], [16, 16, 0, 0]),
              (16, [16, 16, 0, 0], [8, 16, 16, 0]),
              (1, [24, 32, 16, 5], [1, 1, 1, 1]),
              (1, [25, 33, 17, 6], [1, 0, 1, 1])],
    "long": [(256, [0, 2048, 3000, 0], [256, 1, 1, 0]),
             (1, [256, 2049, 3001, 3583], [1, 1, 1, 1]),
             (256, [257, 2050, 3002, 3584], [256, 1, 0, 1])],
}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

# what the harness read from the configurations before the seam:
# projection: (group, leaf, out, in); and (L, H, KV, hd)
PROJECTIONS = {
    "smollm-360m.bcsr90": (
        {"wq": ("attn", "wq", 960, 960), "wk": ("attn", "wk", 320, 960),
         "wv": ("attn", "wv", 320, 960), "wo": ("attn", "wo", 960, 960),
         "wi": ("mlp", "wi", 2560, 960), "wg": ("mlp", "wg", 2560, 960),
         "mlp_wo": ("mlp", "wo", 960, 2560)}, (32, 15, 5, 64)),
    "qwen3-0.6b.bcsr90": (
        {"wq": ("attn", "wq", 2048, 1024), "wk": ("attn", "wk", 1024, 1024),
         "wv": ("attn", "wv", 1024, 1024), "wo": ("attn", "wo", 1024, 2048),
         "wi": ("mlp", "wi", 3072, 1024), "wg": ("mlp", "wg", 3072, 1024),
         "mlp_wo": ("mlp", "wo", 1024, 3072)}, (28, 16, 8, 128)),
}


def config(name):
    if name == "tiny":
        return tiny.cfg()
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_dense_weights_pinned():
    import jax
    cfg = tiny.cfg()
    w = arch.of(cfg).weights(cfg, SEED)
    got = {jax.tree_util.keystr(p): np.asarray(x)
           for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert sorted(got) == sorted(WEIGHTS)
    for name, (shape, nnz, total, sq) in WEIGHTS.items():
        x = got[name].astype(np.float64)
        assert (x.shape, int((x != 0).sum())) == (shape, nnz), name
        assert x.sum() == pytest.approx(total, rel=1e-6, abs=1e-9), name
        assert (x * x).sum() == pytest.approx(sq, rel=1e-6), name


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_reference_logits_pinned(quant):
    import jax
    cfg = tiny.cfg()
    a = arch.of(cfg)
    w = a.weights(cfg, SEED)
    toks = np.random.default_rng(5).integers(0, 512, 48).astype(np.int32)
    at = np.array([3, 17, 30, 39], np.int32)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(a.reference.logits_at(w, cfg, toks, 40, at,
                                              quant=quant), np.float64)
    total, absum, argmax, first = LOGITS[quant]
    assert lg.sum() == pytest.approx(total, rel=1e-5)
    assert np.abs(lg).sum() == pytest.approx(absum, rel=1e-5)
    assert lg.argmax(-1).tolist() == argmax
    assert lg[:, 0] == pytest.approx(first, rel=1e-5)


def blocks_for(cfg, projs):
    """Stored blocks per layer: the configuration's count, less 0-2 by
    layer, so that each layer's calls differ."""
    comp = cfg["compression"]
    return {name: np.array([W.kept_blocks(p.out, p.inp, tuple(comp["block"]),
                                          comp["sparsity"])[2] - (i % 3)
                            for i in range(p.layers)])
            for name, p in projs.items()}


def summed(calls):
    return [len(calls), sum(f for f, _ in calls), sum(b for _, b in calls)]


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_pinned(name):
    cfg = config(name)
    plans = PLANS["short" if name == "tiny" else "long"]
    r = ServeRun(cfg, tiny.mix("longctx-decode"), 1, 1)
    r.plans = [(w, np.array(s), np.array(n)) for w, s, n in plans]
    r.blocks = blocks_for(cfg, r.arch.projections(cfg))
    t = TrainRun(cfg, tiny.mix("debias-train", batch=4, seq_len=512), 1, 1)
    t.blocks = r.blocks
    t.done_times = [1.0, 2.0, 3.0]
    per_step = sum(len(v) for v in t.blocks.values())
    train = readings.train_calls(
        t, {"kernel_calls": {"bsr_spmm": 3 * 3 * per_step}})
    spmm = readings.serve_spmm_calls(r)
    att = readings.serve_attention_calls(r)
    got = {"spmm": summed(spmm), "attention": summed(att),
           "train_spmm": summed(train["bsr_spmm"]),
           "train_sddmm": summed(train["bsr_sddmm"]),
           "spmm_roofline": readings.roofline_pct(spmm, 0.5, PEAK),
           "attention_roofline": readings.roofline_pct(att, 0.5, PEAK),
           "step_mfu.serve": reader("step_mfu.serve")(
               {"run": r, "trace": {"window_s": 30.0}, "peak": PEAK}),
           "step_mfu.train": reader("step_mfu.train")(
               {"run": t, "trace": {"window_s": 30.0}, "peak": PEAK})}
    assert sorted(got) == sorted(COUNTS[name])
    for key, want in COUNTS[name].items():
        assert got[key] == pytest.approx(want, rel=1e-12), key


@pytest.mark.parametrize("name", sorted(PROJECTIONS))
def test_seam_answers_for_the_configurations(name):
    cfg = config(name)
    a = arch.of(cfg)
    projs, (L, H, KV, hd) = PROJECTIONS[name]
    got = a.projections(cfg)
    assert list(got) == list(projs)
    for proj, (group, leaf, out, inp) in projs.items():
        assert got[proj] == arch.Projection(("layers", "b0_attn", group, leaf),
                                            out, inp, L)
    assert a.attention(cfg) == [arch.Attention(L, H, KV, hd, None)]


def test_unknown_architecture_exits_before_device_work(tmp_path,
                                                       monkeypatch):
    cfg = dict(tiny.cfg(), architecture="no-such-decoder")
    (tmp_path / "c.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "c.json"}],
        "workloads": [{"name": "w", "config": "c", "traffic": "chat",
                       "chips": 1}]}))
    monkeypatch.setattr(R, "CHECKOUT", tmp_path)
    monkeypatch.setattr(sys, "path", list(sys.path))    # main() prepends

    def device_work(*a, **k):
        raise AssertionError("device work before the configuration loaded")
    monkeypatch.setattr(R, "enable_compile_cache", device_work)
    monkeypatch.setattr(R, "accelerator", device_work)
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "w", "--seed", "1", "--seconds", "1"])
    # a message, not 0 or None, makes the process exit with code 1
    assert isinstance(e.value.code, str)
    assert "no-such-decoder" in e.value.code and "llama" in e.value.code
    del cfg["architecture"]
    with pytest.raises(SystemExit, match="known architectures"):
        arch.of(cfg)

