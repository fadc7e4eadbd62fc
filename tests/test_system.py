"""End-to-end system behaviour: the full paper pipeline via the public
launchers, and dry-run cell coverage accounting."""
import json
import os

import pytest

from repro.configs import ARCH_IDS, get_config, shapes_for


def test_paper_pipeline_via_launcher(tmp_path):
    from repro.launch.train import main as train_main
    state, hist, hist_db, report = train_main([
        "--arch", "smollm-360m", "--reduced", "--steps", "30",
        "--debias-steps", "10", "--compress", "l1:2.0", "--lr", "3e-3",
        "--log-every", "10", "--ckpt-dir", str(tmp_path)])
    assert report["spc"]["compression_rate"] > 0.2
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert os.listdir(str(tmp_path))      # checkpoints written


def test_serve_launcher(tmp_path):
    from repro.launch.serve import main as serve_main
    out = serve_main(["--arch", "smollm-360m", "--reduced", "--batch", "2",
                      "--prompt-len", "4", "--gen", "6", "--sparse"])
    assert out.shape == (2, 6)


def test_serve_logits_and_hlo_out(tmp_path):
    """--logits-out saves the prefill logits, --hlo-out that prefill's
    compiled HLO text; --hlo-out without --logits-out is refused."""
    import numpy as np

    from repro.launch.serve import main as serve_main
    from repro.models.model_zoo import build
    logits, hlo = tmp_path / "logits.npy", tmp_path / "prefill.hlo.txt"
    argv = ["--arch", "smollm-360m", "--reduced", "--batch", "2",
            "--prompt-len", "4", "--gen", "1", "--sparse",
            "--block", "8", "64", "--hlo-out", str(hlo)]
    serve_main([*argv, "--logits-out", str(logits)])
    vocab = build("smollm-360m", reduced=True).cfg.vocab
    assert np.load(logits).shape == (2, vocab)
    assert "HloModule" in hlo.read_text()
    with pytest.raises(SystemExit, match="--logits-out"):
        serve_main(argv)


def test_cell_coverage_definition():
    """40 assigned cells: 32 runnable + 8 documented long_500k skips."""
    runnable = sum(len(shapes_for(get_config(a))) for a in ARCH_IDS)
    assert runnable == 32
    skipped = sum(1 for a in ARCH_IDS
                  if not get_config(a).sub_quadratic)
    assert skipped == 8
    assert runnable + skipped == 40


@pytest.mark.skipif(not os.path.isdir("experiments/dryrun"),
                    reason="dry-run artifacts not present")
def test_dryrun_artifacts_all_ok():
    import glob
    cells = glob.glob("experiments/dryrun/*.json")
    assert len(cells) >= 64
    for path in cells:
        r = json.load(open(path))
        assert r.get("ok"), f"{r['cell']}: {r.get('error')}"
        if r["mesh"] == "multi":
            assert r["chips"] == 512
        roof = r["roofline"]
        assert roof["flops_per_device"] > 0
        assert roof["dominant"] in ("compute", "memory", "collective")


def test_chip_smoke_refuses_a_cpu(tmp_path):
    """chip_smoke.py is the on-chip proof: off a TPU it exits non-zero,
    names the platform, and prints no result line."""
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and the helper sets nothing; without
    it the cache lives at the fixed <checkout>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    assert enable_compile_cache() == "cache-from-env"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = str(CHECKOUT / ".jax_cache")
        assert enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
        assert (CHECKOUT / "src" / "repro" / "launch").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
