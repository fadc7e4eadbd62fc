"""FLOP and byte counts against hand counts at small shapes."""
import numpy as np
import pytest

from bench import readings, weights as W
from bench.tests import tiny
from bench.work import bsr_sddmm, bsr_spmm, model, paged_flash_attention


def test_spmm_hand_count():
    # 3 live rows, 2 stored (128, 128) blocks, (3, 256) @ W' -> (3, 384)
    f, b = bsr_spmm.work(3, 2, (128, 128), 256, 384, x_bytes=2, w_bytes=4)
    assert f == 2 * 3 * 2 * 128 * 128
    assert b == 3 * 256 * 2 + 2 * 128 * 128 * 4 + 3 * 384 * 4


def test_spmm_counts_live_rows_not_the_tile():
    # the kernel pads 3 rows to 128; the count stays at the 3 live rows
    f3, _ = bsr_spmm.work(3, 1, (128, 128), 128, 128, 2, 4)
    f128, _ = bsr_spmm.work(128, 1, (128, 128), 128, 128, 2, 4)
    assert f128 / f3 == pytest.approx(128 / 3)


def test_sddmm_hand_count():
    f, b = bsr_sddmm.work(16, 3, (128, 128), 256, 384, 2, 2)
    assert f == 2 * 16 * 3 * 128 * 128
    assert b == 16 * 256 * 2 + 16 * 384 * 2 + 3 * 128 * 128 * 4


def test_paged_attention_hand_count():
    # slot 0 decodes at position 9 (10 keys); slot 1 prefills 3 tokens at
    # positions 4..6 (5 + 6 + 7 keys); slot 2 idle
    f, b = paged_flash_attention.work([9, 4, 0], [1, 3, 0], heads=4,
                                      kv_heads=2, hd=8, kv_bytes=2)
    assert f == 4 * 4 * 8 * (10 + 18)
    ctx = 10 + 7
    assert b == 2 * ctx * 2 * 8 * 2 + 4 * 4 * 8 * (4 + 4)


def test_dead_pages_and_idle_slots_do_not_count():
    one = paged_flash_attention.work([9], [1], 4, 2, 8, 2)
    # the same live slot beside idle slots with stale start positions
    more = paged_flash_attention.work([9, 700, 300], [1, 0, 0], 4, 2, 8, 2)
    assert one == more


def test_model_serve_tick():
    # two decoding slots at positions 0 and 1, one idle
    f = model.serve_tick([0, 1, 5], [1, 1, 0], stored_weights=100,
                         head_weights=10, layers=2, heads=3, hd=4)
    assert f == 2 * 2 * 100 + 2 * 2 * 10 + 4 * 3 * 4 * 2 * (1 + 2)


def test_model_train_step():
    f = model.train_step(2, 3, stored_weights=100, head_weights=10,
                         layers=2, heads=3, hd=4)
    assert f == 6 * 6 * 110 + 3 * 4 * 3 * 4 * 2 * 2 * 6




def test_blocks_come_from_the_compressed_params():
    import jax
    from bench.serve import compressed_params, program_model
    cfg = tiny.cfg()
    _, m = program_model(cfg)
    cp = compressed_params(cfg, 5, m)
    got = readings.stored_blocks(cp)
    want = W.stored_blocks(cfg)
    for proj, per_layer in got.items():
        assert list(per_layer) == [want[proj]] * 2, proj
        leaf = W.projections(cfg)[proj]
        data = cp.sparse["layers"]["b0_attn"][leaf[0]][leaf[1]].data
        # stored slots beyond the count are zero padding (pad slot 0 too)
        nz = np.asarray(jax.device_get(data)).reshape(2, data.shape[1], -1)
        assert list((np.abs(nz).sum(-1) > 0).sum(-1)) == [want[proj]] * 2
