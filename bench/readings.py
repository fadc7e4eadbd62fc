"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

Each reader gets a context: the run (its recorded tick plans, registry
snapshots at the window's open and close, the stored-block counts read
from the compressed params at set-up), the reduced trace and the chip's
peaks. These helpers turn those into kernel work, roofline shares and
registry deltas; a reader returns None where there is nothing to read.
"""
from __future__ import annotations

import numpy as np

from bench import weights as W
from bench.work import bsr_sddmm, bsr_spmm, paged_flash_attention


def stored_blocks(cp) -> dict:
    """{projection: blocks per layer} from the compressed params: the slots
    the BCSR gather tables reference (pad slots excluded)."""
    layer = cp.sparse["layers"]["b0_attn"]
    names = {("attn", n): n for n in ("wq", "wk", "wv", "wo")}
    names.update({("mlp", "wi"): "wi", ("mlp", "wg"): "wg",
                  ("mlp", "wo"): "mlp_wo"})
    out = {}
    for (group, leaf), proj in names.items():
        nnz = np.asarray(layer[group][leaf].gather_nnz)     # (L, R)
        out[proj] = nnz.reshape(nnz.shape[0], -1).sum(axis=1)
    return out


def roofline_pct(calls, device_s: float, peak: dict) -> float | None:
    """Share of the roofline: sum over calls of the least time the chip
    could take, max(FLOPs / peak FLOP/s, bytes / peak bytes/s), over the
    kernel's measured device time, in percent."""
    if not device_s or not calls:
        return None
    least = sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
                for f, b in calls)
    return 100.0 * least / device_s


def serve_spmm_calls(run) -> list:
    """(FLOPs, bytes) of every ``bsr_spmm`` call of the window's ticks."""
    cfg = run.cfg
    projs = W.projections(cfg)
    block = tuple(cfg["compression"]["block"])
    calls = []
    for _, _, n_tok in run.plans:
        rows = int(np.asarray(n_tok).sum())
        if rows == 0:
            continue
        for proj, (_, _, out, inp) in projs.items():
            for blocks in run.blocks[proj]:
                calls.append(bsr_spmm.work(rows, int(blocks), block, inp,
                                           out, x_bytes=2, w_bytes=4))
    return calls


def serve_attention_calls(run) -> list:
    """(FLOPs, bytes) of every ``paged_flash_attention`` call."""
    s = W.sizes(run.cfg)
    calls = []
    for _, start, n_tok in run.plans:
        one = paged_flash_attention.work(start, n_tok, s["H"], s["KV"],
                                         s["hd"], kv_bytes=2)
        if one[0] > 0:
            calls.extend([one] * s["L"])
    return calls


def hist_delta(run, name: str) -> tuple[int, float]:
    """(count, sum) a registry histogram gained in the window."""
    def cs(snap):
        rows = snap.get(name, {}).get("series", [])
        return (sum(r["count"] for r in rows), sum(r["sum"] for r in rows))
    c0, s0 = cs(run.reg_open)
    c1, s1 = cs(run.reg_close)
    return c1 - c0, s1 - s0


def counter_delta_by(run, name: str, label: str) -> dict:
    """{label value: increase in the window} of a labelled counter."""
    def vals(snap):
        return {r["labels"].get(label): r["value"]
                for r in snap.get(name, {}).get("series", [])}
    a, b = vals(run.reg_open), vals(run.reg_close)
    return {k: v - a.get(k, 0) for k, v in b.items() if v - a.get(k, 0)}


def train_calls(run, trace: dict) -> dict:
    """(FLOPs, bytes) of the kernel calls of the traced steps: per layer
    and projection one ``bsr_spmm`` input-gradient call, one ``bsr_sddmm``
    weight-gradient call, and as many forward ``bsr_spmm`` calls as the
    trace holds beyond those (a rematerialised forward runs twice).
    Operands are bfloat16 (the step casts weights and activations to the
    compute dtype); outputs float32."""
    cfg, mix = run.cfg, run.mix
    projs = W.projections(cfg)
    block = tuple(cfg["compression"]["block"])
    rows = int(mix["batch"]) * int(mix["seq_len"])
    steps = len(run.done_times)
    per_step = sum(len(run.blocks[p]) for p in projs)
    n_spmm = trace["kernel_calls"].get("bsr_spmm", 0)
    fwd = max(round(n_spmm / max(steps * per_step, 1)) - 1, 1)
    spmm, sddmm = [], []
    for proj, (_, _, out, inp) in projs.items():
        for blocks in run.blocks[proj]:
            b = int(blocks)
            f = bsr_spmm.work(rows, b, block, inp, out, 2, 2)
            t = bsr_spmm.work(rows, b, block, out, inp, 2, 2)
            spmm.extend([f] * fwd + [t])
            sddmm.append(bsr_sddmm.work(rows, b, block, out, inp, 2, 2))
    return {"bsr_spmm": spmm * steps, "bsr_sddmm": sddmm * steps}
