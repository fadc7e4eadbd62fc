"""Test-sized configuration and mixes: the cells' shapes, scaled down."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def cfg(limits=None):
    c = {"name": "tiny", "qk_norm": False, "compute_dtype": "bfloat16",
         "model": {"hidden_size": 128, "intermediate_size": 256,
                   "num_hidden_layers": 2, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 32,
                   "vocab_size": 512, "rms_norm_eps": 1e-6,
                   "initializer_range": 0.02,
                   "rope_theta": 10000.0, "hidden_act": "silu",
                   "tie_word_embeddings": True},
         "compression": {"block": [16, 32], "sparsity": 0.9},
         "limits": dict(limits or {})}
    return c


def mix(name, **over):
    m = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    m = copy.deepcopy(m)
    if m["kind"] == "open_poisson":
        m["prompt_tokens"].update(median=20, min=8, max=48)
        m["output_tokens"].update(median=8, min=4, max=16)
        m["engine"].update(max_batch=8, prefill_chunk=16)
        m["check"]["served_tokens"] = 40
        m["rate_rps"] = 4
        m["warm_s"] = 1
    elif m["kind"] == "closed_sessions":
        m["context_tokens"].update(min=40, max=80)
        m["output_tokens"].update(min=20, max=30)
        m.update(sessions=4, pool=8)
        m["engine"].update(max_batch=4, prefill_chunk=16, token_budget=64,
                           max_seq_len=128)
        m["check"]["served_tokens"] = 40
    else:
        m.update(batch=2, seq_len=32)
    m.update(over)
    return m
