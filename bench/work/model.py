"""The model's work, for model FLOP utilization (``step_mfu.*``).

Counted is what the model requires, not what the implementation does:
2 FLOPs per stored weight per token forward (6 in training: forward,
input gradient, weight gradient), the tied head on the rows that produce
logits, and causal attention over the actual context lengths. Stored
weights are the compressed params' stored blocks; recomputation (remat),
padded rows and idle slots do not count.
"""
import numpy as np


def attention_keys(start_pos, n_tokens, window=None) -> int:
    """Keys met by all live query tokens (a token at position p meets
    p + 1 keys, or ``window`` at most)."""
    start = np.asarray(start_pos, np.int64)
    n = np.asarray(n_tokens, np.int64)
    return int((_keys_below(start + n, window)
                - _keys_below(start, window)).sum())


def _keys_below(m, window):
    """Keys met by the query tokens at positions 0 .. m - 1."""
    if window is None:
        return m * (m + 1) // 2
    w = np.minimum(m, window)
    return w * (w + 1) // 2 + (m - w) * window


def serve_tick(start_pos, n_tokens, stored_weights: int, head_weights: int,
               layers: int, heads: int, hd: int) -> float:
    """FLOPs of one engine tick. ``stored_weights`` is summed over every
    layer; the head runs once per slot that carries a token."""
    n = np.asarray(n_tokens, np.int64)
    tokens, slots = int(n.sum()), int((n > 0).sum())
    return (2.0 * tokens * stored_weights + 2.0 * slots * head_weights
            + 4.0 * heads * hd * layers * attention_keys(start_pos, n))


def train_step(batch: int, seq: int, stored_weights: int, head_weights: int,
               layers: int, heads: int, hd: int) -> float:
    """FLOPs of one training step on ``batch`` rows of ``seq`` tokens."""
    tokens = batch * seq
    keys = batch * seq * (seq + 1) // 2
    return (6.0 * tokens * (stored_weights + head_weights)
            + 3.0 * 4.0 * heads * hd * layers * keys)
