"""Operations and bytes of one ``paged_flash_attention`` call: the query
tokens of a tick against their slots' paged K/V.

Counted is causal attention over the actual context: a query token at
absolute position p meets p + 1 keys, or ``window`` at most, for QK' and
for PV (4 * heads * hd FLOPs per key). Bytes are each live slot's K and V
from the first key its first query token meets to its context end, read
once, plus the live query rows in and out. Pages past a slot's length or
below its window, slots with no token and padded query rows do not count.
"""
import numpy as np

from bench.work.model import attention_keys


def work(start_pos, n_tokens, heads: int, kv_heads: int, hd: int,
         kv_bytes: int, q_bytes: int = 4, o_bytes: int = 4, window=None
         ) -> tuple[float, float]:
    """(FLOPs, bytes) for one layer's call over a tick's slots:
    ``start_pos`` and ``n_tokens`` are per slot (0 tokens: idle slot)."""
    start = np.asarray(start_pos, np.int64)
    n = np.asarray(n_tokens, np.int64)
    live = n > 0
    flops = 4.0 * heads * hd * float(attention_keys(start, n, window))
    first = 0 if window is None else np.maximum(start - window + 1, 0)
    ctx = (start + n - first)[live].sum()
    nbytes = (2.0 * float(ctx) * kv_heads * hd * kv_bytes
              + float(n[live].sum()) * heads * hd * (q_bytes + o_bytes))
    return flops, nbytes
