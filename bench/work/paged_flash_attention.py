"""Operations and bytes of one ``paged_flash_attention`` call: the query
tokens of a tick against their slots' paged K/V.

Counted is causal attention over the actual context: a query token at
absolute position p meets p + 1 keys, for QK' and for PV (4 * heads * hd
FLOPs per key). Bytes are each live slot's K and V up to its context end,
read once, plus the live query rows in and out. Pages past a slot's
length, slots with no token and padded query rows do not count.
"""
import numpy as np


def work(start_pos, n_tokens, heads: int, kv_heads: int, hd: int,
         kv_bytes: int, q_bytes: int = 4, o_bytes: int = 4
         ) -> tuple[float, float]:
    """(FLOPs, bytes) for one layer's call over a tick's slots:
    ``start_pos`` and ``n_tokens`` are per slot (0 tokens: idle slot)."""
    start = np.asarray(start_pos, np.int64)
    n = np.asarray(n_tokens, np.int64)
    live = n > 0
    # sum over the slot's query tokens of (position + 1) keys
    keys = (n * start + n * (n + 1) // 2)[live].sum()
    flops = 4.0 * heads * hd * float(keys)
    ctx = (start + n)[live].sum()
    nbytes = (2.0 * float(ctx) * kv_heads * hd * kv_bytes
              + float(n[live].sum()) * heads * hd * (q_bytes + o_bytes))
    return flops, nbytes
