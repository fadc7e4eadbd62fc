"""Operations and bytes of one ``bsr_sddmm`` call: the weight gradient
dW = dY' @ X at the stored blocks of a BCSR matrix only.

Counted: live rows (not the 128-row tile padding), stored blocks (not pad
slots), each operand once at its model width and dtype.
"""


def work(rows: int, blocks: int, block: tuple, n_out: int, k_in: int,
         dy_bytes: int, x_bytes: int, g_bytes: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes): (rows, n_out) dY and (rows, k_in) X contracted over
    rows into ``blocks`` stored (br, bc) gradient blocks."""
    br, bc = block
    flops = 2.0 * rows * blocks * br * bc
    nbytes = (rows * n_out * dy_bytes + rows * k_in * x_bytes
              + blocks * br * bc * g_bytes)
    return flops, float(nbytes)
