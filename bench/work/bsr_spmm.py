"""Operations and bytes of one ``bsr_spmm`` call: Y = X @ W' (forward) or
dX = dY @ W (the transposed call), W a BCSR matrix.

Counted is the model's work, not the implementation's: only rows that
carry a token (not the rows the kernel pads to its 128-row tile), only the
stored blocks (not the pad slot or the padding slots of a stack), and each
operand's bytes once, at the width and dtype it has in the model. Padding
or tiling a later change adds cannot raise the count, so a share of the
roofline built on it cannot pass 100 %.
"""


def work(rows: int, blocks: int, block: tuple, k_in: int, n_out: int,
         x_bytes: int, w_bytes: int, y_bytes: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one call: ``rows`` live rows of a (rows, k_in)
    operand against ``blocks`` stored (br, bc) blocks, giving (rows, n_out)."""
    br, bc = block
    flops = 2.0 * rows * blocks * br * bc
    nbytes = (rows * k_in * x_bytes + blocks * br * bc * w_bytes
              + rows * n_out * y_bytes)
    return flops, float(nbytes)
