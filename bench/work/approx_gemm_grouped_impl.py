"""Work of the grouped LUT GEMM kernel over rows sorted by expert.

Operands, from the end: the multiplier's table, two matrices, and before
them the routed rows' positions (T*k,).  Forward and dx: sorted rows
(R, k) times expert weights (E, k, n), result (R, n).  dw: the sorted
rows transposed and their output gradient, result (E, k, n) (written
into a layer-stacked gradient where XLA fuses it so).  R is a static
bound with each expert's padding to the row tile; the work counts the
T*k routed rows only.  FLOPs are 2 (T*k) k n; bytes are the routed rows
of the row operands and results, the weights or weight gradient, and
the table."""
from bench.trace import nbytes


def work(operands, results):
    rows, a, b, table = operands[-4:]
    routed = rows[1][0]
    out = results[0][1]
    if len(out) >= 3:                      # dw: (E, k, n)
        E, k, n = out[-3:]
        moved = 4 * routed * n + 4 * E * k * n
    else:                                  # rows (R, k) @ weights (E, k, n)
        k, n = a[1][-1], out[-1]
        moved = nbytes(b) + 4 * routed * n
    return 2.0 * routed * k * n, float(4 * routed * k + moved + nbytes(table))
