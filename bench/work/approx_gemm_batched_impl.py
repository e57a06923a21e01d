"""Work of the batched LUT GEMM kernel: (g, m, k) @ (g, k, n), float32.

FLOPs are 2 g m k n; bytes are the two operands, the multiplier's table
(the last operand) and the (g, m, n) float32 result."""
from bench.trace import nbytes


def work(operands, results):
    a, b, table = operands[-3], operands[-2], operands[-1]
    g, m, k = a[1][-3:]
    n = b[1][-1]
    return (2.0 * g * m * k * n,
            float(nbytes(a) + nbytes(b) + nbytes(table) + 4 * g * m * n))
