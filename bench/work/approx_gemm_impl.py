"""Work of the 2-D LUT GEMM kernel: (m, k) @ (k, n), float32 operands.

The operands that carry the GEMM are the two before the multiplier's
table (the last operand); a fusion XLA builds around the kernel may put
an output buffer and an index in front of them.  FLOPs are 2 m k n;
bytes are the two operands, the table and the (m, n) float32 result."""
from bench.trace import nbytes


def work(operands, results):
    a, b, table = operands[-3], operands[-2], operands[-1]
    m, k = a[1][-2:]
    n = b[1][-1]
    return 2.0 * m * k * n, float(nbytes(a) + nbytes(b) + nbytes(table) + 4 * m * n)
