"""Work of the one-launch LUT attention kernel.

Operands: q (g, r, dh) with a KV head's query rows stacked, K transposed
(g, dh, t), V (g, t, dh), then the position masks and the multiplier's
table.  FLOPs are the score and value contractions over the whole
(r, t) rectangle, 2 * 2 g r t dh; bytes are every operand and the result."""
from bench.trace import nbytes


def work(operands, results):
    q, kt = operands[0], operands[1]
    g, r, dh = q[1][-3:]
    t = kt[1][-1]
    return (4.0 * g * r * t * dh,
            float(sum(nbytes(o) for o in operands) + sum(nbytes(x) for x in results)))
