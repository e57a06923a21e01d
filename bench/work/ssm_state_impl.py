"""Work of the Mamba-2 state-update kernel: one decode step of one layer
of the stacked state (layers, slots, N, heads x head_dim).

Operands: the prefetched layer index, the stacked state, x dt and the
decays (slots, 1, heads x head_dim), B and C (slots, 1, N), then the
multiplier's table.  Only the one layer's state is read and written.
FLOPs are two simulated products and two adds per state element; bytes
are the layer's state in and out, every other operand and the result y
(slots, 1, heads x head_dim)."""
from bench.trace import nbytes


def work(operands, results):
    state = max(operands, key=nbytes, default=None)
    if state is None or len(state[1]) != 4:
        return 0.0, 0.0
    _, slots, n, lanes = state[1]
    elems = slots * n * lanes
    rest = sum(nbytes(o) for o in operands if o is not state)
    return 4.0 * elems, float(2 * 4 * elems + rest + 4 * slots * lanes)
