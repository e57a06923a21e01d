"""The 95th percentile of every gap between consecutive tokens of a
request that ends in the window, in ms.  All live slots share a tick, so
the gaps come in blocks of one tick's length: the percentile steps
between the lengths of ticks with one and with two admission prefills."""
import numpy as np


def read(ctx):
    gaps = ctx["counts"].get("gaps")
    if not gaps:
        return None
    return 1e3 * float(np.percentile(gaps, 95))
