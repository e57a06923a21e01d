"""Share of the admission prefills' tokens that are bucket padding, in
percent: (bucket tokens - true tokens) / bucket tokens, summed over the
``engine.prefill`` spans in the window, whose ``bucket`` and ``tokens``
fields are the engine's counts for that admission
(bench/program_trace.py)."""
from bench.program_trace import span_stats


def read(ctx):
    stats = [st for st in span_stats(ctx, "engine.prefill")
             if "bucket" in st and "tokens" in st]
    padded = sum(st["bucket"] for st in stats)
    if not padded:
        return None
    return 100.0 * (padded - sum(st["tokens"] for st in stats)) / padded
