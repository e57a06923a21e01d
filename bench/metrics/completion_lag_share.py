"""Share of the window in which the serving engine waited for a result
the device had already finished, in percent: for each ``engine.*.wait``
span, its end minus the end of the last device program run started
before it ended, summed over the window (bench/program_trace.py)."""
from bench.program_trace import completion_lags


def read(ctx):
    s = ctx["summary"]
    lags = completion_lags(s)
    if not lags or s.window_ns <= 0:
        return None
    return 100.0 * sum(lags) / s.window_ns
