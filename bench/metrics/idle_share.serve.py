"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of device op intervals) / window."""


def read(ctx):
    s = ctx["summary"]
    if s.window_ns <= 0 or not s.ops:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
