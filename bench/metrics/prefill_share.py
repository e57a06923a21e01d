"""Device time of the admission prefill program over the device's busy
time in the window, in percent.  The prefill program is the XLA module
whose name starts with ``jit_paged_prefill``."""


def read(ctx):
    s = ctx["summary"]
    if s.busy_ns <= 0:
        return None
    pre = sum(d for name, _, d in s.modules if name.startswith("jit_paged_prefill"))
    return 100.0 * pre / s.busy_ns
