"""Device time of the kernel ops that carry no (site, pass) tag over the
device time of every kernel op in the window, in percent: 0 when every
LUT kernel launch is tagged (bench/program_trace.py)."""
from bench.program_trace import kernel_share


def read(ctx):
    return kernel_share(ctx["summary"], lambda tag: tag is None)
