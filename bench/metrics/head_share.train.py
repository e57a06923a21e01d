"""Device time of the LUT kernels tagged with the LM head's site ("head",
or "unembed" for a head tied to the embedding) over the device time of
every kernel op in the window, in percent; untagged kernels stay in the
denominator (bench/program_trace.py)."""
from bench.program_trace import kernel_share


def read(ctx):
    return kernel_share(ctx["summary"],
                        lambda tag: tag is not None and tag[0] in ("head", "unembed"))
