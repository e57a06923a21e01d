"""Device time of the LUT kernels tagged with the Mamba-2 site ("ssm": the
in and out projections, the chunked prefill's contractions and the
decode state update) over the device time of every kernel op in the
window, in percent; untagged kernels stay in the denominator
(bench/program_trace.py).  None where no kernel carries a tag."""
from bench.program_trace import kernel_share


def read(ctx):
    return kernel_share(ctx["summary"],
                        lambda tag: tag is not None and tag[0] == "ssm")
