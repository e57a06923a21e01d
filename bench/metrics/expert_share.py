"""Device time of the grouped expert GEMM kernel's launches (forward, dx
and dw) over the device time of every kernel op in the window, in
percent; None when the window holds no grouped launch."""
from bench import trace as T

KERNEL = "approx_gemm_grouped_impl"


def read(ctx):
    ops = T.kernels(ctx["summary"])
    total = sum(o.dur for o in ops)
    mine = sum(o.dur for o in ops if T.kernel_name(o.text) == KERNEL)
    return 100.0 * mine / total if mine > 0 and total > 0 else None
