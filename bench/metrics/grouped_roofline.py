"""The grouped expert GEMM kernel's share of its roofline, in percent:
the summed least time of its launches (``bench/work/approx_gemm_grouped_impl.py``;
the larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth)
over their summed device time; None when the window holds no grouped
launch."""
from bench import trace as T
from bench.kernels import work_of

KERNEL = "approx_gemm_grouped_impl"


def read(ctx):
    work, peaks = work_of(KERNEL), ctx["peaks"]
    least, spent = 0.0, 0.0
    for op in T.kernels(ctx["summary"]):
        if work is None or T.kernel_name(op.text) != KERNEL:
            continue
        flops, nbytes = work(*T.shapes(op.text))
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
        spent += op.dur / 1e9
    return 100.0 * least / spent if spent > 0 else None
