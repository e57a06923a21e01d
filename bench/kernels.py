"""Least time of the Pallas calls in a trace, from the work of what each
call computes (``bench/work/<kernel>.py``, found by the kernel's name)."""
from __future__ import annotations

from bench import harness
from bench import trace as T


def work_of(name: str):
    path = harness.BENCH / "work" / f"{name}.py"
    return harness.load_module(path).work if path.exists() else None


def roofline_share(summary, peaks) -> float | None:
    """Summed least time over summed device time of every kernel call whose
    work is known, in percent; None when there is none.  A call's least
    time is the larger of its FLOPs over the bf16 peak and its bytes over
    the HBM bandwidth."""
    least, spent = 0.0, 0.0
    for op in T.kernels(summary):
        work = work_of(T.kernel_name(op.text))
        if work is None:
            continue
        flops, nbytes = work(*T.shapes(op.text))
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
        spent += op.dur / 1e9
    return 100.0 * least / spent if spent > 0 else None
