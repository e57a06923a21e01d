"""The training step's model FLOPs over the step time and the chip's bf16
peak, in percent: the whole step's share of the peak."""


def read(ctx):
    c = ctx["counts"]
    if not c.get("steps"):
        return None
    return (100.0 * c["flops_per_step"] * c["steps"]
            / (c["window_s"] * ctx["peaks"]["bf16_flops_per_s"]))
