"""2 N per prompt or decoded token processed in the window, over the
window and the chip's bf16 peak, in percent."""


def read(ctx):
    c = ctx["counts"]
    tokens = c.get("prompt_tokens", 0) + c.get("decode_tokens", 0)
    if not tokens:
        return None
    return (100.0 * c["flops_per_token"] * tokens
            / (c["window_s"] * ctx["peaks"]["bf16_flops_per_s"]))
