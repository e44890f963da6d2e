"""The whole step's share of the chip's peak: model FLOPs of the window's
tokens (``flops.decode_flops``: 2 N plus 4 L d ctx per token) per second of the
window, over the bf16 peak."""

KIND = "decode"


def read(ctx):
    if ctx["kind"] != KIND or ctx["model_flops"] <= 0:
        return None
    return 100.0 * ctx["model_flops"] / ctx["window_s"] / ctx["peaks"]["flops"]
