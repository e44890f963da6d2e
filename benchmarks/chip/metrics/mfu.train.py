"""The whole step's share of the chip's peak: model FLOPs of the window's
tokens (``flops.train_flops_per_token``, PaLM's count) per second of the
window, over the bf16 peak."""

KIND = "train"


def read(ctx):
    if ctx["kind"] != KIND or ctx["model_flops"] <= 0:
        return None
    return 100.0 * ctx["model_flops"] / ctx["window_s"] / ctx["peaks"]["flops"]
