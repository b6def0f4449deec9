"""Model FLOPs of every token served in the window (prefill in its chunked
form, decode through the recurrent step; ``bench/flops.py``) over the
window's seconds, as a share of the chip's peak."""


def read(ctx):
    return 100.0 * ctx["model_flops"] / ctx["window_s"] / ctx["peaks"].flops
