"""Model FLOPs of the window's prefills over the time of THAPI's ``prefill``
spans (dispatch, device and the fence), as a share of the chip's peak: the
whole prefill step, which bounds the SSD kernel's roofline share."""


def read(ctx):
    if not ctx["prefill_span_s"]:
        return None
    return 100.0 * ctx["prefill_flops"] / ctx["prefill_span_s"] / ctx["peaks"].flops
