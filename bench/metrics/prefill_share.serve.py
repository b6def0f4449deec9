"""Share of the window spent in THAPI's ``prefill`` spans (tally total)."""


def read(ctx):
    return 100.0 * ctx["prefill_span_s"] / ctx["window_s"]
