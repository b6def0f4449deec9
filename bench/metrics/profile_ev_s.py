"""Events of the window's THAPI trace over the seconds from the window's end
(``Tracer.stop()``) to the tally in hand."""


def read(ctx):
    return ctx["profile_ev_s"]
