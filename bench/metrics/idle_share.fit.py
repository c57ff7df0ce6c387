"""Share, in %, of one whole fit's wall time (the "fit" span) in which
the device ran no operation: 1 - union of op intervals / span."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * s.idle_share if s.window_s > 0 else None
