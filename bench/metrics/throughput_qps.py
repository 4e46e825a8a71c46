"""Requests answered EXACT per second of the window.  Each backend call's
EXACT requests count by the share of the call's time inside the window,
so the micro-batches that straddle its edges count for the work they did
inside it, and the rate does not jump by a whole micro-batch with the
phase at which the window closes."""


def read(ctx):
    return ctx.exact_in_window / ctx.seconds
