"""Median over the window's backend calls of (call wall time - device
busy time inside the call), ms: planning, tensorizing and merging on the
host, on the trace's clock."""
from bench.lib import trace
from bench.lib.stats import median


def read(ctx):
    if ctx.trace is None:
        return None
    ms = trace.call_host_ms(ctx.trace)
    return median(ms) if ms else None
