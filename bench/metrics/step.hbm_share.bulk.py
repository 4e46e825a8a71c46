"""The bucket step's share of the HBM roofline, %: the packed bytes of
every posting the window's plans read (postings_read of each answer
completed in the window x the served arena's packed bytes per posting) over
the chip's HBM bandwidth, divided by the device's busy time.  It counts
the work the plans require, whatever implements the step."""
from bench.lib import trace


def read(ctx):
    if ctx.trace is None or not ctx.bytes_per_posting:
        return None
    busy = trace.busy_s(ctx.trace)
    if busy <= 0 or ctx.postings_read <= 0:
        return None
    need_s = (ctx.postings_read * ctx.bytes_per_posting
              / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / busy
