"""restart_fetch_s: seconds per resume in ``ICheckClient.restart``: fetch
of every region part from L1 and its decode."""
import statistics

from chipbench.spans import nested


def read(ctx):
    d, spans = ctx["driver"], ctx["spans"]
    pairs = nested(spans, "resume", "restart_fetch", d.w0, d.w1)
    return statistics.fmean(inner.seconds for _, inner in pairs) \
        if pairs else None
