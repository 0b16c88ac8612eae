"""resume_init_s: seconds per resume from the new trainer's construction to
the call of ``restart_if_available`` (random init, compile of the step,
client registration and the snapshot that registers the regions)."""
import statistics

from chipbench.spans import nested


def read(ctx):
    d, spans = ctx["driver"], ctx["spans"]
    pairs = nested(spans, "resume", "restart", d.w0, d.w1)
    return statistics.fmean(inner.start - outer.start
                            for outer, inner in pairs) if pairs else None
