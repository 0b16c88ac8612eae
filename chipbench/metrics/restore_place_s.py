"""restore_place_s: seconds per resume in ``restart_if_available`` outside
the client's fetch: ``restore_pytree`` (host assembly, one H2D a leaf) and
``_shard_state``."""
import statistics

from chipbench.spans import nested


def read(ctx):
    d, spans = ctx["driver"], ctx["spans"]
    outer = nested(spans, "resume", "restart", d.w0, d.w1)
    fetch = nested(spans, "resume", "restart_fetch", d.w0, d.w1)
    if not outer or len(outer) != len(fetch):
        return None
    return statistics.fmean(r.seconds - f.seconds
                            for (_, r), (_, f) in zip(outer, fetch))
