"""snapshot_s: seconds per save spent in ``snapshot_pytree`` (device
encode, D2H, host gather and framing) on the step thread."""
import statistics


def read(ctx):
    spans = ctx["spans"]
    d = ctx["driver"]
    commits = spans.named("commit", d.w0, d.w1)
    snaps = [s for s in spans.named("snapshot", d.w0, d.w1)
             if any(c.start <= s.start and s.end <= c.end for c in commits)]
    return statistics.fmean(s.seconds for s in snaps) if snaps else None
