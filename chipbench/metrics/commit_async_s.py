"""commit_async_s: seconds from ``commit()`` returning to the checkpoint's
COMMIT_DONE (client completer, agent puts, L1 commit), per save."""
import statistics


def read(ctx):
    done = [s.done - s.back for s in ctx["driver"].saves if s.done is not None]
    return statistics.fmean(done) if done else None
