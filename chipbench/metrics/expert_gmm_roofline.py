"""expert_gmm_roofline: the MoE layers' grouped-matmul kernels' device time
(``jax.lax.ragged_dot``'s Mosaic kernels, ``ragged-dot-*`` in the trace:
forward, rematerialised forward and backward calls) against the least time
their work needs at the chip's peaks.

The work is counted from the rows the held experts computed in each MoE
layer of each window step (the trainer's ``moe_rows``, its last
``window_steps`` entries) and the widths (the family's
``expert_gmm_fwd_work``): each forward grouped matmul runs twice (the step
rematerialises every layer) and its backward is two products of the same
FLOPs, one for the rows and one for the weights, so four times the
forward's least time a layer and step."""
from chipbench.work import roofline_s

PASSES = 4


def read(ctx):
    red, d = ctx["trace"], ctx["driver"]
    n, secs = red.kernel_seconds("ragged-dot-none", "ragged-dot-metadata")
    rows_of = getattr(d.trainer, "moe_rows", None)
    if n == 0 or secs <= 0 or rows_of is None:
        return None
    rows = rows_of(d.window_steps)            # (steps, layers, experts)
    if rows is None:
        return None
    fam, cfg, pk = ctx["family"], ctx["cfg"], ctx["peaks"]
    least = sum(PASSES * roofline_s(flops, nbytes, pk)
                for r in rows.sum(axis=2).ravel()
                for flops, nbytes in fam.expert_gmm_fwd_work(cfg, int(r)))
    return 100.0 * least / secs
