"""attention_fwd_roofline: the Pallas flash-attention forward's device time
against the least time its work needs (causal FLOPs and the bytes of q, k,
v, o and the log-sum-exp, from shapes), over every call in the trace."""
from chipbench.work import roofline_s

def read(ctx):
    red = ctx["trace"]
    # the kernel called from repro.kernels.flash_attention.attention
    n, secs = red.kernel_seconds("attention")
    if n == 0 or secs <= 0:
        return None
    flops, nbytes = ctx["family"].attention_fwd_work(ctx["cfg"])
    return 100.0 * n * roofline_s(flops, nbytes, ctx["peaks"]) / secs
