"""step_mfu: the training step's model FLOPs over the chip's bf16 peak.

FLOPs of a step (forward and backward, no rematerialisation, causal
attention once) times the window's steps, over their summed host-clock
durations (dispatch to the loss on the host) times chips times peak."""


def read(ctx):
    d = ctx["driver"]
    steps = [s for s in d.step_spans if d.w0 <= s.start and s.end <= d.w1]
    if not steps:
        return None
    flops = ctx["family"].step_flops(ctx["cfg"]) * len(steps)
    busy = sum(s.seconds for s in steps)
    return 100.0 * flops / (busy * ctx["chips"] * ctx["peaks"]["bf16_flops"])
