"""codec_roofline: the q8 quantize kernels' device time against the least
time their bytes need at the HBM peak.  The bytes are what the algorithm
needs for each region of each save in the window, by the frame it was
encoded as (``chipbench.work.codec_bytes``)."""
from chipbench.work import codec_bytes

def read(ctx):
    d, red = ctx["driver"], ctx["trace"]
    # the kernels called from repro.kernels.ckpt_codec.quantize{,_delta}
    n, secs = red.kernel_seconds("quantize", "quantize_delta")
    if n == 0 or secs <= 0:
        return None
    commits = ctx["spans"].named("commit", d.w0, d.w1)
    nbytes = sum(
        codec_bytes(size, frame)
        for s in ctx["spans"].named("snapshot", d.w0, d.w1)
        if any(c.start <= s.start and s.end <= c.end for c in commits)
        for frame, size in s.info.get("frames", {}).values())
    if nbytes == 0:
        return None
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / secs
