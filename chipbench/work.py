"""Work counts the roofline shares divide by, from shapes alone."""
from __future__ import annotations

BLOCK = 256      # values per q8 block, one f32 scale each


def codec_bytes(n: int, frame: str) -> int:
    """HBM bytes the q8 encode of ``n`` f32 values needs: a keyframe reads
    the values once and writes one int8 code per value and one f32 scale
    per 256 values; a delta also reads the previous codes once and writes
    the XOR delta.  Padding to whole blocks is not counted."""
    scales = 4 * (-(-n // BLOCK))
    key = 4 * n + n + scales
    if frame == "key":
        return key
    if frame == "delta":
        return key + n + n
    raise ValueError(frame)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip needs for the work: the larger of its compute
    and its memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
