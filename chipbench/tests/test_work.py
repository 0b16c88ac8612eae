"""Work counts, peaks and the trace reduction, on numbers worked by hand."""
import json
import os

import pytest

from chipbench import trace, work
from chipbench.models import dense_decoder as fam
from chipbench.peaks import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_qwen_step_flops_by_hand():
    # per layer: wq 2048^2 + wkv 2*2048*256 + wo 2048^2 + 3*2048*11008
    per_layer = 2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 3 * 2048 * 11008
    dense = 2 * 8192 * (4 * per_layer + 2048 * 37984)
    # causal attention once: QK^T and PV, 2 flops a MAC, half the square
    attn = 4 * (2 * 2 * 2 * 16 * 128 * 4096 * 4096 / 2)
    want = 3 * (dense + attn)
    got = fam.step_flops(load("qwen2.5-3b"))
    assert got == pytest.approx(want)
    assert 20e12 < got < 23e12          # "about 22 TFLOP a step"


def test_state_sizes_match_the_cut():
    q, d = load("qwen2.5-3b"), load("deepseek-7b")
    assert fam.param_count(q) == 464_547_840
    assert len(fam.state_names(q)) == 38
    assert fam.param_count(d) == 509_628_416
    assert len(fam.state_names(d)) == 32


def test_codec_bytes_per_frame_kind():
    n = 1000                                   # 4 blocks of 256
    assert work.codec_bytes(n, "key") == 4 * n + n + 4 * 4
    assert work.codec_bytes(n, "delta") == 4 * n + n + 4 * 4 + 2 * n
    with pytest.raises(ValueError):
        work.codec_bytes(n, "raw")


def test_roofline_takes_the_larger_bound():
    pk = peaks("TPU v5 lite")
    assert work.roofline_s(197e12, 0, pk) == pytest.approx(1.0)
    assert work.roofline_s(1.0, 819e9, pk) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_reduction_union_gaps_and_labels():
    kernel = '%attention.7 = (bf16[2]) custom-call(%q), ' \
        'custom_call_target="tpu_custom_call"'
    device = {"/device:TPU:0": [
        ("%fusion.1 = f32[2] fusion()", 1.0, 2.0),
        ("%fusion.1 = f32[2] fusion()", 1.5, 2.5),    # overlap: once
        (kernel, 4.0, 5.0), ("%outside = f32[2] add()", 9.0, 11.0)]}
    spans = [("window", 0.0, 10.0), ("step", 0.5, 3.0), ("commit", 3.0, 8.0)]
    red = trace.reduce_events(device, spans, (0.0, 10.0))
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx(1.5 + 1.0 + 1.0)   # clipped at 10
    assert red.ops["fusion.1"] == (2, 2.0)
    assert red.kernel_seconds("attention") == (1, 1.0)
    assert red.kernel_seconds("fusion") == (0, 0.0)     # not a kernel
    # idle: 0..1 (its middle 0.5 is inside step), 2.5..4 and 5..9 (commit)
    assert red.gaps == [("commit", 4.0), ("commit", 1.5), ("step", 1.0)]
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"


def test_recorded_chip_trace():
    """A trace recorded on one v5e: a matmul under ``bench/step``, 10 ms of
    host sleep, then the q8 quantize kernel under ``bench/commit``, all in
    ``bench/window``."""
    red = trace.read(os.path.join(HERE, "data",
                                  "v5e_step_and_quantize.xplane.pb"))
    assert [s[0] for s in red.spans] == ["window", "step", "commit"]
    assert red.planes == ["/device:TPU:0"]
    assert red.window_s == pytest.approx(0.011917, rel=1e-3)
    assert red.kernels == {"quantize.1"}
    n, secs = red.kernel_seconds("quantize")
    assert n == 1 and secs == pytest.approx(14.232e-6, rel=1e-3)
    assert red.kernel_seconds("attention") == (0, 0.0)
    assert 0 < red.busy_s < 1e-3
    # the sleep is the longest idle gap, under the window alone
    assert red.gaps[0][0] == "window" and red.gaps[0][1] > 0.009
    assert trace.base_name("quantize_delta.12") == "quantize_delta"
    assert trace.op_name("%fusion.4 = f32[2] fusion(%x)") == "fusion.4"
