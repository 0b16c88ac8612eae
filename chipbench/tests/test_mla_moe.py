"""The latent-attention MoE family: a tiny configuration's q8-delta window on
the CPU through the harness's test-only path, planted faults that come out
not correct, and the family's work counts on numbers worked by hand."""
import json
import os

import numpy as np
import pytest

from chipbench import harness, work
from chipbench.models import mla_moe_decoder as mla_fam
from chipbench.peaks import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
SEED = 2**31 + 12345          # more than 32 signed bits hold


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def run_tiny_mla_moe(traffic, seconds=2.0):
    """Run ``traffic`` at ``tests/tiny-mla-moe.json``, added to a copy of the
    benchmark as a cell of its own."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-mla-moe",
                             "file": "chipbench/tests/tiny-mla-moe.json"})
    name = "tiny-mla-moe." + traffic
    bench["workloads"].append({"name": name, "config": "tiny-mla-moe",
                               "traffic": traffic, "chips": 1})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return harness.run_cell(bench, name, SEED, seconds, trace=False,
                            require_tpu=False)


# ------------------------------------------------------------- the window
def test_mla_moe_window_on_cpu():
    """A tiny latent-attention MoE configuration (experts 4-11 of 16 held)
    through the q8-delta save window, against its own family's reference;
    the trainer keeps each step's rows per held expert, and every (token,
    slot) pair routed to a held expert is computed."""
    out = run_tiny_mla_moe("q8delta-save")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "save_stall_s",
                                   "commit_durable_s", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0


def _renormalised_router(monkeypatch):
    """The MoE layer renormalises its top-k weights, which DeepSeek-V2-Lite
    does not (``norm_topk_prob`` false)."""
    import repro.models.moe as moe

    real = moe.moe_apply

    def apply(*a, **k):
        return real(*a, **dict(k, norm_topk_prob=True))

    monkeypatch.setattr(moe, "moe_apply", apply)


def _other_experts_held(monkeypatch):
    """The program computes experts 0-7 where the configuration holds 4-11."""
    import repro.models.moe as moe

    real = moe.moe_apply

    def apply(*a, **k):
        return real(*a, **dict(k, expert_offset=0))

    monkeypatch.setattr(moe, "moe_apply", apply)


def _half_batch(monkeypatch):
    """The timed step trains on half of each batch."""
    import repro.train.elastic as elastic

    real = elastic.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: step(
            state, {n: v[:v.shape[0] // 2] for n, v in batch.items()})

    monkeypatch.setattr(elastic, "make_train_step", make)


@pytest.mark.parametrize("fault", [_renormalised_router, _other_experts_held,
                                   _half_batch],
                         ids=lambda f: f.__name__[1:])
def test_mla_moe_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run_tiny_mla_moe("q8delta-save", seconds=1.0)
    assert not out["correct"], out["checks"]


# ---------------------------------------------------------- work counts
def test_v2_lite_sizes_match_the_cut():
    c = load("deepseek-v2-lite")
    assert mla_fam.param_count(c) == 535_060_992      # 6.42 GB with moments
    assert len(mla_fam.state_names(c)) == 74
    assert mla_fam.shapes(c)["params/stack/b0/moe/w_gu"] == \
        (4, 2, 8, 2048, 1408)


def test_v2_lite_step_flops_by_hand():
    c = load("deepseek-v2-lite")
    tokens = 4 * 4096
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    attn = 16 * 4096 * (192 + 128)          # a token's causal share, x2 MACs
    dense = 3 * 2048 * 10944
    # router, 2 shared experts, and 6 * 8 / 64 routed pairs a token
    moe = 2048 * 64 + 3 * 2048 * 1408 * (2 + 6 * 8 / 64)
    fwd = tokens * (2 * (5 * mla + dense + 4 * moe + 2048 * 12800)
                    + 5 * attn)
    assert mla_fam.step_flops(c) == pytest.approx(3 * fwd)
    mla_share = (2 * mla + attn) * 5 / (fwd / tokens)
    moe_share = 2 * 4 * moe / (fwd / tokens)
    assert mla_share == pytest.approx(0.39, abs=0.01)
    assert moe_share == pytest.approx(0.31, abs=0.01)


def test_expert_gmm_work_by_hand():
    c = load("deepseek-v2-lite")
    (f_gu, b_gu), (f_dn, b_dn) = mla_fam.expert_gmm_fwd_work(c, 1536)
    assert f_gu == 2 * 1536 * 2048 * 2816
    assert f_dn == 2 * 1536 * 1408 * 2048
    assert b_gu == 2 * (1536 * 2048 + 8 * 2048 * 2816 + 1536 * 2816)
    assert b_dn == 2 * (1536 * 1408 + 8 * 1408 * 2048 + 1536 * 2048)


def test_expert_gmm_reader_counts_four_passes():
    """The reader's least time: four times each layer's forward grouped
    matmuls' roofline time, per window step, over the kernels' seconds."""
    c = load("deepseek-v2-lite")
    pk = peaks("TPU v5 lite")
    rows = np.full((2, 4, 8), 1536)     # 2 steps, 4 layers, 8 held experts

    class Trace:
        def kernel_seconds(self, *bases):
            assert "ragged-dot-none" in bases
            return 64, 0.5

    class Trainer:
        def moe_rows(self, last):
            assert last == 2
            return rows

    class Driver:
        trainer, window_steps = Trainer(), 2

    reader = harness.load_module(
        os.path.join(os.path.dirname(HERE), "metrics",
                     "expert_gmm_roofline.py"), "expert_gmm_roofline")
    got = reader.read({"trace": Trace(), "driver": Driver(), "cfg": c,
                       "family": mla_fam, "peaks": pk})
    least = 2 * 4 * 4 * sum(work.roofline_s(f, b, pk) for f, b in
                            mla_fam.expert_gmm_fwd_work(c, 8 * 1536))
    assert got == pytest.approx(100 * least / 0.5)
    # compute-bound at the cell's mean rows: the FLOPs set the time
    assert least == pytest.approx(2 * 4 * 4 * 3 * 2 * 8 * 1536 * 2048 * 1408
                                  / pk["bf16_flops"])
