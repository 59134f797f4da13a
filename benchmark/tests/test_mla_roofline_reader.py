"""``readers/mla_roofline.py`` and ``roofline_mla.py`` on made-up captures: no
chip, no trace file, no JAX.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

import roofline_mla  # noqa: E402
from readers import mla_roofline  # noqa: E402
from selfcheck import MadeUpCapture, scrape_text  # noqa: E402

NAME = "openpangu-ultra-moe-718b-ep16"


def _params(metric):
    with open(os.path.join(HERE, "..", "layer_metrics", metric + ".json")) as f:
        return json.load(f)["params"]


def _config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def _scrape(bursts, rows, pages, hit=None, pairs=None):
    """8 steps x 4 expert layers x 16 held = 512 expert slots a burst."""
    text = scrape_text(bursts=bursts, rows=rows, pages=pages)
    if hit is None:
        return text
    steps = bursts * 8 * 4
    return text + (
        f"dynamo_tpu_engine_moe_experts_hit_total {steps * hit}\n"
        f"dynamo_tpu_engine_moe_expert_slots_total {steps * 16}\n"
        f"dynamo_tpu_engine_moe_mean_expert_tokens_total {steps * pairs / 16}\n")


# 12 bursts of 8 rows, each row on 130 pages of 128 tokens: (130 - 1) x 128 = 16,512 tokens
BEFORE = _scrape(1000, 8000, 1040000, hit=4, pairs=5)
AFTER = _scrape(1012, 8096, 1052480, hit=4, pairs=5)


class Capture(MadeUpCapture):
    def worker_flag(self, flag):
        return {"--decode-steps": "8", "--block-size": "128"}[flag]


def _capture(before, after, kernel_share=0.25, **kw):
    ctx = Capture(before, after, burst_s=0.104, **kw)
    ctx.config = _config()
    if ctx.trace["programs"]:
        ctx.trace["programs"]["decode"]["ops_s"] = 1.2
        ctx.trace["program_top_ops"] = {"decode": [["fusion", 0.7], ["mla_paged_decode custom-call", 1.2 * kernel_share]]}
    return ctx


def test_the_arithmetic_is_the_issues():
    """Attention 196.6 M parameters a layer, one routed expert 47.2 M (94.4 MB
    in bf16), 1,152 B a cached token a layer, 2 x 128 x 1,088 FLOP against it:
    242 FLOP/B, the v5e's ridge, so both bounds are within 1% of each other."""
    cfg = _config()
    assert round(roofline_mla.attention_params(cfg) / 1e6, 1) == 196.6
    assert roofline_mla.expert_params(cfg) * 2 == 94_371_840
    assert roofline_mla.latent_width(cfg) * 2 == 1152
    least, bound, nbytes, flops = roofline_mla.attention_least_seconds(cfg, 8.0, 16512.0, "TPU v5 lite")
    assert nbytes == 8 * 16512 * 1152 and flops == 2 * 8 * 16512 * 128 * 1088
    assert round(flops / nbytes) == 242 and 0.98 < (flops / 197e12) / (nbytes / 819e9) < 1.02
    _, step_bound, terms = roofline_mla.decode_step_least_seconds(cfg, 8.0, 16512.0, 4.0, 5.0, "TPU v5 lite")
    assert step_bound == "hbm" and terms["latent_history"] == 5 * nbytes
    assert terms["experts_hit"] == 4 * 4 * 94_371_840
    assert 3.4e9 < terms["dense_weights"] < 3.6e9  # MLA 1.97, dense FFN 0.85, shared 0.38, head 0.29, routers


CASES = {
    "decode_step": ("kernel.mla_decode_roofline", {}, "mla decode roofline: 8.0 rows x 16512 tokens"),
    "attention_kernel": ("kernel.mla_attention_roofline", {}, "mla attention roofline: 8.0 rows x 16512 tokens"),
    "bursts_in_the_drain": ("kernel.mla_decode_roofline", dict(after=[BEFORE], drained=[AFTER]), "to drained"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_mla_rooflines_read(name):
    metric, change, says = CASES[name]
    ctx = _capture(**{"before": [BEFORE], "after": [AFTER], **change})
    got = mla_roofline.read(_params(metric), ctx)
    assert got is not None and 0 < got < 100 and says in ctx.notes[0]
    if name == "attention_kernel":  # a 13 ms step, a quarter of it in 5 kernel calls: 650 us a call
        least = roofline_mla.attention_least_seconds(_config(), 8.0, 16512.0, "TPU v5 lite")[0]
        assert got == pytest.approx(100.0 * least / 650e-6, rel=1e-9)


NOTHING = {
    "no_decode_program": (dict(before=[BEFORE], after=[AFTER]), "no decode program"),
    "no_burst_counted": (dict(before=[AFTER], after=[AFTER]), "no decode burst was counted"),
    "older_program": (dict(before=[_scrape(1000, 8000, 1040000)], after=[_scrape(1012, 8096, 1052480)]),
                      "do not export"),
    "kernel_not_in_the_trace": (dict(before=[BEFORE], after=[AFTER], kernel_share=0.0), "no mla_paged_decode"),
}


@pytest.mark.parametrize("name", sorted(NOTHING))
def test_mla_rooflines_leave_out_and_raise_nothing(name):
    made_up, says = NOTHING[name]
    ctx = _capture(**made_up)
    if name == "no_decode_program":
        ctx.trace = {"programs": {}}
    got = mla_roofline.read(_params("kernel.mla_attention_roofline"), ctx)
    assert got is None and says in ctx.why_nothing


def test_another_configuration_reads_nothing():
    ctx = MadeUpCapture([BEFORE], [AFTER])  # qwen2.5-0.5b's file
    assert mla_roofline.read(_params("kernel.mla_decode_roofline"), ctx) is None
    assert "not a latent-attention" in ctx.why_nothing
