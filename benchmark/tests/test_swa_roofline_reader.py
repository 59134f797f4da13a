"""``readers/swa_roofline.py`` and ``roofline_swa.py`` on made-up captures: no
chip, no trace file, no JAX.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

import roofline_swa  # noqa: E402
from readers import swa_roofline  # noqa: E402
from selfcheck import MadeUpCapture, scrape_text  # noqa: E402

NAME = "laguna-xs.2-pp8"
KERNEL = "_paged_attention_decode_kernel_impl_custom-call"


def _params(metric):
    with open(os.path.join(HERE, "..", "layer_metrics", metric + ".json")) as f:
        return json.load(f)["params"]


def _config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def _scrape(bursts, rows, pages, window_pages=None, hit=None, pairs=None):
    """8 steps x 4 expert layers x 256 held = 8,192 expert slots a burst."""
    text = scrape_text(bursts=bursts, rows=rows, pages=pages)
    if hit is None:
        return text
    steps = bursts * 8 * 4
    return text + (
        f"dynamo_tpu_engine_decode_window_live_pages_total {window_pages}\n"
        f"dynamo_tpu_engine_moe_experts_hit_total {steps * hit}\n"
        f"dynamo_tpu_engine_moe_expert_slots_total {steps * 256}\n"
        f"dynamo_tpu_engine_moe_mean_expert_tokens_total {steps * pairs / 256}\n")


# 12 bursts of 8 rows: four on 252 full-group pages (32 k tokens), four on 19
# (2.3 k), every row on 5 window-group pages
BEFORE = _scrape(1000, 8000, 1084000, 40000, hit=57, pairs=64)
AFTER = _scrape(1012, 8096, 1084000 + 12 * 4 * (252 + 19), 40000 + 12 * 8 * 5, hit=57, pairs=64)


class Capture(MadeUpCapture):
    def worker_flag(self, flag):
        return {"--decode-steps": "8", "--block-size": "128"}[flag]


def _capture(before, after, kernel_share=0.3, **kw):
    ctx = Capture(before, after, burst_s=0.048, **kw)
    ctx.config = _config()
    if ctx.trace["programs"]:
        ctx.trace["programs"]["decode"]["ops_s"] = 1.2
        ctx.trace["program_top_ops"] = {
            "decode": [["fusion", 0.7], [KERNEL, 1.2 * kernel_share]]}
    return ctx


def test_the_arithmetic_is_the_issues():
    """Attention 29.46 M parameters at 48 heads and 37.88 M at 64, one expert
    3.146 M (6.29 MB in bf16), 4,096 B of K and V a token a layer."""
    cfg = _config()
    assert round(roofline_swa.attention_params(cfg, 48) / 1e6, 2) == 29.46
    assert round(roofline_swa.attention_params(cfg, 64) / 1e6, 2) == 37.88
    assert roofline_swa.expert_params(cfg) * 2 == 6_291_456
    assert roofline_swa.page_bytes(cfg, 128) == 128 * 4096
    assert [(h, s, d) for h, s, d in roofline_swa.layers_of(cfg)] == [
        (48, False, True), (64, True, False), (64, True, False), (64, True, False), (48, False, False)]
    full, window = 4 * (251 + 18.0), 8 * 4.0
    least, bound, nbytes, flops = roofline_swa.attention_least_seconds(cfg, full, window, 128, "TPU v5 lite")
    assert nbytes == (2 * full + 3 * window) * 128 * 4096 and bound == "hbm"
    assert flops == 4 * 128 * 128 * (2 * 48 * full + 3 * 64 * window)
    _, step_bound, terms = roofline_swa.decode_step_least_seconds(
        cfg, 8.0, full, window, 128, 57.0, 64.0, "TPU v5 lite")
    assert step_bound == "hbm" and terms["kv_pages"] == nbytes
    assert terms["experts_hit"] == 4 * 57 * 6_291_456
    # attention 0.35 GB, dense FFN 0.10, shared 0.025, head 0.41, routers 0.008
    assert 0.88e9 < terms["dense_weights"] < 0.92e9


CASES = {
    "decode_step": ("kernel.swa_decode_roofline", {}, "swa decode roofline: 8.0 rows"),
    "attention_kernel": ("kernel.paged_attention_roofline", {}, "paged attention roofline: 8.0 rows"),
    "bursts_in_the_drain": ("kernel.swa_decode_roofline", dict(after=[BEFORE], drained=[AFTER]), "to drained"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_swa_rooflines_read(name):
    metric, change, says = CASES[name]
    ctx = _capture(**{"before": [BEFORE], "after": [AFTER], **change})
    got = swa_roofline.read(_params(metric), ctx)
    assert got is not None and 0 < got < 100 and says in ctx.notes[0]
    assert "1076.0 full-group and 32.0 window-group live pages" in ctx.notes[0]
    if name == "attention_kernel":  # a 6 ms step, three tenths of it in the kernel: 1.8 ms a step
        least = roofline_swa.attention_least_seconds(_config(), 1076.0, 32.0, 128, "TPU v5 lite")[0]
        assert got == pytest.approx(100.0 * least / 1800e-6, rel=1e-9)


NOTHING = {
    "no_decode_program": (dict(before=[BEFORE], after=[AFTER]), "no decode program"),
    "no_burst_counted": (dict(before=[AFTER], after=[AFTER]), "no decode burst was counted"),
    "older_program": (dict(before=[_scrape(1000, 8000, 1084000)], after=[_scrape(1012, 8096, 1097008)]),
                      "do not export"),
    "kernel_not_in_the_trace": (dict(before=[BEFORE], after=[AFTER], kernel_share=0.0),
                                "no paged_attention"),
}


@pytest.mark.parametrize("name", sorted(NOTHING))
def test_swa_rooflines_leave_out_and_raise_nothing(name):
    made_up, says = NOTHING[name]
    ctx = _capture(**made_up)
    if name == "no_decode_program":
        ctx.trace = {"programs": {}}
    got = swa_roofline.read(_params("kernel.paged_attention_roofline"), ctx)
    assert got is None and says in ctx.why_nothing


def test_another_configuration_reads_nothing():
    ctx = MadeUpCapture([BEFORE], [AFTER])  # qwen2.5-0.5b's file
    assert swa_roofline.read(_params("kernel.swa_decode_roofline"), ctx) is None
    assert "not a configuration with sliding-window layers" in ctx.why_nothing
