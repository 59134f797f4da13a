"""``readers/gdn_roofline.py`` and ``roofline_gdn.py`` on made-up captures: no
chip, no trace file, no JAX.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

import roofline_gdn  # noqa: E402
from readers import gdn_roofline  # noqa: E402
from selfcheck import MadeUpCapture, scrape_text  # noqa: E402

NAME = "qwen3-next-80b-a3b-ep2"
KERNEL = "gdn_step_live custom-call"


def _params(metric):
    with open(os.path.join(HERE, "..", "layer_metrics", metric + ".json")) as f:
        return json.load(f)["params"]


def _config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def _scrape(bursts, rows, pages, hit=None, slots=None, mean=None):
    text = scrape_text(bursts=bursts, rows=rows, pages=pages)
    if hit is None:
        return text
    return text + (
        f"dynamo_tpu_engine_moe_experts_hit_total {hit}\n"
        f"dynamo_tpu_engine_moe_expert_slots_total {slots}\n"
        f"dynamo_tpu_engine_moe_mean_expert_tokens_total {mean}\n")


# 12 bursts of 10 rows: five at 32,896 tokens (258 pages of 128, the last
# counted empty) and five at 4,224 (34 pages); per burst 8 steps x 4 expert
# layers = 32 layer-steps of 256 held experts, 46 of them hit and 50 routed
# (token, held expert) pairs a layer-step.
LAYER_STEPS = 12 * 8 * 4
BEFORE = _scrape(1000, 9000, 700000, 50000, 256 * 1000, 1000.0)
AFTER = _scrape(1012, 9120, 700000 + 12 * 5 * (258 + 34), 50000 + 46 * LAYER_STEPS,
                256 * (1000 + LAYER_STEPS), 1000.0 + 50 * LAYER_STEPS / 256)


class Capture(MadeUpCapture):
    def worker_flag(self, flag):
        return {"--decode-steps": "8", "--block-size": "128"}[flag]


def _capture(before, after, kernel_share=0.05, **kw):
    ctx = Capture(before, after, burst_s=0.040, **kw)  # a 5 ms step
    ctx.config = _config()
    if ctx.trace["programs"]:
        ctx.trace["programs"]["decode"]["ops_s"] = 0.48
        ctx.trace["program_top_ops"] = {
            "decode": [["fusion", 0.3], [KERNEL, 0.48 * kernel_share]]}
    return ctx


def test_the_arithmetic_is_the_issues():
    """A Gated DeltaNet layer 33.72 M outside its experts, the attention layer
    27.27 M, router + shared expert 4.20 M, one expert 6.29 MB, 2,048 B of K
    and V a token in the one full layer, 2.10 MB of matrices and a 49 KB conv
    tail a Gated DeltaNet layer a row."""
    cfg = _config()
    assert roofline_gdn.layers_of(cfg) == ["gdn"] * 3 + ["attention"]
    assert roofline_gdn.gdn_params(cfg) == 33_720_512 - 2048  # less the layer's own norm
    assert roofline_gdn.attention_params(cfg) == 27_265_536 - 2048
    assert roofline_gdn.expert_params(cfg) * 2 == 6_291_456
    router_and_shared = roofline_gdn.router_bytes(cfg) // 4 + roofline_gdn.shared_expert_params(cfg)
    assert router_and_shared == 809_504_768 - 256 * 3_145_728 - 2048  # less the layer's norm
    assert roofline_gdn.kv_bytes_per_token_layer(cfg) == 2048
    assert roofline_gdn.state_matrix_bytes(cfg) == 2_097_152 and roofline_gdn.conv_tail_bytes(cfg) == 49_152
    least, nbytes = roofline_gdn.state_update_least_seconds(2_097_152, 10.0, 3, "TPU v5 lite")
    assert nbytes == 2 * 3 * 10 * 2_097_152 and least == pytest.approx(nbytes / 819e9)
    _, bound, terms = roofline_gdn.decode_step_least_seconds(cfg, 10.0, 18560.0, 46.0, 50.0, "TPU v5 lite")
    assert bound == "hbm"
    assert terms["experts_hit"] == 4 * 46 * 6_291_456  # 1.16 GB
    assert terms["kv_history"] == 10 * 18560 * 2048  # 0.38 GB
    assert terms["state"] == 2 * 3 * 10 * (2_097_152 + 49_152)  # 0.13 GB
    # 3 x 33.72 M + 27.26 M + 4 shared experts + the head's 155.6 M in bf16, 4 float32 routers
    assert round(terms["dense_weights"] / 1e9, 2) == 0.61


CASES = {
    "decode_step": ("kernel.gdn_decode_roofline", {}, "gdn decode roofline: 10.0 rows x 18560 tokens"),
    "state_kernel": ("kernel.gdn_state_update_roofline", {}, "gdn state update roofline: 10.0 live rows x 3 layers"),
    "bursts_in_the_drain": ("kernel.gdn_decode_roofline", dict(after=[BEFORE], drained=[AFTER]), "to drained"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_gdn_rooflines_read(name):
    metric, change, says = CASES[name]
    ctx = _capture(**{"before": [BEFORE], "after": [AFTER], **change})
    got = gdn_roofline.read(_params(metric), ctx)
    assert got is not None and 0 < got < 100 and says in ctx.notes[0]
    if name == "state_kernel":  # a 5 ms step, a twentieth of it in the kernel: 250 us a step
        least = roofline_gdn.state_update_least_seconds(2_097_152, 10.0, 3, "TPU v5 lite")[0]
        assert got == pytest.approx(100.0 * least / 250e-6, rel=1e-9)
    else:
        assert "46.0 of 256 held experts hit and 50.0 routed pairs" in ctx.notes[0]
        least = roofline_gdn.decode_step_least_seconds(
            _config(), 10.0, 18560.0, 46.0, 50.0, "TPU v5 lite")[0]
        assert got == pytest.approx(100.0 * least / 5e-3, rel=1e-9)


NOTHING = {
    "no_decode_program": (dict(before=[BEFORE], after=[AFTER]), "no decode program"),
    "no_burst_counted": (dict(before=[AFTER], after=[AFTER]), "no decode burst was counted"),
    "older_program": (dict(before=[_scrape(1000, 9000, 700000)], after=[_scrape(1012, 9120, 717520)]),
                      "do not export"),
    "kernel_not_in_the_trace": (dict(before=[BEFORE], after=[AFTER], kernel_share=0.0),
                                "no gdn_step_live"),
}


@pytest.mark.parametrize("name", sorted(NOTHING))
def test_gdn_rooflines_leave_out_and_raise_nothing(name):
    made_up, says = NOTHING[name]
    ctx = _capture(**made_up)
    if name == "no_decode_program":
        ctx.trace = {"programs": {}}
    got = gdn_roofline.read(_params("kernel.gdn_state_update_roofline"), ctx)
    assert got is None and says in ctx.why_nothing


def test_another_configuration_reads_nothing():
    ctx = MadeUpCapture([BEFORE], [AFTER])  # qwen2.5-0.5b's file
    assert gdn_roofline.read(_params("kernel.gdn_decode_roofline"), ctx) is None
    assert "not a configuration with Gated DeltaNet layers" in ctx.why_nothing
