"""``readers/sala_roofline.py`` and ``roofline_sala.py`` on made-up captures:
no chip, no trace file, no JAX.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

import roofline_sala  # noqa: E402
from readers import sala_roofline  # noqa: E402
from selfcheck import MadeUpCapture, scrape_text  # noqa: E402

NAME = "minicpm-sala-pp4"
KERNEL = "_paged_attention_decode_kernel_impl_custom-call"


def _params(metric):
    with open(os.path.join(HERE, "..", "layer_metrics", metric + ".json")) as f:
        return json.load(f)["params"]


def _config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def _scrape(bursts, rows, selected=None, live=None, on_sparse=None):
    text = scrape_text(bursts=bursts, rows=rows, pages=0)
    if selected is None:
        return text
    return text + (
        f"dynamo_tpu_engine_sparse_pages_selected_total {selected}\n"
        f"dynamo_tpu_engine_sparse_pages_live_total {live}\n"
        f'dynamo_tpu_engine_sparse_rows_total{{path="sparse"}} {on_sparse}\n'
        f'dynamo_tpu_engine_sparse_rows_total{{path="dense"}} {rows - on_sparse}\n')


# 12 bursts of 8 rows: four at 65.7 k tokens (1,027 pages held, 64 selected),
# four at 4.3 k (68 pages held, all visited)
BEFORE = _scrape(1000, 8000, 500000, 4000000, 3000)
AFTER = _scrape(1012, 8096, 500000 + 12 * 4 * (64 + 68), 4000000 + 12 * 4 * (1027 + 68), 3048)


class Capture(MadeUpCapture):
    def worker_flag(self, flag):
        return {"--decode-steps": "8", "--block-size": "64"}[flag]


def _capture(before, after, kernel_share=0.1, **kw):
    ctx = Capture(before, after, burst_s=0.096, **kw)
    ctx.config = _config()
    if ctx.trace["programs"]:
        ctx.trace["programs"]["decode"]["ops_s"] = 1.2
        ctx.trace["program_top_ops"] = {
            "decode": [["fusion", 0.9], [KERNEL, 1.2 * kernel_share]]}
    return ctx


def test_the_arithmetic_is_the_issues():
    """A sparse layer's mixer 52.4 M and a lightning layer's 83.9 M beside an
    FFN of 201.3 M (253.8 M and 285.2 M a layer), 1,024 B of K and V a token
    in two sparse layers, 2.10 MB of state a lightning layer a row."""
    cfg = _config()
    sparse, light = roofline_sala.mixer_params(cfg, "sparse"), roofline_sala.mixer_params(cfg, "lightning")
    assert round((sparse + roofline_sala.ffn_params(cfg)) / 1e6, 1) == 253.8
    assert round((light + roofline_sala.ffn_params(cfg)) / 1e6, 1) == 285.2
    assert roofline_sala.layers_of(cfg) == ["sparse"] + ["lightning"] * 6 + ["sparse"]
    assert roofline_sala.page_bytes(cfg, 64) == 64 * 512 * 2 and roofline_sala.state_bytes(cfg) == 2_097_152
    assert roofline_sala.index_bytes_per_page(cfg, 64) == roofline_sala.page_bytes(cfg, 64) // 32
    visited = 4 * (63 + 67.0)
    least, bound, nbytes, flops = roofline_sala.attention_least_seconds(cfg, visited, 64, "TPU v5 lite")
    assert nbytes == 2 * visited * 65536 and bound == "hbm"
    assert flops == 4 * 2 * visited * 64 * 32 * 128
    _, step_bound, terms = roofline_sala.decode_step_least_seconds(
        cfg, 8.0, visited, 4 * 1026.0, 64, "TPU v5 lite")
    assert step_bound == "hbm" and terms["kv_pages"] == nbytes
    assert terms["state"] == 2 * 6 * 8 * 2_097_152 and terms["compressed_keys"] == 2 * 4 * 1026 * 2048
    # 5.64 GB of parameters less the embedding's 0.60 GB, which is looked up
    assert round(terms["weights"] / 1e9, 2) == 5.04


CASES = {
    "decode_step": ("kernel.sala_decode_roofline", {}, "sala decode roofline: 8.0 rows"),
    "attention_kernel": ("kernel.sparse_attention_roofline", {}, "sparse attention roofline: 8.0 rows"),
    "bursts_in_the_drain": ("kernel.sala_decode_roofline", dict(after=[BEFORE], drained=[AFTER]), "to drained"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_sala_rooflines_read(name):
    metric, change, says = CASES[name]
    ctx = _capture(**{"before": [BEFORE], "after": [AFTER], **change})
    got = sala_roofline.read(_params(metric), ctx)
    assert got is not None and 0 < got < 100 and says in ctx.notes[0]
    assert "520.0 pages visited" in ctx.notes[0]  # 4 x (63 + 67): each row's last page counted empty
    if name == "attention_kernel":  # a 12 ms step, a tenth of it in the kernel: 1.2 ms a step
        least = roofline_sala.attention_least_seconds(_config(), 520.0, 64, "TPU v5 lite")[0]
        assert got == pytest.approx(100.0 * least / 1200e-6, rel=1e-9)
    else:
        assert "4104.0 pages' compressed keys scored" in ctx.notes[0]  # 4 x 1,026


NOTHING = {
    "no_decode_program": (dict(before=[BEFORE], after=[AFTER]), "no decode program"),
    "no_burst_counted": (dict(before=[AFTER], after=[AFTER]), "no decode burst was counted"),
    "older_program": (dict(before=[_scrape(1000, 8000)], after=[_scrape(1012, 8096)]), "do not export"),
    "kernel_not_in_the_trace": (dict(before=[BEFORE], after=[AFTER], kernel_share=0.0),
                                "no paged_attention_decode"),
}


@pytest.mark.parametrize("name", sorted(NOTHING))
def test_sala_rooflines_leave_out_and_raise_nothing(name):
    made_up, says = NOTHING[name]
    ctx = _capture(**made_up)
    if name == "no_decode_program":
        ctx.trace = {"programs": {}}
    got = sala_roofline.read(_params("kernel.sparse_attention_roofline"), ctx)
    assert got is None and says in ctx.why_nothing


def test_another_configuration_reads_nothing():
    ctx = MadeUpCapture([BEFORE], [AFTER])  # qwen2.5-0.5b's file
    assert sala_roofline.read(_params("kernel.sala_decode_roofline"), ctx) is None
    assert "not a configuration with sparse attention layers" in ctx.why_nothing
