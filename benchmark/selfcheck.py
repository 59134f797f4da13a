#!/usr/bin/env python3
"""Checks of the benchmark's own arithmetic, run by hand (not by tier-1):

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

1. percentiles, time per output token and tokens in a window, on made-up
   frames: bursts of 8 tokens, a failed request, a late generator; the
   length and arrival laws, and that a seed reorders the work and never
   changes it;
2. the trace reduction, on a hand-written trace whose numbers can be worked
   on paper, and on ``fixtures/served_decode.xplane.pb``, a few hundred KB
   cut from this PR's first traced run on the chip;
3. the roofline function against hand-worked numbers, for the benchmark's
   configuration and for Qwen3-8B in int8 (whose cell is put off: PERF.md),
   and the reader that feeds it rows and context from the program's per-burst
   counters, on a made-up capture in which every 2 Hz poll read 0 rows
   (``tests/test_decode_roofline_reader.py`` holds the other cases).
"""

from __future__ import annotations

import json
import math
import os
import sys

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, HERE)

import laws  # noqa: E402
import prom  # noqa: E402
import roofline  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def check_stats() -> None:
    assert close(stats.percentile([1, 2, 3, 4, 5], 50), 3.0)
    assert close(stats.percentile([10, 20], 95), 19.5)
    # A failed request is the largest value; the tail lands on the largest
    # finite one.
    assert close(stats.percentile([1.0, 2.0, math.inf], 95), 2.0)
    # 25 output tokens: the first frame (1 token) at 1.0 s, then bursts of 8
    # every 0.2 s: 8 + 8 + 8 = 24 more, the last at 1.6 s.
    r = stats.Record(rid=0, due=0.9, prompt_len=10, max_tokens=25, sent=0.95,
                     frame_times=[1.0, 1.2, 1.4, 1.6], done=1.61, finish_reason="length",
                     usage={"completion_tokens": 25, "prompt_tokens": 10})
    assert close(r.ttft_s(), 0.1)  # from DUE, not from sent
    assert close(r.tpot_s(), 0.6 / 24)  # per token, not per frame (0.2 s a frame)
    assert close(r.tokens_between(0.0, 1.3), 1 + 8)
    assert close(r.tokens_between(1.3, 2.0), 16)
    failed = stats.Record(rid=1, due=1.0, prompt_len=10, max_tokens=25, sent=1.5,
                          error="HTTP 503")
    assert failed.ttft_s() == math.inf and failed.tpot_s() is None
    m = stats.end_to_end([r, failed], 0.0, 2.0, chips=1, setup_s=3.0)
    assert close(m["out_tok_per_s"]["value"], 12.5)
    assert close(m["ttft_p95_ms"]["value"], 100.0)  # the failure sorts last; largest finite reported
    assert close(stats.lateness_ms([r, failed], 0.0, 2.0, 50), (0.05 + 0.5) / 2 * 1e3)
    print("stats: ok")


def check_laws() -> None:
    import numpy as np

    from generators import open_loop

    law = {"law": "lognormal", "median": 200, "sigma": 0.8, "min": 65, "max": 1024}
    xs = laws.stratified(law, 384)
    assert xs == sorted(xs) and xs[0] == 65 and 1000 < xs[-1] <= 1024
    assert xs.count(xs[-1]) < 3 and xs.count(65) < 4  # truncated: no pile at an end
    # Inside [65, 1024] lie the law's quantiles 0.080 to 0.979; the middle of
    # what is left is the 0.5295 quantile of the whole law: 200 e^(0.8 x 0.074).
    assert laws.quantile(law, 0.5) == 212
    for arrivals in ({"law": "poisson"}, {"law": "gamma", "cv": 3.0}):
        g = laws.gaps(arrivals, 384, 40.0)
        assert close(sum(g), 40.0) and g == sorted(g) and g[0] >= 0
    exp = laws.gaps({"law": "poisson"}, 1000, 1000.0)  # mean 1: median ln 2
    assert abs(exp[500] - math.log(2)) < 0.01
    params = {"arrivals": {"law": "gamma", "cv": 3.0}, "prompt_tokens": law, "blocks": 8,
              "output_tokens": {"law": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 256}}
    a, b = (open_loop.plan(params, 9.6, 40.0, np.random.default_rng([seed, 1]))
            for seed in (7, 4_000_000_007))
    assert a != b and len(a) == len(b) == 384 and a[0][0] == b[0][0] == 0.0
    for col in (1, 2):  # the same prompt and output lengths, in another order
        assert sorted(r[col] for r in a) == sorted(r[col] for r in b)
    assert a[-1][0] < 40.0 and b[-1][0] < 40.0
    # Every eighth of the requests carries every eighth quantile: the same
    # output tokens in each stretch whatever the seed.
    for k in range(8):
        assert (sum(r[2] for r in a[48 * k: 48 * (k + 1)])
                == sum(r[2] for r in b[48 * k: 48 * (k + 1)]))
    print("laws: ok")


HAND_TRACE = """
planes {
  name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "jit_step(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_step(2)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p0)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.2 = (s32[], bf16[8,128]{1,0}) while((s32[], bf16[8,128]{1,0}) %tuple.1)" } }
  event_metadata { key: 5 value { id: 5 name: "%fused_layer.7 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %p1), custom_call_target=tpu_custom_call" } }
  lines {
    name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
  }
  lines {
    name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 500000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 }
  }
}
"""


def check_trace() -> None:
    from jax.profiler import ProfileData

    names = {"device_plane": ["^/device:TPU:\\d+$"], "module_line": ["XLA Modules"],
             "op_line": ["XLA Ops"], "custom_call": ["custom-call"],
             "programs": {"decode": {"module": ["jit_step"], "has_op": ["while"]},
                          "prefill": {"module": ["jit_step"], "lacks_op": ["while"]}}}
    pd = ProfileData.from_text_proto(HAND_TRACE)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    got = trace_reduce.reduce_plane(plane, names, window_ns=10_000.0)
    # Busy: [0, 4] and [6, 8] us of a 10 us window -> 6 us, idle share 40%.
    assert close(got["busy_s"], 6e-6), got["busy_s"]
    # The while holds the custom call: self time 1 us + 3 us, not 4 + 3.
    assert close(got["op_self_s"]["while"], 1e-6) and close(got["op_self_s"]["fused_layer custom-call"], 3e-6)
    dec, pre = got["programs"]["decode"], got["programs"]["prefill"]
    assert dec["count"] == 1 and close(dec["median_s"], 4e-6) and close(dec["custom_call_s"] / dec["ops_s"], 0.75)
    assert pre["count"] == 1 and close(pre["total_s"], 2e-6)
    gaps = got["gaps_s"]
    assert close(sum(gaps.values()), 4e-6) and close(got["longest_gap_s"], 2e-6), gaps
    assert any("after decode before prefill" in k for k in gaps)
    print("trace, hand-written: ok")

    fixture = os.path.join(HERE, "fixtures", "served_decode.xplane.pb")
    expect_path = os.path.join(HERE, "fixtures", "served_decode.expect.json")
    if not os.path.isfile(fixture):
        print("trace, recorded fixture: ABSENT")
        return
    with open(expect_path) as f:
        expect = json.load(f)
    pd = ProfileData.from_file(fixture)
    names = trace_reduce.load_names()
    plane = next(p for p in pd.planes if p.name == expect["plane"])
    got = trace_reduce.reduce_plane(plane, names, expect["window_s"] * 1e9)
    assert close(got["busy_s"], expect["busy_s"], 1e-6), (got["busy_s"], expect["busy_s"])
    assert 0 < got["busy_s"] <= expect["window_s"]
    for kind, want in expect["programs"].items():
        have = got["programs"][kind]
        assert have["count"] == want["count"], (kind, have, want)
        assert close(have["total_s"], want["total_s"], 1e-6), (kind, have, want)
    # Self times partition the busy time of a single line of nested events.
    assert sum(got["op_self_s"].values()) <= got["busy_s"] * (1 + 1e-6) + 1e-9
    print(f"trace, recorded fixture: ok (busy {got['busy_s'] * 1e3:.3f} ms of "
          f"{expect['window_s'] * 1e3:.0f} ms, programs "
          f"{ {k: v['count'] for k, v in got['programs'].items()} })")


def check_roofline() -> None:
    def cfg(name):
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            return json.load(f)

    # Qwen3-8B (huggingface.co/Qwen/Qwen3-8B config.json) served in int8.
    q3 = {"hidden_size": 4096, "intermediate_size": 12288, "num_hidden_layers": 36,
          "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
          "vocab_size": 151936, "tie_word_embeddings": False,
          "serving": {"weight_bytes_per_param": 1, "kv_bytes_per_value": 2}}
    # Per layer: q and o 2 x 4096 x 4096, k and v 2 x 4096 x 1024, three
    # feed-forward matrices 3 x 4096 x 12288 = 192,937,984; x 36 layers
    # = 6,945,767,424; the head 151936 x 4096 = 622,329,856.
    assert roofline.matmul_params(q3) == 36 * 192_937_984 + 622_329_856 == 7_568_097_280
    # 7.568 GB of int8 codes streamed once per step -> 9.24 ms at 819 GB/s.
    # (The 8.20 GB the worker reports resident also holds the untied
    # embedding table, 0.62 GB, which a step looks up and does not stream.)
    t, bound = roofline.decode_step_least_seconds(q3, rows=0, mean_ctx=0, device_kind="TPU v5 lite")
    assert bound == "hbm" and close(t, 7_568_097_280 / 819e9) and abs(t * 1e3 - 9.24) < 0.005
    assert roofline.kv_bytes_per_token(q3) == 147_456
    # 32 rows x 400 tokens of bf16 KV add 1.887 GB -> 11.545 ms.
    t, bound = roofline.decode_step_least_seconds(q3, 32, 400, "TPU v5 lite")
    assert bound == "hbm" and abs(t * 1e3 - 11.545) < 0.005, t

    q2 = cfg("qwen2.5-0.5b")
    # Per layer 2 x 896 x 896 + 2 x 896 x 128 + 3 x 896 x 4864 = 14,909,440;
    # x 24 = 357,826,560; the tied head 151936 x 896 = 136,134,656.
    assert roofline.matmul_params(q2) == 24 * 14_909_440 + 136_134_656 == 493_961_216
    assert roofline.kv_bytes_per_token(q2) == 12_288
    t, bound = roofline.decode_step_least_seconds(q2, 0, 0, "TPU v5 lite")
    assert bound == "hbm" and abs(t * 1e3 - 1.2063) < 0.0005, t  # 0.988 GB of bf16
    # At 256 rows the matrix multiplications alone are 2 x 0.494e9 x 256
    # = 0.253 TFLOP = 1.284 ms at 197 TFLOP/s: compute overtakes the weights
    # alone (1.206 ms) but not weights + 256 x 300 tokens of KV (2.36 ms).
    t, bound = roofline.decode_step_least_seconds(q2, 256, 300, "TPU v5 lite")
    assert bound == "hbm" and abs(t * 1e3 - 2.3586) < 0.002, t
    try:
        roofline.peaks_for("TPU v4")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
    print("roofline: ok")


ROWS_FAMILY = "dynamo_tpu_engine_batch_occupancy"
PAGES_FAMILY = "dynamo_tpu_engine_decode_live_pages_total"


def scrape_text(bursts: int, rows: int, pages: int) -> str:
    """A worker's /metrics after ``bursts`` decode bursts of ``rows`` rows in
    all, over ``pages`` live pages; its gauges say the engine is idle NOW."""
    return (
        f'{ROWS_FAMILY}_sum{{phase="decode"}} {rows}\n'
        f'{ROWS_FAMILY}_count{{phase="decode"}} {bursts}\n'
        f'{ROWS_FAMILY}_sum{{phase="prefill"}} 900\n'
        f'{ROWS_FAMILY}_count{{phase="prefill"}} 300\n'
        f"{PAGES_FAMILY} {pages}\n"
        "dynamo_tpu_engine_active_seqs 0\ndynamo_tpu_engine_total_blocks 16384\n"
        "dynamo_tpu_engine_free_blocks 16384\n")


class MadeUpCapture:
    """What ``readers/trace.py`` is given, without a chip or a trace file:
    the snapshots at the capture's start, the window's end and after the
    drain (one text per worker), the 2 Hz polls inside the capture, and the
    reduced trace's median decode program."""

    def __init__(self, before, after, drained=None, burst_s=0.0544, polls=()):
        with open(os.path.join(HERE, "configs", "qwen2.5-0.5b.json")) as f:
            self.config = json.load(f)
        self.n = len(before)
        self.snapshots = {
            when: {f"worker{i}": prom.parse(t) for i, t in enumerate(texts)}
            for when, texts in (("capture_start", before), ("window_end", after),
                                ("drained", drained or after))}
        self.polls = [(t, {f"worker{i}": prom.parse(text) for i in range(self.n)})
                      for t, text in polls]
        self.capture_t0, self.w1 = 36.06, 40.0
        self.trace = {"programs": {"decode": {"count": 12, "median_s": burst_s}} if burst_s else {}}
        self.device_kind = "TPU v5 lite"
        self.notes = []
        self.why_nothing = ""

    def targets(self, which):
        return [f"worker{i}" for i in range(self.n)]

    def worker_flag(self, flag):
        return {"--decode-steps": "8", "--block-size": "16"}[flag]


def roofline_params():
    with open(os.path.join(HERE, "layer_metrics", "kernel.decode_roofline.json")) as f:
        return json.load(f)["params"]


# The capture of PR 31's refused runs: 12 decode bursts of 4 rows were
# dispatched and reaped inside it, each row ending its burst at 330 tokens
# (ceil(330 / 16) = 21 pages: 48 rows, 1,008 pages), and every poll of the
# gauges fell between two requests and read an idle engine.
PR31_BEFORE = scrape_text(bursts=1000, rows=11500, pages=250000)
PR31_AFTER = scrape_text(bursts=1012, rows=11548, pages=251008)
PR31_POLLS = [(t, PR31_AFTER) for t in (36.51, 37.02, 37.53, 38.04, 38.55, 39.06, 39.57)]
# Rows 48 / 12 = 4 a burst; context (1008 - 48) x 16 / 48 = 320 tokens a row
# (the last page counted empty: 10 under the true 330). Bytes of a step:
# 987,922,432 of weights + 4 x 320 x 12,288 of KV = 1,003,651,072, at 819
# GB/s 1.22546 ms; a 54.4 ms burst of 8 steps is 6.8 ms a step: 18.02%.
PR31_SHARE = 100.0 * (987_922_432 + 4 * 320 * 12_288) / 819e9 / (0.0544 / 8)


def check_roofline_reader() -> None:
    from readers import trace

    ctx = MadeUpCapture([PR31_BEFORE], [PR31_AFTER], polls=PR31_POLLS)
    got = trace.read(roofline_params(), ctx)
    assert close(got, PR31_SHARE) and abs(got - 18.0215) < 0.0005, got
    assert "4.0 rows x 320 tokens per dispatched burst" in ctx.notes[0], ctx.notes
    print(f"roofline reader: ok ({ctx.notes[0]})")


if __name__ == "__main__":
    check_stats()
    check_laws()
    check_roofline()
    check_roofline_reader()
    check_trace()
    print("selfcheck passed")
