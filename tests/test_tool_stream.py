"""Incremental tool-call streaming (parsers/incremental.py + jail.py):
per-dialect streaming parity, seeded chunk-boundary fuzz across all 7
dialects, the typed degradation ladder, and bit-identical replay under
the FaultPlane (the ISSUE 15 acceptance proofs at the parser layer; the
SSE wire-level proofs live in tests/test_parsers_http.py)."""

import json
import random

import pytest

from dynamo_tpu.parsers import (
    ArgsDelta,
    CallEnd,
    CallStart,
    ContentDelta,
    ToolCallJail,
    ToolCallParseError,
    detect_and_parse_tool_calls,
)

# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------


def split_at(text, rng, n_cuts):
    """Re-split one corpus text at n randomized delta boundaries."""
    if len(text) < 2 or n_cuts <= 0:
        return [text]
    cuts = sorted(rng.sample(range(1, len(text)), min(n_cuts, len(text) - 1)))
    parts, last = [], 0
    for c in cuts:
        parts.append(text[last:c])
        last = c
    parts.append(text[last:])
    return parts


def stream(deltas, dialect=None, **kw):
    """Feed deltas through a fresh jail → (calls, content, jail).
    calls: index → {name, args (concatenated), error, degraded}."""
    jail = ToolCallJail(dialect, **kw)
    events = []
    for d in deltas:
        events += jail.feed(d)
    events += jail.finish()
    calls, content = {}, []
    for e in events:
        if isinstance(e, ContentDelta):
            content.append(e.text)
        elif isinstance(e, CallStart):
            calls[e.index] = {
                "name": e.name, "args": "", "error": None, "degraded": False,
                "id": e.call_id,
            }
        elif isinstance(e, ArgsDelta):
            calls[e.index]["args"] += e.text
        elif isinstance(e, CallEnd):
            calls[e.index]["error"] = e.error
            calls[e.index]["degraded"] = e.degraded
    # Invariant: every started call was closed (never a dangling call).
    assert not jail.open_calls
    return calls, "".join(content), jail


DSML_TEXT = (
    'before <｜DSML｜function_calls>'
    '<｜DSML｜invoke name="search">'
    '<｜DSML｜parameter name="query" string="true">cats</｜DSML｜parameter>'
    '<｜DSML｜parameter name="limit" string="false">5</｜DSML｜parameter>'
    '</｜DSML｜invoke>'
    '<｜DSML｜invoke name="fetch">'
    '<｜DSML｜parameter name="url" string="true">http://x</｜DSML｜parameter>'
    '</｜DSML｜invoke>'
    '</｜DSML｜function_calls> after'
)

# dialect → list of VALID corpus texts (each compared against the
# one-shot parser at randomized delta boundaries).
CORPUS = {
    "hermes": [
        'Check: <tool_call>\n{"name": "search", "arguments": '
        '{"q": "tpu", "k": [1, 2]}}\n</tool_call> done',
        '<tool_call>{"name": "a", "arguments": {}}</tool_call> and '
        '<tool_call>{"name": "b", "arguments": {"x": {"y": "z,w"}}}'
        '</tool_call>',
    ],
    "mistral": [
        '[TOOL_CALLS][{"name": "add", "arguments": {"a": 1, "b": 2}}, '
        '{"name": "mul", "arguments": {"a": 3}}]',
    ],
    "xml": [
        '<tool_call><function=lookup><parameter=key>abc</parameter>'
        '<parameter=count>3</parameter></function></tool_call> trailing',
    ],
    "harmony": [
        '<|channel|>analysis<|message|>thinking about weather<|end|>'
        '<|start|>assistant<|channel|>commentary to=functions.w '
        '<|constrain|>json<|message|>{"city":"SF"}<|call|>'
        '<|channel|>final<|message|>Here you go!<|end|>',
        # Non-object payloads: scalar and string finalize at the
        # terminator into the one-shot {"value": ...} shape.
        '<|channel|>commentary to=functions.n <|message|>12<|call|>'
        '<|channel|>final<|message|>ok<|end|>',
        '<|channel|>commentary to=functions.s <|message|>"hi there"'
        '<|call|><|channel|>final<|message|>done<|end|>',
    ],
    "dsml": [DSML_TEXT],
    "json": [
        '{"name": "get_weather", "arguments": {"city": "Paris"}}',
        '[{"name": "a", "arguments": {}}, '
        '{"name": "b", "parameters": {"x": 1}}]',
    ],
    "pythonic": [
        '[get_time(tz="UTC"), ping()]',
    ],
}

PINNED_ONLY = {"json", "pythonic"}


def one_shot(dialect, text):
    d = dialect if dialect in PINNED_ONLY else None
    return detect_and_parse_tool_calls(text, dialect=d)


# ---------------------------------------------------------------------------
# Valid-corpus parity fuzz: streamed result == one-shot result at every
# randomized re-split.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dialect", sorted(CORPUS))
def test_chunk_boundary_fuzz_parity(dialect):
    for ti, text in enumerate(CORPUS[dialect]):
        expected_calls, expected_rest = one_shot(dialect, text)
        assert expected_calls, f"corpus text {ti} must parse one-shot"
        for trial in range(25):
            rng = random.Random(f"fuzz:{dialect}:{ti}:{trial}")
            parts = split_at(text, rng, rng.randint(1, 24))
            calls, content, jail = stream(
                parts, dialect if dialect in PINNED_ONLY else None
            )
            assert jail.outcome() == "clean", (
                f"{dialect} trial {trial}: degraded {jail.degrade_reasons}"
            )
            assert [calls[i]["name"] for i in sorted(calls)] == [
                c.name for c in expected_calls
            ], f"{dialect} trial {trial} names"
            for i, exp in zip(sorted(calls), expected_calls):
                got = json.loads(calls[i]["args"])
                assert got == exp.arguments, (
                    f"{dialect} trial {trial} call {i}: "
                    f"{got} != {exp.arguments}"
                )
                assert calls[i]["error"] is None
            # Content parity (whitespace-normalized: the one-shot
            # parsers strip per-segment, streaming preserves interior
            # spacing exactly).
            assert " ".join(content.split()) == " ".join(
                expected_rest.split()
            )


def test_single_char_deltas_every_dialect():
    """The cruelest boundary split: one character per delta (every
    marker, tag, and escape straddles)."""
    for dialect, texts in CORPUS.items():
        expected_calls, _ = one_shot(dialect, texts[0])
        calls, _content, jail = stream(
            list(texts[0]), dialect if dialect in PINNED_ONLY else None
        )
        assert jail.outcome() == "clean", (dialect, jail.degrade_reasons)
        assert [calls[i]["name"] for i in sorted(calls)] == [
            c.name for c in expected_calls
        ]
        for i, exp in zip(sorted(calls), expected_calls):
            assert json.loads(calls[i]["args"]) == exp.arguments


def test_dsml_multibyte_marker_split_mid_codepoint():
    """The <｜DSML｜ marker's fullwidth bars: split at EVERY character
    boundary (including inside the marker, between multi-byte
    codepoints) — the jail must never mis-route or lose a byte."""
    text = DSML_TEXT
    expected_calls, expected_rest = one_shot("dsml", text)
    for cut in range(1, min(len(text), 80)):
        calls, content, jail = stream([text[:cut], text[cut:]])
        assert jail.outcome() == "clean", (cut, jail.degrade_reasons)
        assert [calls[i]["name"] for i in sorted(calls)] == [
            c.name for c in expected_calls
        ], f"cut {cut}"
        assert " ".join(content.split()) == " ".join(expected_rest.split())


# ---------------------------------------------------------------------------
# Streaming-specific semantics
# ---------------------------------------------------------------------------


def test_args_stream_incrementally_json_family():
    """Partial-JSON dialects: the arguments object streams out delta by
    delta — the number of ArgsDelta events grows with the number of
    deltas the args spanned (the old jail emitted exactly one blob)."""
    text = ('<tool_call>{"name": "f", "arguments": {"a": 1, "b": "xy", '
            '"c": [1, 2, 3]}}</tool_call>')
    parts = [text[i:i + 8] for i in range(0, len(text), 8)]
    jail = ToolCallJail()
    events = []
    first_args_at = None
    for pi, p in enumerate(parts):
        evs = jail.feed(p)
        if first_args_at is None and any(
            isinstance(e, ArgsDelta) for e in evs
        ):
            first_args_at = pi
        events += evs
    events += jail.finish()
    n_args = sum(1 for e in events if isinstance(e, ArgsDelta))
    assert n_args > 3, "arguments did not stream incrementally"
    # First argument byte long before the final delta.
    assert first_args_at is not None and first_args_at < len(parts) - 4


def test_name_emitted_as_soon_as_parseable():
    jail = ToolCallJail()
    evs = jail.feed('<tool_call>{"name": "get_weather"')
    assert any(isinstance(e, CallStart) for e in evs)
    assert evs[-1].name == "get_weather" if isinstance(
        evs[-1], CallStart
    ) else True


def test_args_before_name_buffered_then_flushed():
    """Keys in either order: arguments arriving before the name buffer
    and flush immediately after CallStart."""
    jail = ToolCallJail(dialect="json")
    evs = jail.feed('{"arguments": {"x": 1}, ')
    assert not any(isinstance(e, CallStart) for e in evs)
    evs2 = jail.feed('"name": "f"}')
    kinds = [type(e).__name__ for e in evs2]
    assert kinds.index("CallStart") < kinds.index("ArgsDelta")
    calls, _c, _j = stream(['{"arguments": {"x": 1}, "name": "f"}'],
                           dialect="json")
    assert json.loads(calls[0]["args"]) == {"x": 1}


def test_two_calls_with_content_between():
    """Back-to-back calls with content between them: indices keep
    counting, content interleaves in order."""
    calls, content, jail = stream([
        'first <tool_call>{"name": "a", "arguments": {}}</tool_call>',
        ' middle ',
        '<tool_call>{"name": "b", "arguments": {"k": 1}}</tool_call> end',
    ])
    assert [calls[i]["name"] for i in sorted(calls)] == ["a", "b"]
    assert sorted(calls) == [0, 1]
    assert content == "first  middle  end"


def test_harmony_analysis_vs_commentary_routing():
    """Harmony routing: analysis is dropped (reasoning), commentary
    to=functions.* is a call, final is content — across split deltas."""
    text = CORPUS["harmony"][0]
    for trial in range(10):
        rng = random.Random(f"harmony-route:{trial}")
        parts = split_at(text, rng, 12)
        calls, content, _ = stream(parts)
        assert [calls[i]["name"] for i in sorted(calls)] == ["w"]
        assert json.loads(calls[0]["args"]) == {"city": "SF"}
        assert "thinking" not in content
        assert content.strip() == "Here you go!"


def test_pythonic_nested_json_inside_string_arg():
    """Nested JSON (with commas, brackets, quotes) inside a pythonic
    string argument must not split the literal early."""
    payload = '{"a": [1, 2], "b": "x,y", "c": {"d": ")"}}'
    text = f"[post(body='{payload}', n=2)]"
    for trial in range(10):
        rng = random.Random(f"pyn:{trial}")
        calls, _content, jail = stream(
            split_at(text, rng, 10), dialect="pythonic"
        )
        assert jail.outcome() == "clean", jail.degrade_reasons
        args = json.loads(calls[0]["args"])
        assert args == {"body": payload, "n": 2}


def test_string_arguments_degraded_wrap_streaming():
    """A string-valued arguments field that is not JSON becomes the
    lossy __raw__ wrap with degraded=true — same as unary _normalize."""
    calls, _c, jail = stream(
        ['{"name": "f", "arguments": "not { json"}'], dialect="json"
    )
    assert json.loads(calls[0]["args"]) == {"__raw__": "not { json"}
    assert calls[0]["degraded"] is True
    assert calls[0]["error"] is None


# ---------------------------------------------------------------------------
# Malformed corpus: the degradation ladder — every stream completes.
# ---------------------------------------------------------------------------

MALFORMED = [
    # (deltas, dialect) — truncations, bad nesting, drift.
    (['<tool_call>{"name": "f", "arguments": {"a": [1, 2'], None),
    (['<tool_call>{"name": "f", "arguments": {"a": 1]]}'], None),
    (['<tool_call>garbage not json</tool_call>'], None),
    (['[TOOL_CALLS]{"name": "f", "argu'], None),
    (['[TOOL_CALLS] definitely prose'], None),
    (['<｜DSML｜function_calls><｜DSML｜invoke name="x">'
      '<｜DSML｜parameter name="k" string="true">v'], None),
    (['<｜DSML｜oops>not the block'], None),
    (['<|channel|>commentary to=functions.f <|message|>{"a": '], None),
    (['<|channel|>weird<|message|>body<|end|>'], None),
    (['[f(a=1, b'], "pythonic"),
    (['[f(1, 2)]'], "pythonic"),
    (['{"name": "f", "arguments": {"x": '], "json"),
    (['{"no_name_here": 1}'], "json"),
    (['<tool_call><function=f><parameter=k>v'], None),
    (['<tool_call><wrong=f>'], None),
]


@pytest.mark.parametrize("case", range(len(MALFORMED)))
def test_malformed_completes_never_raises(case):
    deltas, dialect = MALFORMED[case]
    text = "".join(deltas)
    for trial in range(8):
        rng = random.Random(f"mal:{case}:{trial}")
        parts = split_at(text, rng, rng.randint(1, 12))
        calls, content, jail = stream(parts, dialect)
        # The ladder fired somewhere: every started call is sealed with
        # a typed error OR the jailed text came back as content.
        assert jail.degrade_reasons, (case, trial)
        for c in calls.values():
            assert c["error"] is None or isinstance(c["error"], str)
        # Nothing vanished silently: there were calls, content, or a
        # recorded degrade — and the jail is still usable.
        post = jail.feed("after") if not jail._finished else None


def test_truncated_call_seals_emitted_deltas():
    """Rung 1: a call whose deltas already reached the client is sealed
    with a CallEnd carrying the structured error."""
    jail = ToolCallJail()
    evs = jail.feed('<tool_call>{"name": "f", "arguments": {"a": 1, ')
    assert any(isinstance(e, ArgsDelta) for e in evs)
    evs2 = jail.finish()
    ends = [e for e in evs2 if isinstance(e, CallEnd)]
    assert len(ends) == 1 and ends[0].error == "truncated"
    assert jail.calls_started == 1 and jail.calls_done == 1


def test_degrade_after_emission_never_duplicates_call_text():
    """A whole malformed call arriving in ONE delta (CallStart + the
    degrade land inside one step): the sealed call must NOT also replay
    its raw text as content — the client would see the call twice."""
    calls, content, jail = stream(
        ['pre <tool_call>{"name": "f", "arguments": {"a": 1]]}'])
    assert calls[0]["name"] == "f"
    assert calls[0]["error"] == "bad_nesting"
    assert '"name"' not in content and "tool_call" not in content
    assert content == "pre "


def test_harmony_truncated_string_payload_sealed():
    """An unterminated string payload at EOF is a truncated seal, not a
    silently-clean empty call."""
    calls, _c, jail = stream(
        ['<|channel|>commentary to=functions.f <|message|>"partial str'])
    assert calls[0]["error"] == "truncated"
    assert jail.outcome() == "degraded"


def test_unstarted_jailed_text_degrades_to_content():
    """Rung 2: jailed text that never produced a call comes back as
    content deltas, byte-exact."""
    raw = '<tool_call>{"nam'
    calls, content, jail = stream([raw])
    assert calls == {}
    assert content == raw


def test_drift_mid_stream_recovers_detection():
    """A drifted call degrades, and the jail KEEPS WORKING: a later
    well-formed call on the same stream still streams."""
    jail = ToolCallJail()
    evs = jail.feed('[TOOL_CALLS]nonsense then ')
    evs += jail.feed('<tool_call>{"name": "ok", "arguments": {}}</tool_call>')
    evs += jail.finish()
    starts = [e for e in evs if isinstance(e, CallStart)]
    assert [s.name for s in starts] == ["ok"]
    assert jail.degrade_reasons  # the drift was counted


def test_buffer_cap_bounds_every_dialect():
    """A dialect that never closes cannot grow host memory: unresolved
    buffer is bounded by the cap, then the stream passes through."""
    # Each opener leaves the machine in a state that legitimately
    # BUFFERS what follows (an unclosed name string / parameter value /
    # channel header) — the adversarial growth case.
    openers = {
        None: '<tool_call>{"name": "',
        "dsml": ('<｜DSML｜function_calls><｜DSML｜invoke name="x">'
                 '<｜DSML｜parameter name="k" string="true">'),
        "harmony": "<|channel|>commentary",
    }
    for dialect, opener in openers.items():
        jail = ToolCallJail(dialect, buffer_cap=256)
        jail.feed(opener)
        total = 0
        for _ in range(50):
            evs = jail.feed("x" * 64)
            total += sum(
                len(e.text) for e in evs if isinstance(e, ContentDelta)
            )
        assert "buffer_cap" in jail.degrade_reasons, dialect
        # After the cap: passthrough, bounded internal state.
        assert jail._machine is None
        assert len(jail._buf) <= 256


# ---------------------------------------------------------------------------
# FaultPlane: deterministic parser-death replay (parser.jail.feed seam)
# ---------------------------------------------------------------------------


def _run_with_plan(plan_dict):
    from dynamo_tpu.runtime import fault_names as fn
    from dynamo_tpu.runtime.faults import FaultPlan, armed

    deltas = [
        'hello <tool_call>{"name": "f", ',
        '"arguments": {"a": 1}}</tool_call>',
        ' bye',
    ]
    trace = []
    events = []
    err = None
    ids = iter(f"call-replay-{i}" for i in range(100))
    with armed(FaultPlan.from_dict(plan_dict)) as plane:
        # Deterministic call ids: bit-identical replay covers the full
        # event stream, not the stream modulo random ids.
        jail = ToolCallJail(call_id_factory=lambda: next(ids))
        try:
            for d in deltas:
                events += jail.feed(d)
            events += jail.finish()
        except ToolCallParseError as exc:
            err = str(exc)
        trace = list(plane.trace)
    return [repr(e) for e in events], err, [tuple(t) for t in trace]


def test_injected_parser_death_is_typed_and_replays_bit_identically():
    from dynamo_tpu.runtime import fault_names as fn

    plan = {
        "seed": 7,
        "rules": [
            # Hit indices are 1-based: hit 2 = the SECOND feed, after the
            # first feed's content already reached the client.
            {"point": fn.PARSER_JAIL_FEED, "kind": "error", "at": [2]},
        ],
    }
    ev1, err1, tr1 = _run_with_plan(plan)
    ev2, err2, tr2 = _run_with_plan(plan)
    assert err1 is not None, "injected fault must surface as parse error"
    assert (ev1, err1, tr1) == (ev2, err2, tr2), "replay diverged"
    # The events before the death were already delivered (hit 1 = the
    # second feed; the first feed's content delta reached the client).
    assert any("hello" in e for e in ev1)


def test_parser_exception_counted_on_plane():
    from dynamo_tpu.parsers.observe import parser_plane
    from dynamo_tpu.runtime import fault_names as fn
    from dynamo_tpu.runtime.faults import FaultPlan, armed

    plane = parser_plane()
    before = plane.exceptions
    plan = FaultPlan.from_dict({
        "seed": 3,
        "rules": [{"point": fn.PARSER_JAIL_FEED, "kind": "error",
                   "at": [1]}],
    })
    with armed(plan):
        jail = ToolCallJail()
        with pytest.raises(ToolCallParseError):
            jail.feed("x")
    assert plane.exceptions == before + 1


# ---------------------------------------------------------------------------
# Observability closures
# ---------------------------------------------------------------------------


def test_parser_metrics_cover_all_parser_family():
    from dynamo_tpu.parsers.observe import ParserMetrics
    from dynamo_tpu.runtime import metric_names as mn

    emitted = {m.name for m in ParserMetrics().registry._metrics}
    assert emitted == set(mn.ALL_PARSER)


def test_parser_flight_ring_records_lifecycle():
    from dynamo_tpu.parsers.observe import parser_plane

    plane = parser_plane()
    n0 = plane.flight.total
    stream(['<tool_call>{"name": "f", "arguments": {}}</tool_call>'])
    kinds = {e["kind"] for e in plane.flight.snapshot()}
    assert plane.flight.total > n0
    assert {"jail_commit", "call"} <= kinds


def test_degrades_counted_per_dialect_and_reason():
    from dynamo_tpu.parsers.observe import parser_plane

    plane = parser_plane()
    before = plane.metrics.degraded_calls.value(
        dialect="hermes", reason="truncated"
    )
    stream(['<tool_call>{"name": "f", "arguments": {"x": 1'])
    after = plane.metrics.degraded_calls.value(
        dialect="hermes", reason="truncated"
    )
    assert after == before + 1


def test_clean_streams_leave_the_recovery_counters_alone():
    """Zero spurious activations: well-formed calls of every dialect, split
    a character at a time, move neither the process-wide degrade counters
    nor the exception counter, and the ring sees commits and calls only."""
    from dynamo_tpu.parsers.observe import parser_plane

    plane = parser_plane()
    degrades0, exceptions0 = dict(plane.degrades), plane.exceptions
    events0 = plane.flight.total
    calls = 0
    for dialect, texts in sorted(CORPUS.items()):
        for text in texts:
            got, _content, jail = stream(
                list(text), dialect if dialect in PINNED_ONLY else None
            )
            assert jail.outcome() == "clean", (dialect, jail.degrade_reasons)
            calls += len(got)
    assert calls >= len(CORPUS)
    assert dict(plane.degrades) == degrades0
    assert plane.exceptions == exceptions0
    new = plane.flight.snapshot()[-(plane.flight.total - events0):]
    assert new and {e["kind"] for e in new} <= {"jail_commit", "call"}
