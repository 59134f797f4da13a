"""Unified CLI (VERDICT #10; ref: launch/dynamo-run/src/opt.rs +
entrypoint/input.rs batch/stdin/text inputs)."""

import json
import os
import subprocess
import sys

import pytest

ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONUNBUFFERED": "1"}


def run_cli(args, input_text=None, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.cli", *args],
        input=input_text,
        capture_output=True,
        text=True,
        env=ENV,
        timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def test_env_command_prints_registry():
    res = run_cli(["env"])
    assert res.returncode == 0
    assert "DYN_TPU_DISCOVERY" in res.stdout
    assert "default=" in res.stdout


def test_batch_mode_writes_jsonl(tmp_path):
    batch = tmp_path / "in.jsonl"
    out = tmp_path / "out.jsonl"
    batch.write_text('{"text": "hello"}\n{"prompt": "world"}\n')
    res = run_cli(
        ["run", "--input", f"batch:{batch}", "--model", "mock",
         "--max-tokens", "4", "--out", str(out)]
    )
    assert res.returncode == 0, res.stderr
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["prompt"] == "hello"
    assert all(l["tokens"] == 4 for l in lines)
    assert "batch done: 2 requests" in res.stderr


def test_stdin_mode():
    res = run_cli(
        ["run", "--input", "stdin", "--model", "mock", "--max-tokens", "3"],
        input_text="one\ntwo\n",
    )
    assert res.returncode == 0, res.stderr
    assert len(res.stdout.splitlines()) == 2


def test_batch_mode_real_engine(tmp_path):
    """The tiny JaxEngine path (builtin config, random weights)."""
    batch = tmp_path / "in.jsonl"
    batch.write_text('{"text": "the quick brown fox"}\n')
    res = run_cli(
        ["run", "--input", f"batch:{batch}", "--model", "tiny",
         "--max-tokens", "3", "--num-kv-blocks", "64"],
        timeout=420,
    )
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout.splitlines()[0])
    assert doc["tokens"] == 3


def test_unknown_input_rejected():
    res = run_cli(["run", "--input", "carrier-pigeon", "--model", "mock"])
    assert res.returncode != 0
    assert "unknown --input" in res.stderr


def test_service_delegation_help():
    res = run_cli(["mocker", "--help"])
    assert res.returncode == 0
    assert "--model-name" in res.stdout


async def test_observe_snapshot_against_live_worker(capsys):
    """`dynamo-tpu observe` fetches /debug/memory, /debug/compiles and
    /debug/flight from a running worker's system server and pretty-prints
    them (in-process: a subprocess would pay a full engine compile)."""
    import argparse

    from dynamo_tpu.cli.run import add_observe_args, main_observe
    from dynamo_tpu.runtime.system_server import (
        SystemStatusServer,
        attach_engine,
    )
    from tests.test_jax_engine import make_engine, req, run_one

    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        await run_one(engine, req(range(10, 20), max_tokens=3))
        parser = argparse.ArgumentParser()
        add_observe_args(parser)
        args = parser.parse_args(["--port", str(server.port)])
        await main_observe(args)
        out = capsys.readouterr().out
        assert "device memory" in out and "kv_cache" in out
        assert "compiled programs" in out and "runner.decode_state" in out
        assert "flight recorder" in out and "dispatch" in out

        args = parser.parse_args(["--port", str(server.port), "--json"])
        await main_observe(args)
        doc = json.loads(capsys.readouterr().out)
        assert set(doc) == {"memory", "compiles", "flight"}
    finally:
        await server.stop()
        await engine.stop()


async def test_observe_trajectory_against_live_worker(capsys):
    """`dynamo-tpu observe trajectory <trace_id>` pretty-prints the
    stitched view (phases, per-hop spans, dominant phase) from a live
    in-process worker's /debug/trajectory endpoint."""
    import argparse

    from dynamo_tpu.cli.run import add_observe_args, main_observe
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.system_server import (
        SystemStatusServer,
        attach_engine,
    )
    from dynamo_tpu.runtime.trajectory import global_store
    from dynamo_tpu.utils.tracing import span
    from tests.test_jax_engine import make_engine, req

    global_store()  # attach the store to the tracer BEFORE spans flow
    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        from dynamo_tpu.runtime.engine import collect

        ctx = Context(baggage={})
        with span("http.chat_completions", ctx, model="tiny") as root:
            await collect(
                engine.generate(req(range(10, 20), max_tokens=3), ctx)
            )

        parser = argparse.ArgumentParser()
        add_observe_args(parser)
        args = parser.parse_args(
            ["trajectory", root.trace_id, "--port", str(server.port)]
        )
        await main_observe(args)
        out = capsys.readouterr().out
        assert f"trajectory {root.trace_id}" in out
        assert "phases:" in out and "dominant" in out
        assert "http.chat_completions" in out

        # Index view (no trace id) lists recent trajectories.
        args = parser.parse_args(["trajectory", "--port", str(server.port)])
        await main_observe(args)
        out = capsys.readouterr().out
        assert "trajectories" in out and root.trace_id in out

        args = parser.parse_args(
            ["trajectory", root.trace_id, "--port", str(server.port),
             "--json"]
        )
        await main_observe(args)
        doc = json.loads(capsys.readouterr().out)
        assert doc["trace_id"] == root.trace_id
        assert set(doc["phases"]) == {
            "queue", "prefill", "kv_transfer", "decode", "handoff_stall",
            "overhead",
        }
    finally:
        await server.stop()
        await engine.stop()


async def test_observe_kvcache_against_live_worker(capsys):
    """`dynamo-tpu observe kvcache` pretty-prints the KV-reuse plane (hit
    rate, cache ROI, sketch health, hot prefixes) from a live in-process
    worker's /debug/kvcache endpoints."""
    import argparse

    from dynamo_tpu.cli.run import add_observe_args, main_observe
    from dynamo_tpu.runtime.kv_reuse_observe import global_plane
    from dynamo_tpu.runtime.system_server import (
        SystemStatusServer,
        attach_engine,
    )
    from tests.test_jax_engine import make_engine, req, run_one

    reused0 = global_plane().metrics.reused_tokens.value()
    engine, _ = make_engine()
    server = SystemStatusServer(host="127.0.0.1", port=0)
    attach_engine(server, engine)
    await server.start()
    try:
        # Same 16-token prompt twice: the second admission prefix-hits.
        await run_one(engine, req(range(10, 26), max_tokens=3))
        await run_one(engine, req(range(10, 26), max_tokens=3))
        parser = argparse.ArgumentParser()
        add_observe_args(parser)
        args = parser.parse_args(["kvcache", "--port", str(server.port)])
        await main_observe(args)
        out = capsys.readouterr().out
        assert "kv reuse" in out and "hit rate" in out
        assert "prefill tokens" in out and "sketch" in out
        assert "hot prefixes" in out

        args = parser.parse_args(
            ["kvcache", "--port", str(server.port), "--json"]
        )
        await main_observe(args)
        doc = json.loads(capsys.readouterr().out)
        assert set(doc) == {"kvcache", "prefixes"}
        # The replayed prompt's cached blocks show up as reused tokens
        # (>= : the plane is process-global, other tests feed it too).
        assert doc["kvcache"]["reused_prefill_tokens"] >= reused0 + 12
        assert doc["kvcache"]["sketch"]["capacity"] > 0
        assert doc["prefixes"]["prefixes"]  # sketch tracked the anchor
    finally:
        await server.stop()
        await engine.stop()


async def test_debug_kvcache_200_without_engine():
    """/debug/kvcache serves 200 on a bare system server (mock attach /
    partial engine): the plane is process-global, never engine-owned."""
    import aiohttp

    from dynamo_tpu.runtime.system_server import SystemStatusServer

    server = SystemStatusServer(host="127.0.0.1", port=0)
    await server.start()
    try:
        async with aiohttp.ClientSession() as session:
            for path in ("/debug/kvcache", "/debug/kvcache/prefixes"):
                url = f"http://127.0.0.1:{server.port}{path}"
                async with session.get(url) as r:
                    assert r.status == 200
                    doc = await r.json()
                    assert "sketch" in doc
            # The metrics surface carries the ALL_KVCACHE family too.
            url = f"http://127.0.0.1:{server.port}/metrics"
            async with session.get(url) as r:
                assert r.status == 200
                body = await r.text()
                assert "dynamo_tpu_kvcache_misses_total" in body
    finally:
        await server.stop()


# -- lint --------------------------------------------------------------------


def test_lint_clean_over_package():
    """`dynamo-tpu lint` over the shipped package: zero non-baselined
    findings, exit 0."""
    res = run_cli(["lint"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "dynlint: clean" in res.stderr


def test_lint_json_format():
    res = run_cli(["lint", "--format", "json"])
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["ok"] is True and doc["new"] == []


def test_lint_detects_and_baselines_new_findings(tmp_path):
    """Exit 1 on a fresh finding; --write-baseline grandfathers it; the
    baselined run exits 0 again."""
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "bad.py").write_text(
        "def f(fn):\n"
        "    try:\n"
        "        fn()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    baseline = tmp_path / "baseline.json"

    res = run_cli(["lint", "--root", str(tree), "--baseline", ""])
    assert res.returncode == 1
    assert "DYN003" in res.stdout and "bad.py" in res.stdout

    res = run_cli(
        ["lint", "--root", str(tree), "--baseline", str(baseline),
         "--write-baseline"]
    )
    assert res.returncode == 0 and baseline.exists()

    res = run_cli(["lint", "--root", str(tree), "--baseline", str(baseline)])
    assert res.returncode == 0
    assert "grandfathered" in res.stderr


def test_lint_rejects_unknown_rule():
    res = run_cli(["lint", "--rules", "DYN999"])
    assert res.returncode == 2
    assert "unknown rule" in res.stderr


def test_lint_explain_prints_catalog_entry():
    res = run_cli(["lint", "--explain", "DYN007"])
    assert res.returncode == 0
    assert "DYN007" in res.stdout
    assert "get_running_loop" in res.stdout


def test_lint_explain_unknown_rule():
    res = run_cli(["lint", "--explain", "DYN999"])
    assert res.returncode == 2
    assert "unknown rule" in res.stderr


def test_env_markdown_emits_reference_table():
    res = run_cli(["env", "--markdown"])
    assert res.returncode == 0
    assert "# Configuration knob reference" in res.stdout
    assert "DYN_TPU_KV_CHUNK_BYTES" in res.stdout


def test_lint_foreign_root_runs_portable_rules_only():
    """A --root outside the package must not drown in repo-config
    mismatch noise (hot-path roots, metric registry, ring owners): a
    clean foreign tree exits 0 under the portable rules."""
    good = os.path.join(
        os.path.dirname(__file__), "fixtures", "dynlint", "dyn003_good"
    )
    res = run_cli(["lint", "--root", good, "--baseline", ""])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "dynlint: clean" in res.stderr


def test_lint_foreign_root_rejects_repo_scoped_rules(tmp_path):
    """Explicitly asking for a repo-config rule on a foreign tree must
    error, not silently report clean."""
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "ok.py").write_text("x = 1\n")
    res = run_cli(
        ["lint", "--root", str(tree), "--baseline", "", "--rules", "DYN004"]
    )
    assert res.returncode == 2
    assert "disabled for a foreign --root" in res.stderr


def test_lint_write_baseline_refuses_foreign_clobber(tmp_path):
    """--write-baseline from a foreign --root must never overwrite the
    checked-in package baseline (explicitly or via the default)."""
    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "ok.py").write_text("x = 1\n")
    res = run_cli(["lint", "--root", str(tree), "--write-baseline"])
    assert res.returncode == 2
    assert "refusing" in res.stderr
    res = run_cli(
        ["lint", "--root", str(tree), "--baseline", "", "--write-baseline"]
    )
    assert res.returncode == 2
    assert "needs a --baseline PATH" in res.stderr
