#!/usr/bin/env python3
"""chip_smoke.py — does the serving path start and answer on the chip?

Drives the README quick-start cluster once, through the entry points a user
calls — ``python -m dynamo_tpu.discd``, ``python -m dynamo_tpu.worker
--model qwen3-8b --quantization int8``, ``python -m dynamo_tpu.frontend`` —
at the published Qwen3-8B widths (all 36 layers, random weights from the
seed), sends a few OpenAI requests over HTTP, and checks what came out by
the repo's own means: token counts and finish reasons, the device the
worker says it holds (``/debug/memory``), that decode bursts ran and what
attention served them (``/engine/stats``), the compiles
(``/debug/compiles``) and the prefix hit (``/debug/kvcache``). Then it
starts the worker a second time, which must be served from the compile cache, and compiles every Pallas kernel against
its XLA reference (``python -m dynamo_tpu.ops.pallas.chip_check``). On a
host with four chips it goes on to a tensor-parallel worker over all four
and a prefill/decode pair on two different chips.

This process NEVER imports JAX, nor a module that does: a chip belongs to
one process at a time, and a parent that had touched JAX would hold it
while its children fail or hang. Everything that needs the device is a
child with a file-backed log (a PIPE nobody drains blocks the child).
Stages run one after another and a stage's processes are gone before the
next starts.

Any failed step, any child that exits early, any assertion: non-zero exit
with the tail of the offending log and no result line. No chip: non-zero.
The last line of a passing run's stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Times printed here are set-up times (spawn → first token, compiles
included), not speed.

    python chip_smoke.py                   # on the chip (send it through the chip tool)
    python chip_smoke.py --rehearse-cpu    # control-flow rehearsal, --model tiny;
                                           # its last line says it was NOT a chip run
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from dynamo_tpu.utils.jax_env import compile_cache_dir  # jax-free by contract

HERE = os.path.dirname(os.path.realpath(__file__))
HOST = "127.0.0.1"
MAX_TOKENS = 32
KV_BLOCKS = 1024  # sized explicitly: stage_serve prints what it holds
BLOCK_SIZE = 16

# Each chat prompt renders to 163-174 tokens under the builtin tokenizer,
# so prompt + 32 generated + the decode lookahead stays inside ONE pow2
# block-table bucket (16 blocks = 256 tokens): every new prefill bucket and
# decode width is a compile of a 36-layer unrolled program. The first
# renders to 170 = 10 full blocks + 10 tokens, so its repeat prefills a
# 10-token tail over a cached prefix — the chunk shape that reaches the
# Pallas prefill kernel (fresh prompts attend densely, without it).
_TOPICS = [
    "Paged key value caches let a serving engine share memory "
    "between many requests of different lengths without fragmentation. ",
    "Continuous batching admits new sequences into a running decode batch "
    "as soon as a slot frees up instead of waiting for the batch to end. ",
    "Disaggregated serving runs the prompt on one pool of workers and the "
    "token by token decode on another pool and moves the cache between. ",
    "Tensor parallel layers split attention heads and feed forward columns "
    "across chips and add the partial results back with one reduction. ",
]
_ASK = (
    "Explain the idea in plain words, then list two costs and one benefit "
    "for a small team. "
)
CHAT_PROMPTS = [t + _ASK for t in _TOPICS]
COMPLETION_PROMPT = (
    "A router that knows which worker already holds a prompt prefix can "
    "send the request there and skip most of the prefill work. " * 2
)
LOG_POISON = ("Task was destroyed", "Traceback (most recent call last)")
STAGES = ["serve", "again", "kernels", "four-chip"]  # the probe always runs


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def _die_with_parent() -> None:
    """preexec: SIGKILL this child when the parent dies, on every exit path
    the parent cannot handle itself (SIGKILL, crash)."""
    PR_SET_PDEATHSIG = 1
    ctypes.CDLL("libc.so.6", use_errno=True).prctl(
        PR_SET_PDEATHSIG, signal.SIGKILL
    )


class Child:
    """One child process with a file-backed log."""

    live: List["Child"] = []

    def __init__(self, name: str, argv: List[str], env: Dict[str, str],
                 logdir: str) -> None:
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=HERE, stdout=self._log,
            stderr=subprocess.STDOUT, preexec_fn=_die_with_parent,
        )
        Child.live.append(self)

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def tail(self, nbytes: int = 4000) -> str:
        return self.log_text()[-nbytes:]

    def running(self) -> bool:
        return self.proc.poll() is None

    def stop(self, sig: int = signal.SIGTERM, timeout: float = 90.0) -> int:
        """Signal, wait, and return the exit code. Escalates to SIGKILL on
        timeout and says so (a worker that cannot drain is a failure)."""
        if self.running():
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                self._close()
                raise SmokeFailure(
                    f"{self.name} did not exit within {timeout:.0f}s of "
                    f"signal {sig}; killed\n--- {self.name} log tail ---\n"
                    f"{self.tail()}"
                )
        self._close()
        return self.proc.returncode

    def run_to_end(self, timeout: float) -> int:
        """Wait for a child that ends by itself; kill it at the timeout.
        Returns its exit code."""
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
        return self.stop()

    def tagged_json(self, tag: str) -> Optional[Any]:
        """The JSON after ``tag`` on the last log line that starts with it."""
        lines = [l for l in self.log_text().splitlines() if l.startswith(tag)]
        return json.loads(lines[-1][len(tag):]) if lines else None

    def _close(self) -> None:
        if self in Child.live:
            Child.live.remove(self)
        self._log.close()


def kill_all_children() -> None:
    for child in list(Child.live):
        if child.running():
            child.proc.kill()
        try:
            child.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        child._close()


def check_alive(children: List[Child]) -> None:
    for child in children:
        if not child.running():
            raise SmokeFailure(
                f"{child.name} exited early with code "
                f"{child.proc.returncode}\n--- {child.name} log tail ---\n"
                f"{child.tail()}"
            )


def wait_for_line(child: Child, needle: str, timeout: float,
                  others: List[Child]) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if needle in child.log_text():
            return
        check_alive([child] + others)
        time.sleep(0.2)
    raise SmokeFailure(
        f"{child.name}: {needle!r} not seen within {timeout:.0f}s\n"
        f"--- {child.name} log tail ---\n{child.tail()}"
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------


def http_json(url: str, body: Optional[dict] = None, timeout: float = 60.0) -> Any:
    """GET, or POST when there is a body; the decoded JSON reply."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as exc:
        raise SmokeFailure(
            f"{url} -> HTTP {exc.code}: {exc.read().decode()[:2000]}"
        ) from exc


def stream_chat(base: str, model: str, prompt: str,
                timeout: float) -> Dict[str, Any]:
    """One streamed chat completion. Returns counts and arrival times —
    never text: builtin presets serve a 383-entry tokenizer, ids above it
    decode to "", so at a 151,936-wide vocabulary the content is almost
    empty and proves nothing."""
    body = {
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": MAX_TOKENS,
        "stream": True,
        "stream_options": {"include_usage": True},
        "nvext": {"ignore_eos": True},
    }
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    arrivals: List[float] = []
    finish, usage, errors = None, None, []
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                frame = json.loads(line[len("data: "):])
                if "error" in frame:
                    errors.append(frame["error"])
                    continue
                if frame.get("usage"):
                    usage = frame["usage"]
                # One frame per engine output (the prefill's first token,
                # then one per decode burst) whether or not its tokens
                # decode to any text.
                for choice in frame.get("choices") or []:
                    arrivals.append(time.monotonic())
                    if choice.get("finish_reason"):
                        finish = choice["finish_reason"]
    except urllib.error.HTTPError as exc:
        raise SmokeFailure(
            f"chat stream -> HTTP {exc.code}: {exc.read().decode()[:2000]}"
        ) from exc
    return {
        "arrivals": arrivals, "finish_reason": finish, "usage": usage,
        "errors": errors,
    }


def assert_stream(tag: str, res: Dict[str, Any]) -> None:
    if res["errors"]:
        raise SmokeFailure(f"{tag}: error frame {res['errors'][0]}")
    if res["finish_reason"] != "length":
        raise SmokeFailure(f"{tag}: finish_reason {res['finish_reason']!r}")
    usage = res["usage"] or {}
    if usage.get("completion_tokens") != MAX_TOKENS:
        raise SmokeFailure(f"{tag}: usage {usage} (want {MAX_TOKENS} completion tokens)")
    if not 150 <= usage.get("prompt_tokens", 0) <= 250:
        raise SmokeFailure(f"{tag}: prompt_tokens {usage.get('prompt_tokens')} outside 150..250")
    if len(res["arrivals"]) < 2:
        raise SmokeFailure(
            f"{tag}: {len(res['arrivals'])} token frames — need a first "
            "token and at least one inter-token gap"
        )


# ---------------------------------------------------------------------------
# the cluster
# ---------------------------------------------------------------------------


class Cluster:
    """discd + worker(s) + frontend as child processes over
    discd/ZMQ/TCP, exactly the README quick start."""

    def __init__(self, tag: str, logdir: str, base_env: Dict[str, str]) -> None:
        self.tag = tag
        self.logdir = logdir
        self.disc_port, self.xsub, self.xpub = free_port(), free_port(), free_port()
        self.http_port = free_port()
        self.env = dict(base_env)
        self.env.update({
            "DYN_TPU_DISCOVERY": "discd",
            "DYN_TPU_DISCOVERY_ADDR": f"{HOST}:{self.disc_port}",
            "DYN_TPU_EVENT_PLANE": "zmq",
            "DYN_TPU_EVENT_PLANE_ADDR": f"{HOST}:{self.xsub}:{self.xpub}",
            "DYN_TPU_REQUEST_PLANE": "tcp",
            "PYTHONUNBUFFERED": "1",
        })
        self.discd: Optional[Child] = None
        self.frontend: Optional[Child] = None
        self.workers: List[Tuple[Child, int]] = []  # (child, system port)

    @property
    def base(self) -> str:
        return f"http://{HOST}:{self.http_port}"

    def children(self) -> List[Child]:
        out = [w for w, _ in self.workers]
        if self.discd:
            out.append(self.discd)
        if self.frontend:
            out.append(self.frontend)
        return out

    def start_discd(self) -> None:
        self.discd = Child(
            f"{self.tag}-discd",
            [sys.executable, "-m", "dynamo_tpu.discd", "--port",
             str(self.disc_port), "--xsub", str(self.xsub), "--xpub",
             str(self.xpub)],
            self.env, self.logdir,
        )
        wait_for_line(self.discd, "discd ready", 60, [])

    def spawn_worker(self, name: str, args: List[str],
                     env_extra: Optional[Dict[str, str]] = None) -> Child:
        port = free_port()
        env = dict(self.env, **(env_extra or {}))
        child = Child(
            f"{self.tag}-{name}",
            [sys.executable, "-m", "dynamo_tpu.worker", "--system-port",
             str(port), "--num-kv-blocks", str(KV_BLOCKS), *args],
            env, self.logdir,
        )
        self.workers.append((child, port))
        return child

    def wait_workers(self, timeout: float) -> None:
        for child, _ in self.workers:
            wait_for_line(child, "worker serving", timeout, self.children())

    def start_frontend(self, model: str) -> None:
        self.frontend = Child(
            f"{self.tag}-frontend",
            [sys.executable, "-m", "dynamo_tpu.frontend", "--host", HOST,
             "--http-port", str(self.http_port)],
            self.env, self.logdir,
        )
        wait_for_line(self.frontend, "frontend listening", 60, self.children())
        deadline = time.monotonic() + 60
        while True:
            ids = [m["id"] for m in http_json(self.base + "/v1/models")["data"]]
            if model in ids:
                return
            if time.monotonic() > deadline:
                raise SmokeFailure(f"model {model!r} never appeared: {ids}")
            check_alive(self.children())
            time.sleep(0.25)

    def system(self, idx: int, path: str, body: Optional[dict] = None) -> Any:
        """A route of worker ``idx``'s system server."""
        _, port = self.workers[idx]
        return http_json(f"http://{HOST}:{port}{path}", body)

    def stop(self) -> None:
        """SIGTERM frontend → workers → discd. Workers must drain and
        exit 0; no log may hold a destroyed task or a traceback."""
        problems = []
        if self.frontend:
            self.frontend.stop(timeout=30)  # no handler: dies of the signal
        for child, _ in self.workers:
            rc = child.stop(timeout=120)
            if rc != 0:
                problems.append(f"{child.name} exited {rc} after SIGTERM")
        if self.discd:
            self.discd.stop(timeout=30)
        for child in self.children():
            text = child.log_text()
            for poison in LOG_POISON:
                if poison in text:
                    at = text.index(poison)
                    problems.append(
                        f"{child.name} log holds {poison!r}:\n"
                        f"{text[max(0, at - 500): at + 1500]}"
                    )
        if problems:
            raise SmokeFailure("\n".join(problems))


# ---------------------------------------------------------------------------
# reads
# ---------------------------------------------------------------------------


def read_devices(cluster: Cluster, idx: int, want_platform: str) -> List[dict]:
    """The device rows of the process that HOLDS the devices. Read before
    any burst counter is trusted: off-chip nothing it counts is the chip's."""
    devices = cluster.system(idx, "/debug/memory")["devices"]
    if not devices or any(d.get("platform") != want_platform for d in devices):
        raise SmokeFailure(
            f"worker holds {[(d.get('platform'), d.get('device_kind')) for d in devices]}"
            f", want every device on {want_platform!r}"
        )
    return devices


def served_path(cluster: Cluster, idx: int, run: "Run", tp: int = 1) -> dict:
    """Decode bursts ran through the decode program, and the attention the
    runner chose is the platform's (the Pallas kernels on one chip; XLA on
    the CPU and under a mesh). Returns the stats."""
    stats = cluster.system(idx, "/engine/stats", body={})
    program = cluster.system(idx, "/debug/compiles")["programs"].get(
        "runner.decode_state", {})
    if stats["decode_steps"] < 1 or program.get("compiles", 0) < 1:
        raise SmokeFailure(
            f"no decode burst served: decode_steps={stats['decode_steps']}, "
            f"runner.decode_state={program}"
        )
    want = "pallas" if run.platform == "tpu" and tp == 1 else "xla"
    if stats["attention_impl"] != want:
        raise SmokeFailure(
            f"attention served from {stats['attention_impl']!r} "
            f"({stats['attention_reason']}), want {want!r} on {run.platform} "
            f"with tp={tp}"
        )
    return stats


def compile_snapshot(cluster: Cluster, idx: int) -> dict:
    snap = cluster.system(idx, "/debug/compiles")
    if snap["totals"]["storms"]:
        raise SmokeFailure(f"recompile storm: {json.dumps(snap['totals'])}")
    return snap


def cache_entries() -> set:
    d = compile_cache_dir()
    if not os.path.isdir(d):
        return set()
    return {n for n in os.listdir(d) if n.endswith("-cache")}


def metric_value(cluster: Cluster, idx: int, name: str) -> Optional[float]:
    """Sum of every sample of a metric family in the worker's Prometheus
    scrape; None when the family has no sample (a counter nothing
    incremented)."""
    _, port = cluster.workers[idx]
    with urllib.request.urlopen(f"http://{HOST}:{port}/metrics", timeout=30) as r:
        text = r.read().decode()
    samples = [
        float(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.split(" ")[0].split("{")[0] == name
    ]
    return sum(samples) if samples else None


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """What every stage needs: the children's environment, where their logs
    go, which model at which size, and what the probe found."""

    rehearse: bool
    env: Dict[str, str]
    logdir: str
    timeouts: argparse.Namespace  # start_timeout, request_timeout, kernel_timeout
    platform: str  # the platform every device row must name
    model_arg: str  # worker --model
    model: str  # its name on the wire
    serve_args: List[str]
    tp: int
    dev: Dict[str, Any] = field(default_factory=dict)  # filled by stage_probe

    def bring_up(self, tag: str,
                 workers: List[Tuple[str, List[str], Dict[str, str]]]) -> Cluster:
        """discd, then the workers (name, args, extra environment), then
        the frontend, each waited for."""
        cluster = Cluster(tag, self.logdir, self.env)
        cluster.start_discd()
        for name, args, env_extra in workers:
            cluster.spawn_worker(name, args, env_extra)
        cluster.wait_workers(self.timeouts.start_timeout)
        cluster.start_frontend(self.model)
        return cluster


def stage_probe(run: Run) -> None:
    """Ask a child which device JAX sees, and fail fast without a chip.
    The child exits (releasing the chip) before any worker starts."""
    code = (
        "import json, jax, jaxlib\n"
        "try:\n"
        "    import libtpu; lt = getattr(libtpu, '__version__', 'unknown')\n"
        "except ImportError:\n"
        "    lt = None\n"
        "d = jax.devices()\n"
        "print('PROBE ' + json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'count': len(d), 'jax': jax.__version__, "
        "'jaxlib': jaxlib.__version__, 'libtpu': lt}))\n"
    )
    child = Child("probe", [sys.executable, "-c", code], run.env, run.logdir)
    rc = child.run_to_end(180)
    dev = child.tagged_json("PROBE ")
    if rc != 0 or dev is None:
        raise SmokeFailure(f"device probe failed (rc={rc})\n{child.tail()}")
    print(f"[probe] platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
          f"libtpu={dev['libtpu']}", flush=True)
    if dev["platform"] != run.platform:
        raise SmokeFailure(
            f"JAX found platform {dev['platform']!r}, not {run.platform!r}: "
            "no accelerator, no smoke"
        )
    run.dev = dev


def run_requests(run: Run, cluster: Cluster, t_spawn: float) -> float:
    """Four concurrent streamed chats (staggered 0.2 s so the first is
    admitted alone — the same program the second start replays), the first
    prompt again (a prefix hit), one unary completion, /v1/models. Prints
    the counts; returns set-up seconds (spawn → first token of stream 0)."""
    timeout = run.timeouts.request_timeout
    results: List[Optional[dict]] = [None] * len(CHAT_PROMPTS)
    failures: List[BaseException] = []

    def one(i: int) -> None:
        try:
            results[i] = stream_chat(cluster.base, run.model, CHAT_PROMPTS[i], timeout)
        except BaseException as exc:  # re-raised on the main thread below
            failures.append(exc)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(CHAT_PROMPTS))]
    for t in threads:
        t.start()
        time.sleep(0.2)
    while any(t.is_alive() for t in threads):
        check_alive(cluster.children())
        time.sleep(0.5)
    if failures:
        raise failures[0]
    for i, res in enumerate(results):
        assert_stream(f"stream {i}", res)
    assert_stream(
        "repeat of stream 0",
        stream_chat(cluster.base, run.model, CHAT_PROMPTS[0], timeout),
    )
    unary = http_json(cluster.base + "/v1/completions", {
        "model": run.model, "prompt": COMPLETION_PROMPT,
        "max_tokens": MAX_TOKENS, "nvext": {"ignore_eos": True},
    }, timeout=timeout)
    if unary["choices"][0]["finish_reason"] != "length" or \
            unary["usage"]["completion_tokens"] != MAX_TOKENS:
        raise SmokeFailure(f"unary completion: {json.dumps(unary)[:600]}")
    ids = [m["id"] for m in http_json(cluster.base + "/v1/models")["data"]]
    if run.model not in ids:
        raise SmokeFailure(f"/v1/models lost {run.model!r}: {ids}")
    frames = [len(r["arrivals"]) for r in results]
    print(f"[{cluster.tag}] requests: {len(results) + 1} streams + 1 unary, "
          f"prompt tokens {[r['usage']['prompt_tokens'] for r in results]}, "
          f"{sum(frames)} token frames, {sum(frames) - len(frames)} "
          f"inter-frame gaps, all finish=length with {MAX_TOKENS} completion "
          "tokens")
    return results[0]["arrivals"][0] - t_spawn


def stage_serve(run: Run) -> Dict[str, Any]:
    """The quick-start cluster, cold. Every request of the set."""
    cache_before = cache_entries()
    cluster = run.bring_up("serve", [("worker", run.serve_args, {})])
    t_spawn = cluster.workers[0][0].t_spawn
    print(f"[serve] worker said 'serving' {time.monotonic() - t_spawn:.1f}s "
          "after spawn (nothing is compiled yet: there is no warm-up)", flush=True)
    setup_s = run_requests(run, cluster, t_spawn)

    devices = read_devices(cluster, 0, run.platform)
    ledger = cluster.system(0, "/debug/memory")["sources"]["engine"]
    weights, kv_bytes = ledger["params"], ledger["kv_cache"]
    mem0 = devices[0].get("memory_stats") or {}
    if run.platform == "tpu" and not 0 < weights < mem0.get("bytes_in_use", 0):
        raise SmokeFailure(
            f"device 0 bytes_in_use {mem0.get('bytes_in_use')} does not exceed "
            f"the weight bytes {weights}: the weights are not on the chip"
        )
    for _ in range(3):  # a burst in flight when the streams ended is reaped late
        stats = served_path(cluster, 0, run)
        scraped = metric_value(cluster, 0, "dynamo_tpu_engine_decode_steps")
        if scraped == stats["decode_steps"]:
            break
        time.sleep(0.5)
    else:
        raise SmokeFailure(
            f"/metrics decode_steps {scraped} disagrees with /engine/stats "
            f"{stats['decode_steps']}"
        )
    compiles = compile_snapshot(cluster, 0)
    kv = cluster.system(0, "/debug/kvcache")
    if (kv.get("hits") or {}).get("device", 0) < 1 or \
            kv.get("reused_prefill_tokens", 0) < BLOCK_SIZE:
        raise SmokeFailure(
            "no device prefix hit for the repeated prompt: "
            f"hits={kv.get('hits')} reused={kv.get('reused_prefill_tokens')}"
        )
    cluster.stop()
    router_index = [
        l.split("router index: ")[1].strip()
        for l in cluster.frontend.log_text().splitlines()
        if "router index: " in l
    ]
    logits_tmp = 16 * 151936 * 4 if run.model == "qwen3-8b" else 0
    print(f"[serve] device rows: "
          f"{[(d['id'], d['platform'], d['device_kind']) for d in devices]}")
    print(f"[serve] memory: weights {weights / 1e9:.2f} GB + KV pool "
          f"{kv_bytes / 1e9:.2f} GB ({KV_BLOCKS} blocks x {BLOCK_SIZE} tokens) "
          f"+ [16, 151936] f32 logits temporary {logits_tmp / 1e6:.1f} MB; "
          f"peak bytes in use {mem0.get('peak_bytes_in_use')} of limit "
          f"{mem0.get('bytes_limit')}")
    print(f"[serve] decode bursts reaped: {stats['decode_steps']} "
          f"(runner.decode_state: "
          f"{compiles['programs']['runner.decode_state']['compiles']} programs)")
    print(f"[serve] attention: {stats['attention_impl']} ({stats['attention_reason']})")
    print(f"[serve] compiles: {json.dumps(compiles['totals'])}")
    for name, prog in compiles["programs"].items():
        if prog["compile_seconds"] >= 1.0:
            print(f"[serve]   {name}: {prog['compiles']} compiles, "
                  f"{prog['compile_seconds']:.1f}s")
    print(f"[serve] prefix hit: hits={kv.get('hits')} "
          f"reused_prefill_tokens={kv.get('reused_prefill_tokens')}")
    print(f"[serve] router index loaded by the frontend: "
          f"{router_index[0] if router_index else 'none built'}")
    cache_written = len(cache_entries() - cache_before)
    print(f"[serve] SET-UP seconds, first start (spawn -> first token, every "
          f"compile included; {len(cache_before)} cache entries before, "
          f"{cache_written} written): {setup_s:.1f}", flush=True)
    return {"setup_s": setup_s, "compiles": compiles,
            "cache_written": cache_written}


def stage_second_start(run: Run, first: Dict[str, Any]) -> None:
    """The same worker again. Its programs must come from the compile cache
    the first start filled."""
    before = cache_entries()
    cluster = run.bring_up("again", [("worker", run.serve_args, {})])
    res = stream_chat(cluster.base, run.model, CHAT_PROMPTS[0],
                      run.timeouts.request_timeout)
    assert_stream("second start, stream 0", res)
    setup_s = res["arrivals"][0] - cluster.workers[0][0].t_spawn
    read_devices(cluster, 0, run.platform)
    compiles = compile_snapshot(cluster, 0)
    cluster.stop()
    written = sorted(cache_entries() - before)
    cold_total = first["compiles"]["totals"]["compile_seconds"]
    warm_total = compiles["totals"]["compile_seconds"]
    print(f"[again] compiles: {json.dumps(compiles['totals'])}")
    for name, warm in compiles["programs"].items():
        cold = first["compiles"]["programs"].get(name)
        if cold and cold["compile_seconds"] >= 5.0:
            print(f"[again]   {name}: {warm['compiles']} programs loaded in "
                  f"{warm['compile_seconds']:.1f}s (first start: "
                  f"{cold['compiles']} compiled in {cold['compile_seconds']:.1f}s)")
    print(f"[again] SET-UP seconds, second start (spawn -> first token, "
          f"programs from the compile cache; {len(written)} cache entries "
          f"written): {setup_s:.1f}   [first start: {first['setup_s']:.1f}; "
          f"compile seconds {warm_total:.1f} vs {cold_total:.1f}]", flush=True)
    # The cache keeps every program (min compile time 0), so a program the
    # second start had to compile is a program it wrote: none may be.
    if written:
        raise SmokeFailure(
            "the second start compiled programs the first start should have "
            f"cached: {written}"
        )
    # Only a first start that really compiled (it wrote entries) must be
    # slower: on a machine whose cache came warm both starts load.
    if first["cache_written"] and warm_total >= cold_total:
        raise SmokeFailure("the second start was not faster to compile than the first")


def stage_kernels(run: Run) -> None:
    """Every Pallas entry point, interpret=False, against XLA."""
    out = os.path.join(HERE, "chiprun_out", "kernel_table.json")
    argv = [sys.executable, "-m", "dynamo_tpu.ops.pallas.chip_check", "--out", out]
    if run.rehearse:
        argv.append("--interpret")
    child = Child("kernels", argv, run.env, run.logdir)
    rc = child.run_to_end(run.timeouts.kernel_timeout)
    table = [l for l in child.log_text().splitlines()
             if l.startswith(("|", "kernel table"))]
    print("[kernels] " + "\n[kernels] ".join(table), flush=True)
    if rc != 0:
        raise SmokeFailure(f"kernel stage failed (rc={rc})\n{child.tail()}")


# One process for each chip (established on a four-chip v5e host, my chip
# run, PR 21): libtpu gives a process exactly the chip these name, and two
# such processes run side by side. Each then sees ONE device, renumbered to
# id 0, so device ids do not tell the chips apart.
def one_chip_env(chip: int) -> Dict[str, str]:
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": f"{HOST}:{8476 + chip}",
        "TPU_MESH_CONTROLLER_PORT": str(8476 + chip),
    }


def probe_one_chip_per_process(run: Run) -> bool:
    """Do two processes, each given one chip, run side by side here? Prints
    what each saw, or the runtime's refusal."""
    code = (
        "import json, time, jax\n"
        "d = jax.devices()\n"
        "print('ONECHIP ' + json.dumps({'count': len(d), 'ids': [x.id for x in d], "
        "'coords': [getattr(x, 'coords', None) for x in d]}), flush=True)\n"
        "time.sleep(8)\n"
    )
    kids = [
        Child(f"onechip-c{chip}", [sys.executable, "-c", code],
              dict(run.env, **one_chip_env(chip)), run.logdir)
        for chip in (0, 1)
    ]
    rcs = [kid.run_to_end(120) for kid in kids]
    seen = [kid.tagged_json("ONECHIP ") for kid in kids]
    ok = all(rc == 0 for rc in rcs) and all(s and s["count"] == 1 for s in seen)
    print(f"[four-chip] one chip per process, variables {one_chip_env(0)} / "
          f"{one_chip_env(1)}: {'WORKS' if ok else 'REFUSED'} — two processes "
          f"side by side saw {seen}, exit codes {rcs}", flush=True)
    if not ok:
        for kid in kids:
            print(f"[four-chip]   {kid.name} log tail: {kid.tail(1500)!r}", flush=True)
    return ok


def stage_tp(run: Run) -> None:
    """A tensor-parallel worker over every chip serves the same requests;
    every device must hold bytes."""
    tag = f"tp{run.tp}"
    cluster = run.bring_up(tag, [(
        "worker",
        ["--model", run.model_arg, "--tensor-parallel-size", str(run.tp)], {},
    )])
    setup_s = run_requests(run, cluster, cluster.workers[0][0].t_spawn)
    devices = read_devices(cluster, 0, run.platform)
    stats = served_path(cluster, 0, run, tp=run.tp)
    compiles = compile_snapshot(cluster, 0)
    cluster.stop()
    held = [(d["id"], (d.get("memory_stats") or {}).get("bytes_in_use")) for d in devices]
    print(f"[{tag}] bytes in use per device: {held}")
    print(f"[{tag}] decode bursts reaped: {stats['decode_steps']}; "
          f"attention {stats['attention_impl']} ({stats['attention_reason']})")
    print(f"[{tag}] compiles: {json.dumps(compiles['totals'])}")
    print(f"[{tag}] SET-UP seconds (spawn -> first token): {setup_s:.1f}", flush=True)
    if run.platform == "tpu" and (
        len(devices) < run.tp or any(not b for _, b in held[:run.tp])
    ):
        raise SmokeFailure(f"not every one of {run.tp} devices holds bytes: {held}")


def stage_disagg(run: Run, one_chip: bool) -> None:
    """A prefill worker and a decode worker, two processes on two chips,
    behind one frontend: both chips hold weights, the request's KV moved,
    the stream completes."""
    cluster = run.bring_up("pd", [
        ("prefill", [*run.serve_args, "--is-prefill-worker"],
         one_chip_env(0) if one_chip else {}),
        ("decode", run.serve_args, one_chip_env(1) if one_chip else {}),
    ])
    res = stream_chat(cluster.base, run.model, CHAT_PROMPTS[0],
                      run.timeouts.request_timeout)
    assert_stream("disaggregated stream", res)
    held, limit = [], None
    for idx, role in ((0, "prefill"), (1, "decode")):
        devices = read_devices(cluster, idx, run.platform)
        weights = cluster.system(idx, "/debug/memory")["sources"]["engine"]["params"]
        mem = devices[0].get("memory_stats") or {}
        in_use, limit = mem.get("bytes_in_use"), mem.get("bytes_limit")
        held.append((role, len(devices), devices[0].get("id"), weights, in_use))
        if run.platform == "tpu" and (
            len(devices) != 1 or not in_use or in_use <= weights
        ):
            raise SmokeFailure(
                f"{role} worker does not hold its weights on one chip: {held}"
            )
    # Both processes call their chip device 0; what shows they are two
    # chips is that together they hold more than one chip can.
    if run.platform == "tpu" and held[0][4] + held[1][4] <= limit:
        raise SmokeFailure(
            f"prefill + decode bytes in use fit one chip's {limit}: {held}"
        )
    pulled = metric_value(cluster, 1, "dynamo_tpu_disagg_blocks_pulled_total") or 0
    nbytes = metric_value(cluster, 1, "dynamo_tpu_disagg_bytes_pulled_total") or 0
    decode_spawn = cluster.workers[1][0].t_spawn
    cluster.stop()
    print(f"[pd] (role, devices seen, device id, weight bytes, bytes in use): {held}"
          + (f"; together more than one chip's limit {limit}: two chips"
             if run.platform == "tpu" else ""))
    print(f"[pd] KV moved to the decode worker: {pulled:.0f} blocks, {nbytes:.0f} bytes")
    print(f"[pd] SET-UP seconds (decode worker spawn -> first token): "
          f"{res['arrivals'][0] - decode_spawn:.1f}; stream completed "
          f"(finish=length, {MAX_TOKENS} tokens)", flush=True)
    if pulled <= 0 or nbytes <= 0:
        raise SmokeFailure("no KV block moved from the prefill to the decode worker")


def stage_four_chip(run: Run) -> None:
    """On a host with four chips: one process for each chip?, a
    tensor-parallel worker over all of them, a prefill/decode pair on two."""
    if run.dev["count"] < 4:
        print(f"[four-chip] skipped: the worker reports {run.dev['count']} "
              "device(s), the stage needs 4", flush=True)
        return
    one_chip = not run.rehearse and probe_one_chip_per_process(run)
    stage_tp(run)
    if run.rehearse or one_chip:
        stage_disagg(run, one_chip)
    else:
        print("[four-chip] prefill/decode pair LEFT OUT: the runtime "
              "refused one chip per process (above)", flush=True)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="rehearse the script's control flow on the CPU at --model tiny "
        "(8 virtual devices). NOT a chip run, and says so on its last line.",
    )
    ap.add_argument("--logdir", default=None, help="keep child logs here")
    ap.add_argument(
        "--stages", default=",".join(STAGES),
        help="comma-separated subset of " + ",".join(STAGES) + " (chip "
        "minutes are budgeted: debug one stage at a time). A subset's last "
        "line says it was partial; only the full run prints ok=true.",
    )
    # spawn -> "worker serving" includes the worker's own prefill ladder
    # (12 programs at the default --prefill-chunk 512: ~4 min cold at 8B)
    ap.add_argument("--start-timeout", type=float, default=900.0)
    ap.add_argument("--request-timeout", type=float, default=900.0)
    ap.add_argument("--kernel-timeout", type=float, default=600.0)
    args = ap.parse_args()
    stages = args.stages.split(",")
    if set(stages) - set(STAGES) or ("again" in stages and "serve" not in stages):
        ap.error(f"--stages: choose from {STAGES}; 'again' needs 'serve'")

    logdir = args.logdir or os.path.join(HERE, "chiprun_out", "chip_smoke_logs")
    os.makedirs(logdir, exist_ok=True)
    env = dict(os.environ)
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
        run = Run(True, env, logdir, args, "cpu", "tiny", "tiny-llama",
                  ["--model", "tiny"], tp=2)
    else:
        run = Run(False, env, logdir, args, "tpu", "qwen3-8b", "qwen3-8b",
                  ["--model", "qwen3-8b", "--quantization", "int8"], tp=4)

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.monotonic()
    try:
        stage_probe(run)
        placed = "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "checkout default"
        print(f"[smoke] compile cache: {compile_cache_dir()} ({placed})", flush=True)
        if "serve" in stages:
            first = stage_serve(run)
        if "again" in stages:
            stage_second_start(run, first)
        if "kernels" in stages:
            stage_kernels(run)
        if "four-chip" in stages:
            stage_four_chip(run)
    except SmokeFailure as exc:
        print(f"CHIP SMOKE FAILED after {time.monotonic() - t0:.0f}s: {exc}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        kill_all_children()
    print(f"[smoke] stages {stages} passed in {time.monotonic() - t0:.0f}s",
          flush=True)
    device = {"platform": run.dev["platform"], "kind": run.dev["kind"],
              "count": run.dev["count"]}
    if args.rehearse_cpu:
        result = {"ok": False, "rehearsal": "CPU rehearsal at --model tiny: "
                  "NOT a chip run", "device": device}
    elif stages != STAGES:
        result = {"ok": False, "partial": stages, "device": device}
    else:
        result = {"ok": True, "device": device}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
