#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json over the served path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts discd, the cell's worker(s) and the frontend as child processes, sends
the cell's traffic to the frontend's HTTP port from this process, and prints
one JSON object as the last line of its standard output. This process never
imports JAX (a chip belongs to one process at a time). Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a data
file found by name; see README.md beside this file.

    --rehearse-cpu   the same control flow at ``--model tiny`` on the CPU; its
                     last line names platform ``cpu`` and carries no device
                     metric. Never what the driver runs.
    --sweep R1,R2,.. offer these rates (requests/s) for 20 s each, ascending,
                     and print a table; not a measured run.

Without ``--rehearse-cpu`` and without a chip the command exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib
import json
import os
import shutil
import signal
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, HERE)

import aiohttp  # noqa: E402

import prom  # noqa: E402
import stats  # noqa: E402
from client import Client  # noqa: E402
from launcher import (  # noqa: E402
    ROOT, BenchFailure, Child, Cluster, check_alive, kill_all_children,
    one_chip_env,
)

POLL_HZ = 2.0
CAPTURE_SECONDS = 4.0  # the traced part: the last seconds of the window
DRAIN_TIMEOUT_S = 240.0
START_TIMEOUT_S = 900.0  # spawn -> ``worker serving``
PROBE_OUTPUT_TOKENS = 8


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS:6.1f}s] {msg}", flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise BenchFailure(f"no such benchmark file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_layer_metrics() -> Dict[str, Dict[str, Any]]:
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        if fn.endswith(".json"):
            out[fn[:-5]] = load_json("layer_metrics", fn)
    return out


@dataclass
class Segment:
    kind: str  # "lap" or "window"
    index: int  # 0 for the window, 1.. for the laps
    t0: float
    duration: float


class Ctx:
    """What the readers of per-layer metrics are given."""

    def __init__(self) -> None:
        self.snapshots: Dict[str, Dict[str, prom.Samples]] = {}
        self.routes: Dict[Tuple[str, str, str], Any] = {}
        self.polls: List[Tuple[float, Dict[str, prom.Samples]]] = []
        self.records: List[stats.Record] = []
        self.w0 = self.w1 = self.capture_t0 = 0.0
        self.trace: Optional[Dict[str, Any]] = None
        self.config: Dict[str, Any] = {}
        self.worker_args: List[str] = []
        self.device_kind = ""
        self.n_workers = 1
        self.chips = 1
        self.notes: List[str] = []
        self.why_nothing = ""  # a reader's reason for leaving its metric out

    def targets(self, which: str) -> List[str]:
        if which == "frontend":
            return ["frontend"]
        return [f"worker{i}" for i in range(self.n_workers)]

    def worker_flag(self, flag: str) -> str:
        """The value of a worker flag as this cell runs it: from the
        configuration's arguments, else the CLI's default recorded there."""
        args = self.worker_args
        if flag in args:
            return args[args.index(flag) + 1]
        defaults = self.config["serving"].get("worker_flag_defaults", {})
        if flag in defaults:
            return str(defaults[flag])
        raise BenchFailure(f"worker flag {flag} is neither set nor has a recorded default")


class Traffic:
    """The API a generator drives: a clock, ``fire``, and the segments
    (warm-up laps, then the window) that the harness places."""

    def __init__(self, run: "Run", client: Client) -> None:
        self._run = run
        self.client = client
        self.seed = run.args.seed
        self.vocab = int(run.config["vocab_size"]) if not run.args.rehearse_cpu else 500
        self.stop_at: Optional[float] = None
        self.tasks: List[asyncio.Task] = []

    def now(self) -> float:
        return self.client.now()

    async def sleep_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    def fire(self, due: float, prompt: List[int], max_tokens: int, tag: str = "") -> asyncio.Task:
        task = asyncio.ensure_future(self.client.request(due, prompt, max_tokens, tag))
        self.tasks.append(task)
        return task

    async def next_segment(self) -> Optional[Segment]:
        return await self._run.next_segment(self)


class Run:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.cell = load_json("cells", args.workload + ".json")
        self.config = load_json("configs", self.cell["config"] + ".json")
        self.traffic = load_json("traffic", self.cell["traffic"] + ".json")
        self.layer_metrics = load_layer_metrics()
        self.generator = importlib.import_module("generators." + self.traffic["generator"])
        serving = self.config["rehearse_cpu"] if args.rehearse_cpu else self.config["serving"]
        self.serving = serving
        self.model = serving["served_model_name"]
        self.out_dir = os.path.join(
            ROOT, "bench_out", f"{args.workload}-s{args.seed}-t{args.trace}")
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.cluster: Optional[Cluster] = None
        self.ctx = Ctx()
        self.ctx.config = self.config
        self.ctx.worker_args = list(serving["workers"][0]["args"])
        self.ctx.n_workers = len(serving["workers"])
        self.ctx.chips = int(self.cell["chips"])
        self.http: Optional[aiohttp.ClientSession] = None
        self.segments: List[Segment] = []
        self.lap_compiles: List[float] = []
        self.failures: List[str] = []  # why ``correct`` is false
        self.window: Optional[Segment] = None
        self.capture_started = False
        self.side_tasks: List[asyncio.Task] = []
        self.trace_dirs: List[str] = []

    # -- the cluster -------------------------------------------------------

    def child_env(self) -> Dict[str, str]:
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)  # the driver's own; nothing here reads it
        # One compile cache at a fixed path inside the checkout (the path is
        # part of the cache's key), whatever the machine came with: the
        # parent's and the change's checkouts must share nothing, and a shared
        # directory with a size cap evicts under the worker (chip run of PR
        # 23: cache work then held the worker's event loop past its 5 s
        # liveness budget and the frontend declared it dead).
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
        if self.args.rehearse_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def start_cluster(self) -> None:
        self.cluster = Cluster(self.out_dir, self.child_env())
        self.cluster.start_discd()
        workers = self.serving["workers"]
        for i, w in enumerate(workers):
            extra = one_chip_env(w["chip"]) if len(workers) > 1 and not self.args.rehearse_cpu else {}
            self.cluster.spawn_worker(f"worker{i}", list(w["args"]), extra)
        self.cluster.wait_workers(START_TIMEOUT_S)
        say(f"{len(workers)} worker(s) serving "
            f"({time.monotonic() - self.cluster.workers[0][0].t_spawn:.1f}s after spawn)")
        self.cluster.start_frontend(self.model, list(self.serving.get("frontend_args", [])))

    def urls(self) -> Dict[str, str]:
        out = {"frontend": self.cluster.base}
        for i in range(len(self.cluster.workers)):
            out[f"worker{i}"] = self.cluster.worker_url(i)
        return out

    async def get_json(self, url: str, body: Optional[dict] = None, timeout: float = 60.0) -> Any:
        kw = {"timeout": aiohttp.ClientTimeout(total=timeout)}
        req = self.http.post(url, json=body, **kw) if body is not None else self.http.get(url, **kw)
        async with req as resp:
            text = await resp.text()
            if resp.status != 200:
                raise BenchFailure(f"{url} -> HTTP {resp.status}: {text[:500]}")
            return json.loads(text)

    async def scrape(self) -> Dict[str, prom.Samples]:
        async def one(name: str, url: str):
            async with self.http.get(url + "/metrics", timeout=aiohttp.ClientTimeout(total=30)) as r:
                return name, prom.parse(await r.text())
        return dict(await asyncio.gather(*(one(n, u) for n, u in self.urls().items())))

    async def snapshot(self, when: str) -> None:
        self.ctx.snapshots[when] = await self.scrape()
        paths = {m["params"]["path"] for m in self.layer_metrics.values()
                 if m["reader"] == "route_json"}
        paths.add("/debug/compiles")
        for target in self.ctx.targets("workers"):
            for path in paths:
                self.ctx.routes[(when, target, path)] = await self.get_json(self.urls()[target] + path)

    async def compiles(self) -> float:
        total = 0.0
        for target in self.ctx.targets("workers"):
            snap = await self.get_json(self.urls()[target] + "/debug/compiles")
            total += snap["totals"]["compiles"]
        return total

    async def compile_report(self, label: str) -> None:
        """Compile counts and seconds by program, and the cache's entry count:
        what set-up spent on compiling, and whether the cache served it."""
        cache = self.child_env()["JAX_COMPILATION_CACHE_DIR"]
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        for target in self.ctx.targets("workers"):
            snap = await self.get_json(self.urls()[target] + "/debug/compiles")
            rows = [(n, p["compiles"], p["compile_seconds"]) for n, p in snap["programs"].items()
                    if p["compiles"]]
            say(f"{label}: {target} compiles {snap['totals']['compiles']} in "
                f"{snap['totals']['compile_seconds']:.1f}s "
                f"{[(n, c, round(t, 1)) for n, c, t in sorted(rows, key=lambda r: -r[2])]}; "
                f"{entries} entries in the compile cache")

    async def poller(self, client: Client) -> None:
        while True:
            await asyncio.sleep(1.0 / POLL_HZ)
            try:
                self.ctx.polls.append((client.now(), await self.scrape()))
            except (aiohttp.ClientError, asyncio.TimeoutError):
                pass  # a missed poll is a missing sample, nothing more
            check_alive(self.cluster.children())

    # -- device, probes, warm-up ------------------------------------------

    async def read_devices(self) -> Dict[str, Any]:
        rows = []
        for target in self.ctx.targets("workers"):
            rows += (await self.get_json(self.urls()[target] + "/debug/memory"))["devices"]
        peaks = [(r.get("memory_stats") or {}).get("peak_bytes_in_use", 0) for r in rows]
        return {
            "platform": rows[0].get("platform") if rows else None,
            "kind": rows[0].get("device_kind") if rows else None,
            "count": len(rows),
            "memory_peak_bytes": max(peaks) if peaks else 0,
            "_all": [(r.get("platform"), r.get("device_kind")) for r in rows],
        }

    async def require_device(self) -> None:
        dev = await self.read_devices()
        want_platform = "cpu" if self.args.rehearse_cpu else self.config["serving"]["platform"]
        if any(p != want_platform for p, _ in dev["_all"]) or not dev["_all"]:
            raise BenchFailure(f"workers hold {dev['_all']}, the cell needs platform {want_platform!r}")
        if not self.args.rehearse_cpu and dev["count"] < int(self.cell["chips"]):
            raise BenchFailure(f"{dev['count']} chip(s) found, the cell needs {self.cell['chips']}")
        self.ctx.device_kind = dev["kind"]

    def check_record(self, rec: stats.Record, what: str) -> None:
        """The facts a completed request must show. Failed requests are not
        judged here: they are counted in ``failed``."""
        if not rec.ok:
            return
        usage = rec.usage or {}
        problems = []
        if usage.get("completion_tokens") != rec.max_tokens:
            problems.append(f"{usage.get('completion_tokens')} output tokens, asked {rec.max_tokens}")
        if rec.finish_reason != "length":
            problems.append(f"finish_reason {rec.finish_reason!r}")
        if usage.get("prompt_tokens") != rec.prompt_len:
            problems.append(f"usage.prompt_tokens {usage.get('prompt_tokens')}, sent {rec.prompt_len} ids")
        for p in problems:
            self.failures.append(f"{what} #{rec.rid} (prompt {rec.prompt_len}, due {rec.due:.2f}s): {p}")

    async def wait_listed(self) -> None:
        """Up to a minute for the frontend to list the model again."""
        for _ in range(120):
            listed = await self.get_json(self.cluster.base + "/v1/models")
            if self.model in [m["id"] for m in listed["data"]]:
                return
            await asyncio.sleep(0.5)

    async def probes(self, traffic: Traffic) -> None:
        """One request at a time against an idle engine, so that batch
        composition is the same in every run; then the first again, which
        must be served as a prefix hit."""
        import laws
        import numpy as np

        rng = np.random.default_rng([self.args.seed, 15485863])
        lens = self.generator.probe_lengths(self.traffic)
        prompts = [laws.token_ids(rng, n, traffic.vocab) for n in lens]
        async def probe(i: int, prompt: List[int]) -> stats.Record:
            # A first compile can hold the worker's event loop past the
            # frontend's 5 s liveness budget; the request then ends
            # ``no_instances``. That is set-up weather, not an output: wait for
            # the model to be listed again and ask again. An idle engine that
            # never answers leaves nothing to measure.
            for attempt in range(3):
                rec = await traffic.client.request(traffic.now(), prompt, PROBE_OUTPUT_TOKENS, "probe")
                if rec.ok:
                    return rec
                say(f"probe {i} (prompt {len(prompt)}), attempt {attempt + 1}, failed: {rec.error}")
                await self.wait_listed()
            raise BenchFailure(f"probe {i} (prompt {len(prompt)}) failed three times: {rec.error}")

        for i, prompt in enumerate(prompts):
            self.check_record(await probe(i, prompt), "probe")
        name = "dynamo_tpu_kvcache_reused_prefill_tokens_total"
        before = await self.scrape()
        rec = await probe(0, prompts[0])
        self.check_record(rec, "probe repeat")
        after = await self.scrape()
        reused = sum(
            (prom.total(after[t], name) or 0.0) - (prom.total(before[t], name) or 0.0)
            for t in self.ctx.targets("workers"))
        if reused <= 0:
            self.failures.append(
                f"probe repeat (prompt {len(prompts[0])}) was not served as a prefix hit: "
                f"{name} did not increase")
        say(f"probes: prompts {lens} + repeat, reused prefill tokens {reused:.0f}")
        await self.compile_report("after the probes")

    async def warmup_bursts(self, traffic: Traffic) -> None:
        """``rows`` simultaneous fresh prompts of one length: the prefill
        program of that batch shape, which random laps meet too rarely to
        count on."""
        import laws
        import numpy as np

        rng = np.random.default_rng([self.args.seed, 32452843])
        bursts = self.traffic.get("warmup", {}).get("bursts", [])
        for rows, n_prompt, n_out in bursts:
            # As with the probes: a compile can hold the worker's event loop
            # past the frontend's liveness budget, and the burst then ends in
            # 404s within milliseconds, its program never compiled (1 run of
            # 11 at PR 30 and 1 of 19 at PR 32 opened their window on 26 and
            # 18 of 42 programs). Wait for the model and send the burst again, with
            # fresh prompts (a repeated one would be a prefix hit).
            for attempt in range(3):
                now = traffic.now()
                recs = await asyncio.gather(*(
                    traffic.client.request(now, laws.token_ids(rng, n_prompt, traffic.vocab), n_out, "burst")
                    for _ in range(rows)))
                lost = [rec for rec in recs if not rec.ok]
                if not lost:
                    break
                say(f"warm-up burst {rows} x {n_prompt}, attempt {attempt + 1}: "
                    f"{len(lost)} failed: {lost[0].error}")
                await self.wait_listed()
            for rec in recs:
                self.check_record(rec, "warm-up burst")
        if bursts:
            await self.compile_report(f"after {len(bursts)} warm-up bursts")

    # -- segments ----------------------------------------------------------

    async def next_segment(self, traffic: Traffic) -> Optional[Segment]:
        now = traffic.now()
        warm = self.traffic.get("warmup", {})
        lap_s = float(warm.get("lap_seconds", 10.0))
        if self.window is not None:
            return None  # the window has ended: the generator stops
        self.lap_compiles.append(await self.compiles())
        laps = len(self.segments)
        if laps >= int(warm.get("laps", 1)):
            quiet = laps == 0 or self.lap_compiles[-1] == self.lap_compiles[-2]
            await self.snapshot("window_start")
            seg = Segment("window", 0, traffic.now(), float(self.args.seconds))
            self.window = seg
            traffic.stop_at = seg.t0 + seg.duration
            self.ctx.w0, self.ctx.w1 = seg.t0, seg.t0 + seg.duration
            say(f"window opens after {laps} lap(s), lap compile counts {self.lap_compiles}"
                + ("" if quiet else " — the last lap still compiled"))
            self.side_tasks.append(asyncio.ensure_future(self.end_of_window(traffic)))
            if self.args.trace:
                self.side_tasks.append(asyncio.ensure_future(self.start_capture(traffic)))
            return seg
        seg = Segment("lap", laps + 1, now, lap_s)
        self.segments.append(seg)
        return seg

    async def end_of_window(self, traffic: Traffic) -> None:
        await traffic.sleep_until(self.ctx.w1)
        self.ctx.snapshots["window_end"] = await self.scrape()

    async def start_capture(self, traffic: Traffic) -> None:
        """Start the profiler late in the window, WITHOUT ``seconds``: an
        auto-stop would export the trace on the worker's event loop in the
        middle of traffic and stall every stream."""
        await traffic.sleep_until(self.ctx.w1 - CAPTURE_SECONDS)
        self.ctx.capture_t0 = traffic.now()
        self.ctx.snapshots["capture_start"] = await self.scrape()
        for i, target in enumerate(self.ctx.targets("workers")):
            d = os.path.join(self.out_dir, f"trace{i}")
            reply = await self.get_json(
                self.urls()[target] + "/debug/profile", {"action": "start", "dir": d})
            if not reply.get("ok"):
                raise BenchFailure(f"profiler did not start on {target}: {reply}")
            self.trace_dirs.append(d)
        self.ctx.capture_t0 = traffic.now()
        self.capture_started = True

    async def stop_capture(self) -> None:
        """Only after the last stream has drained: ``stop_trace`` runs on the
        worker's event loop and takes seconds to minutes."""
        t = time.monotonic()
        for target in self.ctx.targets("workers"):
            reply = await self.get_json(
                self.urls()[target] + "/debug/profile", {"action": "stop"}, timeout=600)
            if not reply.get("ok"):
                raise BenchFailure(f"profiler did not stop on {target}: {reply}")
        say(f"profiler stopped and exported in {time.monotonic() - t:.1f}s")

    def reduce_trace(self) -> None:
        out = os.path.join(self.out_dir, "trace_summary.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        child = Child(
            "trace_reduce",
            [sys.executable, os.path.join(HERE, "trace_reduce.py"), "--window-s",
             repr(self.ctx.w1 - self.ctx.capture_t0), "--out", out, *self.trace_dirs],
            env, self.out_dir)
        rc = child.run_to_end(240)
        if os.path.isfile(out):
            with open(out) as f:
                self.ctx.trace = json.load(f)
        if rc != 0 or not self.ctx.trace or "error" in self.ctx.trace:
            raise BenchFailure(
                f"trace reduction failed (rc={rc}): {(self.ctx.trace or {}).get('error')}\n{child.tail()}")
        for d in self.trace_dirs:  # the raw trace is large; the summary stays
            shutil.rmtree(d, ignore_errors=True)

    # -- the run -----------------------------------------------------------

    async def measure(self) -> Dict[str, Any]:
        self.http = aiohttp.ClientSession()
        try:
            async with Client(self.cluster.base, self.model) as client:
                return await self._measure(client)
        finally:
            await self.http.close()

    async def _measure(self, client: Client) -> Dict[str, Any]:
        traffic = Traffic(self, client)
        await self.require_device()
        stats_route = await self.get_json(self.urls()["worker0"] + "/engine/stats", {})
        say(f"decode_path {stats_route.get('decode_path')} ({stats_route.get('decode_path_reason')}); "
            f"attention_impl {stats_route.get('attention_impl')} ({stats_route.get('attention_reason')})")
        await self.probes(traffic)
        await self.warmup_bursts(traffic)

        client.t0 = time.monotonic()  # traffic starts: segment times count from here
        n_setup = len(client.records)
        poll = asyncio.ensure_future(self.poller(client))
        gen = asyncio.ensure_future(self.generator.run(traffic, self.traffic, self.cell))
        try:
            while not gen.done():
                await asyncio.wait([gen, poll], timeout=1.0, return_when=asyncio.FIRST_COMPLETED)
                if poll.done():
                    poll.result()  # a dead child: raise
            gen.result()
            for side in self.side_tasks:
                await side  # a failed capture start or snapshot must not pass in silence
            if self.window is None:
                raise BenchFailure("the generator ended before the window opened")
            pending = [t for t in traffic.tasks if not t.done()]
            say(f"window closed; draining {len(pending)} request(s)")
            if pending:
                _, late = await asyncio.wait(pending, timeout=DRAIN_TIMEOUT_S)
                for t in late:
                    t.cancel()
                await asyncio.gather(*late, return_exceptions=True)
        finally:
            poll.cancel()
            gen.cancel()
        records = client.records[n_setup:]
        self.ctx.records = records
        # Every request of the laps and the window as the client saw it, so
        # that a candidate statistic can be tried on a run already made.
        with open(os.path.join(self.out_dir, "records.jsonl"), "w") as f:
            for r in records:
                f.write(json.dumps(dataclasses.asdict(r)) + "\n")
        if self.args.trace:
            if not self.capture_started:
                raise BenchFailure("the capture never started")
            await self.stop_capture()
        await self.snapshot("drained")
        await self.compile_report("after the drain")
        device = await self.read_devices()
        device.pop("_all")
        w0, w1 = self.ctx.w0, self.ctx.w1
        setup_s = (client.t0 + w0) - T_PROCESS
        due = [r for r in records if w0 <= r.due < w1]
        for rec in due:
            self.check_record(rec, "request")
        failed = [r for r in due if not r.ok]
        for rec in failed[:5]:
            say(f"failed request #{rec.rid} (prompt {rec.prompt_len}, due {rec.due:.2f}s): {rec.error}")
        want_kind = self.config["serving"]["device_kind"]
        if not self.args.rehearse_cpu:
            if device["platform"] != self.config["serving"]["platform"] or device["kind"] != want_kind:
                self.failures.append(f"device {device['platform']}/{device['kind']}, cell declares tpu/{want_kind}")
            if device["count"] != int(self.cell["chips"]):
                self.failures.append(f"{device['count']} devices, cell declares {self.cell['chips']}")
        say(f"requests due in the window: {len(due)}, failed {len(failed)}; "
            f"compiles at lap ends {self.lap_compiles}, after the drain {await self.compiles():.0f}")
        return {
            "records": records, "due": due, "failed": failed, "device": device,
            "setup_s": setup_s,
        }

    def listed(self, kind: str) -> List[str]:
        """The ``end_to_end`` or ``per_layer`` metrics BENCHMARK.json lists
        for this cell: all that carry no ``workloads`` key, and those whose
        key names it. A later PR gives a cell metrics of its own that way,
        with entries and files alone."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            metrics = json.load(f)[kind]
        return [m["name"] for m in metrics
                if not m.get("workloads") or self.args.workload in m["workloads"]]

    def result_line(self, m: Dict[str, Any]) -> Dict[str, Any]:
        ctx, args = self.ctx, self.args
        chips = int(self.cell["chips"])
        device = m["device"]
        if args.trace:
            metrics: Dict[str, Dict[str, Any]] = {}
            for name in self.listed("per_layer"):
                spec = self.layer_metrics[name]
                if args.rehearse_cpu and spec["source"] == "device_trace":
                    continue  # a CPU run carries no device metric
                reader = importlib.import_module("readers." + spec["reader"])
                ctx.why_nothing = ""
                value = reader.read(spec.get("params", {}), ctx)
                if value is not None:
                    metrics[name] = {"value": value, "unit": spec["unit"]}
                else:
                    say(f"LEFT OUT of the line: {name}: the {spec['reader']} reader found "
                        f"nothing to read" + (f" ({ctx.why_nothing})" if ctx.why_nothing else ""))
            if ctx.trace:
                device["busy_s"] = ctx.trace["busy_s"]
                device["window_s"] = ctx.trace["window_s"]
        else:
            every = stats.end_to_end(m["records"], ctx.w0, ctx.w1, chips, m["setup_s"])
            say("end to end, every candidate: "
                + ", ".join(f"{k}={v['value']:.4f}" for k, v in every.items()))
            metrics = {k: every[k] for k in self.listed("end_to_end") if k in every}
        for note in ctx.notes:
            say(note)
        for why in self.failures[:10]:
            say(f"CHECK FAILED: {why}")
        line = {
            "correct": not self.failures,
            "attempted": len(m["due"]),
            "failed": len(m["failed"]),
            "metrics": metrics,
            "device": device,
        }
        if args.trace and ctx.trace:
            line["breakdown"] = {
                "device_ops": ctx.trace["device_ops"], "idle_gaps": ctx.trace["idle_gaps"]}
        if args.rehearse_cpu:
            line["rehearsal"] = "CPU rehearsal at --model tiny: NOT a chip run"
        return line


def run_reference(run: "Run") -> None:
    """A plain reference, when a later PR has added one beside the
    configuration (ROADMAP R1): ``references/<configuration>.py``, run as a
    child after the workers have gone (outside the window), given the
    configuration's file; a non-zero exit makes ``correct`` false."""
    name = run.cell["config"]
    path = os.path.join(HERE, "references", name + ".py")
    if not os.path.isfile(path):
        return
    child = Child("reference", [sys.executable, path, "--config",
                                os.path.join(HERE, "configs", name + ".json")],
                  run.child_env(), run.out_dir)
    if child.run_to_end(600) != 0:
        run.failures.append(f"reference {name}.py disagreed or failed: {child.tail(500)!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--sweep", default=None, help="comma-separated rates, requests/s")
    args = ap.parse_args()
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    args.seed = int(args.seed) % (1 << 32)

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        run = Run(args)
        run.start_cluster()
        if args.sweep:
            sys.modules.setdefault("run", sys.modules[__name__])  # sweep imports from this module
            import sweep
            return sweep.main(run, [float(r) for r in args.sweep.split(",")])
        measured = asyncio.run(run.measure())
        run.cluster.stop()
        if args.trace:
            try:
                run.reduce_trace()
            except BenchFailure as exc:
                if not args.rehearse_cpu:
                    raise
                run.ctx.trace = None
                say(f"rehearsal: no device plane on the CPU ({str(exc).splitlines()[0]})")
        run_reference(run)
        line = run.result_line(measured)
    except (BenchFailure, ValueError) as exc:
        print(f"BENCHMARK FAILED after {time.monotonic() - T_PROCESS:.0f}s: {exc}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        kill_all_children()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
