"""The knee sweep: a mode of the same command (``--sweep R1,R2,...``), run
once when a cell is defined, never part of a measured run.

One cluster, one set-up; the cell's own generator is offered each rate for
``STEP_SECONDS``, ascending, with a drain between. The knee is the highest
rate at which at least 99% of the requests due completed and
``dynamo_tpu_engine_waiting`` did not grow over the second half of the step.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import List

import prom
import stats
from client import Client

STEP_SECONDS = 20.0
DRAIN_CAP_S = 60.0


async def _sweep(run, rates: List[float]) -> int:
    import aiohttp
    from run import Segment, Traffic, say

    run.http = aiohttp.ClientSession()
    rows = []
    try:
        async with Client(run.cluster.base, run.model) as client:
            await run.require_device()
            traffic = Traffic(run, client)
            await run.probes(traffic)
            await run.warmup_bursts(traffic)
            for step, rate in enumerate([rates[0]] + rates):  # the first pass is a warm-up lap
                client.t0 = time.monotonic()
                first = len(client.records)
                traffic.tasks = []
                given = []

                async def next_segment():
                    if given:
                        return None
                    given.append(Segment("window", 0, traffic.now(), STEP_SECONDS))
                    return given[0]

                traffic.next_segment = next_segment
                traffic.seed = run.args.seed + step
                waiting = []

                async def poll():
                    while True:
                        await asyncio.sleep(0.5)
                        snap = await run.scrape()
                        waiting.append((client.now(), sum(
                            prom.total(snap[t], "dynamo_tpu_engine_waiting") or 0.0
                            for t in run.ctx.targets("workers"))))

                poller = asyncio.ensure_future(poll())
                c0 = await run.compiles()
                await run.generator.run(traffic, run.traffic, dict(run.cell, rate=rate))
                t_end = client.now()
                pending = [t for t in traffic.tasks if not t.done()]
                if pending:
                    await asyncio.wait(pending, timeout=DRAIN_CAP_S)
                poller.cancel()
                drain_s = client.now() - t_end
                for t in traffic.tasks:
                    if not t.done():
                        t.cancel()
                await asyncio.gather(*traffic.tasks, return_exceptions=True)
                recs = client.records[first:]
                done_in_time = sum(1 for r in recs if r.ok)
                half = [w for t, w in waiting if STEP_SECONDS / 2 <= t < STEP_SECONDS]
                q = len(half) // 2
                grew = (sum(half[q:]) / max(len(half[q:]), 1)) - (sum(half[:q]) / max(q, 1)) if half else 0.0
                e2e = stats.end_to_end(recs, 0.0, STEP_SECONDS, int(run.cell["chips"]), 0.0)
                row = {
                    "rate": rate, "due": len(recs), "completed": done_in_time,
                    "waiting_max": max((w for _, w in waiting), default=0.0),
                    "waiting_growth_2nd_half": grew, "drain_s": drain_s,
                    "compiles": await run.compiles() - c0,
                    **{k: v["value"] for k, v in e2e.items() if k != "setup_s"},
                }
                if step:
                    rows.append(row)
                say(("warm-up pass " if not step else "") + json.dumps(row))
    finally:
        await run.http.close()
    print("| rate req/s | due | completed | waiting max | waiting growth, 2nd half | drain s | "
          "ttft p50 ms | ttft p95 ms | tpot p95 ms | tokens/s | compiles |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['rate']:g} | {r['due']} | {r['completed']} | {r['waiting_max']:.0f} | "
              f"{r['waiting_growth_2nd_half']:.1f} | {r['drain_s']:.1f} | {r['ttft_p50_ms']:.1f} | "
              f"{r['ttft_p95_ms']:.1f} | {r.get('tpot_p95_ms', float('nan')):.2f} | "
              f"{r['out_tok_per_s']:.0f} | {r['compiles']:.0f} |")
    knee = None
    for r in rows:  # ascending; the knee is the last rate before the first that fails
        if r["completed"] >= 0.99 * r["due"] and r["waiting_growth_2nd_half"] <= 1.0:
            knee = r["rate"]
        else:
            break
    print(json.dumps({"sweep": rows}))
    print(f"KNEE {knee} RATE {0.8 * knee:.2g}" if knee else "KNEE none", flush=True)
    return 0


def main(run, rates: List[float]) -> int:
    try:
        return asyncio.run(_sweep(run, sorted(rates)))
    finally:
        run.cluster.stop()
