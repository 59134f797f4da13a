"""The client side of the frontend's HTTP port: one streamed
``/v1/completions`` request with pre-tokenised ids, timed by this process's
monotonic clock. Copied in idea from ``dynamo_tpu/bench/loadgen.py`` (token
ids as the prompt, ``nvext.ignore_eos``, SSE timing); the schedule, the
lengths and the per-token arithmetic are the benchmark's own.

A request carries no deadline and the time-outs are generous: a stall shows
as latency, not as a failure.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import List, Optional

import aiohttp

from stats import Record

SOCK_READ_TIMEOUT_S = 300.0  # a stall is latency; only a dead server fails


class Client:
    def __init__(self, base: str, model: str) -> None:
        self.base = base
        self.model = model
        self.t0 = time.monotonic()  # start of traffic; reset by the harness
        self.records: List[Record] = []
        self._session: Optional[aiohttp.ClientSession] = None
        self._next = 0

    async def __aenter__(self) -> "Client":
        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=None, sock_read=SOCK_READ_TIMEOUT_S),
            connector=aiohttp.TCPConnector(limit=0),
        )
        return self

    async def __aexit__(self, *exc) -> None:
        await self._session.close()

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def request(self, due: float, prompt: List[int], max_tokens: int,
                      tag: str = "") -> Record:
        """Sleep until ``due``, send, read the stream to its end. Never
        raises for a failed request: the failure is in the record."""
        rec = Record(rid=self._next, due=due, prompt_len=len(prompt),
                     max_tokens=max_tokens, tag=tag)
        self._next += 1
        self.records.append(rec)
        delay = due - self.now()
        if delay > 0:
            await asyncio.sleep(delay)
        body = {
            "model": self.model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0, "stream": True,
            "stream_options": {"include_usage": True},
            "nvext": {"ignore_eos": True},
        }
        rec.sent = self.now()
        try:
            async with self._session.post(self.base + "/v1/completions", json=body) as resp:
                if resp.status != 200:
                    rec.error = f"HTTP {resp.status}: {(await resp.text())[:300]}"
                    return rec
                async for raw in resp.content:
                    line = raw.strip()
                    if not line.startswith(b"data:"):
                        continue
                    data = line[5:].strip()
                    now = self.now()
                    if data == b"[DONE]":
                        rec.done = now
                        break
                    try:
                        frame = json.loads(data)
                    except json.JSONDecodeError:
                        rec.error = f"malformed SSE frame: {data[:200]!r}"
                        return rec
                    if "error" in frame:
                        rec.error = f"error frame: {json.dumps(frame['error'])[:300]}"
                        return rec
                    if frame.get("usage"):
                        rec.usage = frame["usage"]
                    for choice in frame.get("choices") or []:
                        rec.frame_times.append(now)
                        if choice.get("finish_reason"):
                            rec.finish_reason = choice["finish_reason"]
            if rec.done is None:
                rec.error = "stream ended without [DONE]"
            elif not rec.frame_times:
                rec.error = "stream carried no token frame"
        except asyncio.CancelledError:
            rec.error = "cancelled by the harness (drain time-out)"
            raise
        except Exception as exc:  # connection errors land in the record
            rec.error = f"{type(exc).__name__}: {exc}"
        return rec
