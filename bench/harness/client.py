"""The load generator: HTTP streaming clients on one asyncio loop, one
thread.  It never gives up on a request by itself and cancels nothing:
requests due (open loop) or started (closed loop) inside the window are
all awaited to their end after it closes; one drain deadline, longer than
the program's own `serve_request_deadline_s`, marks a hang.

A request fails for exactly three causes:
  status    a status other than 200, an error frame, or a broken stream
  tokens    the stream ended with another token count than `max_tokens`
  no_end    no end by the drain deadline
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Callable, List, Optional

from bench.harness.schedule import Request


@dataclasses.dataclass
class Outcome:
    index: int
    prompt_len: int
    max_tokens: int
    due: float                        # seconds after the window opened
    sent: Optional[float] = None
    first: Optional[float] = None     # first token at the client
    last: Optional[float] = None      # last token at the client
    tokens: int = 0
    status: Optional[int] = None
    cause: Optional[str] = None       # None: the request succeeded
    detail: str = ""
    request_id: str = ""

    def failure_line(self) -> str:
        return json.dumps({"failed_request": self.index, "cause": self.cause,
                           "prompt_len": self.prompt_len,
                           "max_tokens": self.max_tokens,
                           "due_s": self.due, "sent_s": self.sent,
                           "status": self.status,
                           "tokens_received": self.tokens,
                           "detail": self.detail[:300]})


async def _stream(session, url: str, req: Request, out: Outcome,
                  temperature: float, t_open: float) -> None:
    body = json.dumps({"tokens": req.tokens, "max_tokens": req.max_tokens,
                       "temperature": temperature})
    out.sent = time.perf_counter() - t_open
    try:
        async with session.post(
                url, data=body,
                headers={"Content-Type": "application/json",
                         "X-Request-Id": out.request_id}) as resp:
            out.status = resp.status
            if resp.status != 200:
                out.cause = "status"
                out.detail = (await resp.text())[:300]
                return
            async for line in resp.content:
                now = time.perf_counter() - t_open
                item = json.loads(line)
                if "token" not in item:
                    out.cause, out.detail = "status", f"error frame {item}"
                    return
                if out.first is None:
                    out.first = now
                out.last = now
                out.tokens += 1
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 - the cause is recorded and printed
        out.cause, out.detail = "status", f"{type(e).__name__}: {e}"
        return
    if out.tokens != req.max_tokens:
        out.cause = "tokens"
        out.detail = f"stream ended after {out.tokens} of {req.max_tokens}"


async def drive(url: str, requests: List[Request], *, loop_kind: str,
                seconds: float, clients: int, temperature: float,
                drain_s: float, id_prefix: str,
                on_open: Callable[[], None], stagger_s: float = 0.0,
                at: Optional[List[tuple]] = None) -> dict:
    """Runs the window.  `on_open()` is called as it opens; `at` is a list
    of (offset_s, blocking callable) run in threads at their offsets (the
    profiler window of a traced run).  Returns outcomes of the attempted
    requests and the window's bounds."""
    import aiohttp

    loop = asyncio.get_running_loop()
    outcomes: List[Outcome] = []
    tasks: List[asyncio.Task] = []
    side: List[asyncio.Future] = []
    timeout = aiohttp.ClientTimeout(total=None, sock_read=None)
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=connector) as session:
        on_open()
        t_open = time.perf_counter()

        def start(req: Request, due: float) -> asyncio.Task:
            out = Outcome(req.index, req.prompt_len, req.max_tokens, due,
                          request_id=f"{id_prefix}-{req.index}")
            outcomes.append(out)
            task = loop.create_task(
                _stream(session, url, req, out, temperature, t_open))
            tasks.append(task)
            return task

        async def side_call(offset: float, fn) -> None:
            await asyncio.sleep(max(0.0, t_open + offset
                                    - time.perf_counter()))
            await loop.run_in_executor(None, fn)

        for offset, fn in at or []:
            side.append(loop.create_task(side_call(offset, fn)))

        if loop_kind == "open":
            for req in requests:
                wait = t_open + req.due_s - time.perf_counter()
                if wait > 0:
                    await asyncio.sleep(wait)
                start(req, req.due_s)
        elif loop_kind == "closed":
            queue = iter(requests)

            async def caller(k: int) -> None:
                # Callers start `stagger_s` apart: sent at once, their
                # first requests would reach the engine in a random order.
                await asyncio.sleep(k * stagger_s)
                while time.perf_counter() - t_open < seconds:
                    await start(next(queue), time.perf_counter() - t_open)

            callers = [loop.create_task(caller(k)) for k in range(clients)]
        else:
            raise ValueError(f"loop kind {loop_kind!r}")

        remaining = t_open + seconds - time.perf_counter()
        if remaining > 0:
            await asyncio.sleep(remaining)
        t_close = time.perf_counter()
        # The window closing fails nothing: everything attempted is
        # awaited, up to the drain deadline.
        pending = set(tasks)
        if loop_kind == "closed":
            pending |= set(callers)
        if pending:
            _, late = await asyncio.wait(pending, timeout=drain_s)
            if loop_kind == "closed":
                for c in callers:
                    if c.done() and not c.cancelled() and c.exception():
                        raise c.exception()
            for t in late:
                t.cancel()
            await asyncio.gather(*late, return_exceptions=True)
        for s in side:
            await s
        t_end = time.perf_counter()
    for out in outcomes:
        if out.cause is None and (out.last is None
                                  or out.tokens != out.max_tokens):
            out.cause = "no_end"
            out.detail = (f"no end {t_end - t_open - out.due:.1f}s after "
                          f"it was due; drain deadline {drain_s:.0f}s "
                          f"after the window closed")
    return {"outcomes": outcomes, "window_s": t_close - t_open,
            "drain_s": t_end - t_close, "gave_up_s": t_end - t_open}
