"""The load generator: a process of its own, off JAX, one thread.

    python -m chipbench.loadgen <plan.json>

It reads a plan (the loop kind, the window's length; an open loop's requests
as `schedule` built them; a closed loop's traffic and seed, from which it
draws `schedule.closed_stream` itself, because that list has no end), drives
a generate server over HTTP, and writes one JSON file: a record per request
with the arrival time of every streamed token, the window it measured, and
what it read from the server at the window's edges (/metrics, the device
record, the size of the server's log).  It takes no
measurement of its own beyond timestamps; `stats` and the per-layer readers
reduce them.  All times are this process's CLOCK_MONOTONIC, which the parent
shares.

closed loop: clients start staggered and pull requests from one list that
    never runs out; the window opens when every client has finished
    `warm_rounds` requests, and closes `seconds` later while traffic still
    flows.
open loop: request i is sent at its due time whatever is outstanding; the
    window opens after the lead-in and closes `seconds` later; the schedule
    keeps flowing through the tail, until every request due in the window
    has the first byte of its answer.
"""

import asyncio
import json
import os
import sys
import time

import aiohttp

from chipbench import schedule

now = time.monotonic


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        self.base = plan["url"]
        self.records = []
        self.scrapes = {}
        self.slice_scrapes = []
        self.device_samples = []
        self.log_offsets = {}
        self.window = None
        self.trace_window = None
        self.notes = []
        self.session = None

    # -- one request ---------------------------------------------------------
    async def generate(self, request: dict, due: float, phase: str) -> dict:
        record = {"i": request["i"], "phase": phase,
                  "prompt_tokens": request["prompt_tokens"],
                  "output_tokens": request["output_tokens"],
                  "due": due, "sent": None, "first": None, "last": None,
                  "tokens": [], "ok": False, "error": None}
        self.records.append(record)
        body = json.dumps({"text_input": request["prompt"],
                           "max_tokens": request["output_tokens"],
                           "temperature": 0.0}).encode()
        finish = None
        try:
            record["sent"] = now()
            async with self.session.post(
                    self.base + self.plan["path"], data=body,
                    headers={"content-type": "application/json"}) as resp:
                if resp.status != 200:
                    text = (await resp.read())[:200]
                    record["error"] = f"HTTP {resp.status}: {text!r}"
                    return record
                async for line in resp.content:
                    if not line.startswith(b"data: "):
                        continue
                    t = now()
                    if record["first"] is None:
                        record["first"] = t
                    event = json.loads(line[6:])
                    token = event.get("token")
                    if token and token.get("id") is not None:
                        record["tokens"].append(t)
                        record["last"] = t
                    finish = event.get("finish_reason", finish)
        except asyncio.CancelledError:
            record["error"] = "cut"  # still running when the run ended
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            record["error"] = f"{type(e).__name__}: {e}"
            return record
        got = len(record["tokens"])
        if got == request["output_tokens"] and finish == "length":
            record["ok"] = True
        else:
            record["error"] = (f"asked {request['output_tokens']} tokens, "
                               f"got {got}, finish {finish!r}")
        return record

    # -- reading the server ----------------------------------------------------
    async def get_text(self, path: str) -> str:
        async with self.session.get(self.base + path) as resp:
            return (await resp.read()).decode("utf-8", "replace")

    async def post_json(self, path: str, payload: dict) -> str:
        async with self.session.post(
                self.base + path, data=json.dumps(payload).encode()) as resp:
            return (await resp.read()).decode("utf-8", "replace")

    def log_size(self) -> int:
        try:
            return os.path.getsize(self.plan["server_log"])
        except OSError:
            return -1

    async def edge(self, name: str) -> None:
        """What the per-layer readers difference: counters and the log's
        size at an edge of the window or of the traced part of it."""
        self.log_offsets[name] = self.log_size()
        self.scrapes[name] = {"t": now(),
                              "metrics": await self.get_text("/metrics")}

    async def poll(self) -> None:
        """Inside the window: the device record every second (memory in
        use), /metrics every slice."""
        every = float(self.plan["slice_s"])
        next_slice = self.window[0] + every
        while True:
            await asyncio.sleep(1.0)
            try:
                device = json.loads(await self.get_text("/v2"))["device"]
                self.device_samples.append([now(), device["hbm_in_use"]])
                if now() >= next_slice:
                    next_slice += every
                    self.slice_scrapes.append(
                        {"t": now(),
                         "metrics": await self.get_text("/metrics")})
            except (aiohttp.ClientError, KeyError, ValueError) as e:
                self.notes.append(f"poll: {type(e).__name__}: {e}")

    async def measure(self, opened: float) -> None:
        """Open the window now, close it `seconds` later; start the trace
        `trace.seconds` before its end where the plan asks for one."""
        seconds = float(self.plan["seconds"])
        self.window = [opened, opened + seconds]
        await self.edge("open")
        poller = asyncio.ensure_future(self.poll())
        trace = self.plan.get("trace")
        try:
            if trace:
                start = self.window[1] - float(trace["seconds"])
                await asyncio.sleep(max(0.0, start - now()))
                await self.edge("trace_start")
                t0 = now()
                await self.post_json("/debug/profiler/start",
                                     {"log_dir": trace["log_dir"]})
                self.trace_window = [t0, None]
            await asyncio.sleep(max(0.0, self.window[1] - now()))
            await self.edge("close")
        finally:
            poller.cancel()

    async def end_trace(self) -> None:
        """Writing the trace stalls the server for seconds, so it is stopped
        when nothing is measured any more: after the window, and after the
        tail in which an open loop still waits for first answers."""
        if self.trace_window:
            self.trace_window[1] = now()
            await self.post_json("/debug/profiler/stop", {})

    # -- the two loops ---------------------------------------------------------
    async def closed_loop(self) -> None:
        plan = self.plan
        requests = schedule.closed_stream(plan["traffic"], plan["seed"])
        clients = int(plan["clients"])
        done = [0] * clients
        warm = asyncio.Event()
        rounds = int(plan["warm_rounds"])

        async def client(k: int):
            await asyncio.sleep(k * float(plan["stagger_s"]) / clients)
            while True:
                phase = "window" if self.window else "warm"
                await self.generate(next(requests), now(), phase)
                done[k] += 1
                if min(done) >= rounds:
                    warm.set()

        tasks = [asyncio.ensure_future(client(k)) for k in range(clients)]
        try:
            await warm.wait()
            await self.measure(now())
            await self.end_trace()
        finally:
            await stop(tasks)

    async def open_loop(self) -> None:
        plan = self.plan
        opened = now() + 0.5 - min(r["due_s"] for r in plan["requests"])
        seconds = float(plan["seconds"])
        tasks = []

        async def dispatch():
            for request in plan["requests"]:
                due = opened + request["due_s"]
                await asyncio.sleep(max(0.0, due - now()))
                phase = ("lead_in" if request["due_s"] < 0 else
                         "window" if request["due_s"] < seconds else "tail")
                tasks.append(asyncio.ensure_future(
                    self.generate(request, due, phase)))

        def all_answered() -> bool:
            return all(r["first"] is not None or r["error"]
                       for r in self.records if r["phase"] == "window")

        dispatcher = asyncio.ensure_future(dispatch())
        try:
            await asyncio.sleep(max(0.0, opened - now()))
            await self.measure(opened)
            deadline = self.window[1] + float(plan["tail_s"])
            while now() < deadline and not all_answered():
                await asyncio.sleep(0.05)
            await self.end_trace()
        finally:
            await stop([dispatcher] + tasks)

    async def run(self) -> dict:
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector,
                                         timeout=timeout) as self.session:
            if self.plan["loop"] == "closed":
                await self.closed_loop()
            else:
                await self.open_loop()
        return {"window": self.window, "trace_window": self.trace_window,
                "records": self.records, "scrapes": self.scrapes,
                "slice_scrapes": self.slice_scrapes,
                "device_samples": self.device_samples,
                "log_offsets": self.log_offsets, "notes": self.notes}


async def stop(tasks) -> None:
    """Cancel what still runs (requests cut by the run's end) and wait."""
    for task in tasks:
        task.cancel()
    for result in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(result, Exception):  # a cancellation is not one
            raise result


def main(argv) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    result = asyncio.run(Generator(plan).run())
    tmp = plan["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, plan["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
