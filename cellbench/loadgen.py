"""Load generator: a child process that NEVER imports JAX.

    python3 loadgen.py <schedule.json> <records.jsonl>

It replays the schedule ``traffic.build`` made against the parent's
port over real HTTP (aiohttp client), so the server's interpreter lock
is not shared with its load.  Protocol on the pipes: the child prints
``ready`` when its connections can be made, reads one line holding the
window's start on the ``time.monotonic()`` clock (system-wide on
Linux, so parent and child read the same clock), runs ramp + window,
waits for what is in flight (bounded), writes one JSON record per
request and exits 0.  A closed loop's callers each ask again when their
answer is complete; with ``barrier`` they ask again together, when the
last answer of the round is complete (callers that work through a
batch in rounds); with ``stagger_s`` caller j sends its first request
j x stagger_s after the ramp begins.

Record: ``{"i", "client", "due", "sent", "first", "events": [[t, n],
...], "done", "status", "tokens", "error"}`` — times in seconds from
the window's start; ``events`` holds each ndjson line of a stream with
the number of tokens it carried (words of the delta: the synthetic
piece table spells one word per token).
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

DRAIN_S = 60.0  # bound on waiting for requests in flight at the end


async def one(http, url: str, req: dict, rec: dict, t0: float) -> None:
    import aiohttp

    rec["sent"] = time.monotonic() - t0
    try:
        async with http.post(url, json=req["body"]) as r:
            rec["status"] = r.status
            if req["body"].get("stream") and r.status == 200:
                events = rec["events"] = []
                async for line in r.content:
                    t = time.monotonic() - t0
                    if not line.strip():
                        continue
                    msg = json.loads(line)
                    if "delta" in msg:
                        n = len(msg["delta"].split())
                        if n:
                            events.append([t, n])
                            rec.setdefault("first", t)
                    elif msg.get("done"):
                        rec["tokens"] = msg.get("tokens_generated")
                        rec["finish"] = msg.get("finish_reason")
                        rec["done"] = t
                    elif "error" in msg:
                        rec["error"] = json.dumps(msg["error"])[:200]
            else:
                body = await r.read()
                rec["done"] = time.monotonic() - t0
                if r.status == 200:
                    rec["ok_body"] = bool(body)
                else:
                    rec["error"] = body[:200].decode("utf-8", "replace")
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]


async def run(schedule: dict, out_path: str) -> None:
    import aiohttp

    url = schedule["url"]
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=schedule["seconds"] + DRAIN_S + 60)
    records: list[dict] = []
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as http:
        # Open the connections the loop will use before the clock
        # starts (keep-alive), so a burst of requests arrives as a
        # burst and not one TCP handshake after another.
        async def touch() -> None:
            async with http.get(schedule["ready_url"]) as r:
                await r.read()

        await asyncio.gather(*(touch() for _ in range(
            max(int(schedule.get("clients") or 0), 1))))
        print("ready", flush=True)
        loop = asyncio.get_running_loop()
        line = await loop.run_in_executor(None, sys.stdin.readline)
        t0 = float(line)  # window start, time.monotonic() clock
        end = schedule["seconds"]
        tasks = []
        if schedule["loop"] == "open":
            for i, req in enumerate(schedule["requests"]):
                delay = t0 + req["due"] - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                rec = {"i": i, "due": req["due"],
                       "prompt_tokens": req["prompt_tokens"]}
                records.append(rec)
                tasks.append(asyncio.create_task(one(http, url, req, rec, t0)))
        else:
            by_client: dict[int, list] = {}
            for req in schedule["requests"]:
                by_client.setdefault(req["client"], []).append(req)

            async def ask(j: int, k: int) -> None:
                req = by_client[j][k % len(by_client[j])]
                rec = {"i": k, "client": j, "prompt_tokens": req["prompt_tokens"]}
                records.append(rec)
                await one(http, url, req, rec, t0)
                rec["due"] = rec["sent"]  # a closed loop has no schedule

            async def client(j: int) -> None:
                late = start + j * stagger - time.monotonic()
                if late > 0:
                    await asyncio.sleep(late)
                k = 0
                while time.monotonic() - t0 < end:
                    await ask(j, k)
                    k += 1

            async def rounds() -> None:
                k = 0
                while time.monotonic() - t0 < end:
                    await asyncio.gather(*(ask(j, k) for j in by_client))
                    k += 1

            start = t0 - schedule["ramp_s"]
            stagger = float(schedule.get("stagger_s", 0.0))
            delay = start - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            if schedule.get("barrier"):
                tasks = [asyncio.create_task(rounds())]
            else:
                tasks = [asyncio.create_task(client(j)) for j in by_client]
        done, pending = await asyncio.wait(
            tasks, timeout=max(t0 + end + DRAIN_S - time.monotonic(), 1.0))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    with open(out_path, "w", encoding="utf-8") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def main(argv: list[str]) -> int:
    with open(argv[1], encoding="utf-8") as f:
        schedule = json.load(f)
    asyncio.run(run(schedule, argv[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
