"""Find an open-loop cell's knee once, by one sweep in one process.

    python3 -m cellbench.sweep --workload <cell> --rates 2,3,4,5,6 --seconds 30

Boots the cell's service once, then offers the cell's traffic mix at
each rate in turn (same generator, same child-process load, a drain
between rates) and prints one table row per rate.  The knee is the
highest rate with no shed or failed request and no growing backlog:
the time to first token of the window's last third stays near its
first third's, and nothing is left waiting when the window closes.
The cell's traffic file then fixes ``rate_per_s`` at 0.8 of it; the
benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from . import reduce, spec, traffic
from .run import device_facts, drive, say


def row(rate: float, w, seconds: float) -> dict:
    recs = w.records
    bad = [r for r in recs if reduce.failed(r, True)]
    ok = [r for r in recs if not reduce.failed(r, True)]
    ttft = lambda rs: [r["first"] - r["due"] for r in rs]  # noqa: E731
    third = seconds / 3.0
    first = [r for r in ok if r["due"] < third]
    last = [r for r in ok if r["due"] >= 2 * third]
    waiting = sum(1 for r in recs if r.get("first", seconds + 1) > seconds)
    gaps = [g for r in ok for g in reduce.token_gaps(r)]
    shed = reduce.hist_delta(w.prom_after.get("requests_shed", reduce.EMPTY_FAMILY),
                             w.prom_before.get("requests_shed"))["value"]
    return {
        "rate_per_s": rate, "sent": len(recs), "failed": len(bad), "shed": shed,
        "ttft_p50_ms": reduce.median(ttft(ok)) * 1e3 if ok else None,
        "ttft_p95_ms": reduce.pctile(ttft(ok), 0.95) * 1e3 if ok else None,
        "ttft_p50_first_third_ms": reduce.median(ttft(first)) * 1e3 if first else None,
        "ttft_p50_last_third_ms": reduce.median(ttft(last)) * 1e3 if last else None,
        "tbt_p95_ms": reduce.pctile(gaps, 0.95) * 1e3 if gaps else None,
        "waiting_at_close": waiting,
        "tokens_per_s": sum(k for r in w.all_records for t, k in r.get(
            "events", []) if 0 <= t < seconds) / seconds,
        "late_p95_ms": reduce.pctile(
            [r["sent"] - r["due"] for r in recs if "sent" in r], 0.95) * 1e3
        if recs else None,
        "compiles": w.compiles,
    }


async def sweep(cell, rates: list[float], seconds: float, seed: int, work: str):
    from .service import Service

    extra = {"DEVICE": "tpu", "WARMUP": "1", "LOG_LEVEL": "WARNING"}
    async with Service(cell.config, work, extra) as svc:
        say("boot", svc.facts)
        for rate in rates:
            mix = {**cell.traffic, "rate_per_s": rate}
            schedule = traffic.build(mix, cell.config["prompt"], seed, seconds)
            w = await drive(svc, schedule, work, f"sweep_{cell.name}")
            say("sweep", row(rate, w, seconds))
            await asyncio.sleep(2.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    cell = spec.resolve(a.workload)
    if cell.traffic["loop"] != "open":
        print("cellbench.sweep: only an open-loop cell has a knee", file=sys.stderr)
        return 2
    peaks = spec.load_json(os.path.join(cell.bench_dir, "peaks.json"))
    work = os.path.join(spec.REPO, ".cellbench_work")
    os.makedirs(work, exist_ok=True)
    say("device", device_facts(peaks, cell.chips, False))
    asyncio.run(sweep(cell, [float(r) for r in a.rates.split(",")],
                      a.seconds, a.seed, work))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
