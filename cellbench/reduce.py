"""Arithmetic from records, counters and histograms to metric values.

``parse_prom`` / ``hist_delta`` / ``hist_pctile`` are a COPY of
``benchmarks/harness.py``'s scrape arithmetic (pinned there by
``tests/test_bench_helpers.py``); the copy lives here so that a PR
which claims a gain cannot change the yardstick.  PERF.md lists the
original for a later PR to delete.
"""

from __future__ import annotations

import math


def pctile(xs: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least a
    share ``q`` of all samples at or under it."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    return s[max(0, math.ceil(len(s) * q) - 1)]


def median(xs: list[float]) -> float:
    return pctile(xs, 0.5)


def parse_prom(text: str) -> dict:
    """Prometheus text exposition -> ``{family: {"count", "sum",
    "buckets": {le: cumulative}, "value"}}`` summed over label
    children.  Histograms fill count/sum/buckets; counters and gauges
    fill ``value``."""
    out: dict[str, dict] = {}

    def fam(name: str) -> dict:
        return out.setdefault(
            name, {"count": 0.0, "sum": 0.0, "buckets": {}, "value": 0.0})

    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, value = line.rsplit(" ", 1)
        try:
            value = float(value)
        except ValueError:
            continue
        name = head.split("{", 1)[0]
        if name.endswith("_bucket") and "le=" in head:
            labels = head.split("{", 1)[1].rstrip("}")
            le = next(kv.split("=", 1)[1].strip('"')
                      for kv in labels.split(",") if kv.startswith("le="))
            le = math.inf if le == "+Inf" else float(le)
            b = fam(name[: -len("_bucket")])["buckets"]
            b[le] = b.get(le, 0.0) + value
        elif name.endswith("_count"):
            fam(name[: -len("_count")])["count"] += value
        elif name.endswith("_sum"):
            fam(name[: -len("_sum")])["sum"] += value
        else:
            base = name[: -len("_total")] if name.endswith("_total") else name
            fam(base)["value"] += value
    return out


EMPTY_FAMILY = {"count": 0.0, "sum": 0.0, "buckets": {}, "value": 0.0}


def hist_delta(after: dict, before: dict | None) -> dict:
    """after − before for one family of ``parse_prom``."""
    before = before or EMPTY_FAMILY
    return {
        "count": after["count"] - before["count"],
        "sum": after["sum"] - before["sum"],
        "value": after["value"] - before["value"],
        "buckets": {le: c - before["buckets"].get(le, 0.0)
                    for le, c in after["buckets"].items()},
    }


def hist_pctile(h: dict, q: float) -> float | None:
    """Percentile estimate from cumulative buckets (linear
    interpolation inside the landing bucket — PromQL's
    ``histogram_quantile``).  None on an empty histogram; a percentile
    landing in the +Inf bucket reports the largest finite edge."""
    total = h["count"]
    if total <= 0:
        return None
    target = q * total
    lo_edge, lo_count = 0.0, 0.0
    for le in sorted(h["buckets"]):
        c = h["buckets"][le]
        if c >= target:
            if math.isinf(le):
                return lo_edge
            span = c - lo_count
            frac = (target - lo_count) / span if span > 0 else 1.0
            return lo_edge + (le - lo_edge) * frac
        lo_edge, lo_count = (0.0 if math.isinf(le) else le), c
    return lo_edge


CONTROL_SLACK = 3  # tokens of one stream that may render as no word


# -- request records -----------------------------------------------------


def in_window(rec: dict, seconds: float) -> bool:
    """A request belongs to the window when it was DUE inside it (open
    loop) or sent inside it (closed loop: due == sent)."""
    return 0.0 <= rec.get("due", rec.get("sent", -1.0)) < seconds


def failed(rec: dict, stream: bool) -> bool:
    """Non-200, shed, cut or malformed: it never drops out of a
    percentile, it counts as the window's length."""
    if rec.get("status") != 200 or "error" in rec or "done" not in rec:
        return True
    if stream:
        n = sum(e[1] for e in rec.get("events", []))
        # The words the events carried must be the token count the
        # server states, less the few control tokens (<unk>, <s>) a
        # random-weight model emits now and then, which spell no word;
        # no token at all is a truncated stream.
        stated = rec.get("tokens")
        return n == 0 or stated is None or not 0 <= stated - n <= CONTROL_SLACK
    return not rec.get("ok_body", False)


def token_gaps(rec: dict) -> list[float]:
    """Gaps between consecutive output tokens of one stream: a token
    takes the arrival time of the event that carried it, so the tokens
    of one chunk are 0 apart and the chunk's first token carries the
    chunk gap."""
    out, prev = [], None
    for t, n in rec.get("events", []):
        if prev is not None:
            out.append(t - prev)
        out.extend([0.0] * (n - 1))
        prev = t
    return out
