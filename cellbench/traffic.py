"""The one general traffic generator: a mix file's parameters + a seed
-> a schedule of requests (no JAX here; plain Python + the stdlib).

Steadiness rule (the contract's): every seed gets the SAME pool of
requests — sizes at equal-probability quantiles of the mix's
distributions, and the very same prompt texts (drawn once from the
mix's own ``text_seed``) — and the same multiset of inter-arrival gaps,
in another order.  So two seeds offer the same work (with greedy
decoding, the same answers too), and run-to-run spread is the system's,
not the draw's.

Mix file keys
  loop        "closed" | "open"
  clients     closed loop: concurrent callers, each sends its next
              request when the previous one completed
  barrier     closed loop: true = the callers ask in rounds, all
              together again when the round's last answer is complete
  rate_per_s  open loop: mean arrivals per second, fixed in the file
  arrivals    open loop: {"dist": "poisson"} or {"dist": "gamma", "cv": 3}
  ramp_s      seconds of the same traffic sent before the window opens
              (counted as set-up; brings the loop to its steady state)
  stagger_s   closed loop without a barrier: caller j sends its first
              request j x stagger_s after the ramp begins, so the
              service's first admission is one request and not a race
              for how many of a burst it catches (default 0: all at once)
  endpoint    "stream" (POST /predict stream=true) | "unary"
  prompt_tokens / output_tokens
              {"dist": "uniform"|"lognormal"|"fixed", ...}, in the
              service's own tokens; output_tokens only for streams
  pool        closed loop: requests in the mix's pool (quantile
              resolution), default 512; an open loop's pool is its run:
              rate x (ramp + window) requests, each sent once
  text_seed   seeds the pool's prompt texts and the pairing of prompt
              with answer length (default 0); ``--seed`` only orders
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

PIECE_SPECIALS = 3  # <unk>, <s>, </s> of the synthetic piece table
_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def quantiles(dist: dict, n: int) -> list[int]:
    """``n`` equal-probability quantiles of a length distribution,
    rounded to whole tokens and clipped to [lo, hi]."""
    kind = dist["dist"]
    ps = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        vals = [float(dist["value"])] * n
    elif kind == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        vals = [lo + p * (hi - lo) for p in ps]
    elif kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        nd = NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(p)) for p in ps]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("lo", 1), dist.get("hi", math.inf)
    return [int(min(max(round(v), lo), hi)) for v in vals]


def gaps(arrivals: dict, rate: float, n: int) -> list[float]:
    """``n`` inter-arrival gaps with mean 1/rate: equal-probability
    quantiles of the exponential (poisson) or gamma distribution."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = arrivals.get("dist", "poisson")
    if kind == "poisson":
        raw = [-math.log(1.0 - p) for p in ps]
    elif kind == "gamma":
        # Shape k = 1/cv^2; quantiles by inverting a sampled CDF keeps
        # this dependency-free and seed-independent.
        k = 1.0 / float(arrivals["cv"]) ** 2
        rng = random.Random(12345)
        draws = sorted(rng.gammavariate(k, 1.0 / k) for _ in range(64 * n))
        raw = [draws[int(p * len(draws))] for p in ps]
    else:
        raise ValueError(f"unknown arrival distribution {kind!r}")
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def prompt_text(tokens: int, prompt: dict, rng: random.Random) -> str:
    """A text that the configuration's tokenizer encodes to exactly
    ``tokens`` tokens.  ``prompt`` is the config file's ``prompt`` key:
    ``{"kind": "pieces", "vocab": V, "specials": 1}`` — words ``w<id>``
    of the synthetic piece table, one token each, plus BOS;
    ``{"kind": "bytes", "specials": 2}`` — the byte tokenizer, one
    token per ASCII character, plus CLS and SEP."""
    n = max(tokens - int(prompt.get("specials", 0)), 1)
    if prompt["kind"] == "pieces":
        v = int(prompt["vocab"])
        return " ".join(f"w{rng.randrange(PIECE_SPECIALS, v)}" for _ in range(n))
    if prompt["kind"] == "bytes":
        chars = [rng.choice(_LETTERS) for _ in range(n)]
        for i in range(1, n - 1):
            if chars[i - 1] != " " and rng.random() < 0.18:
                chars[i] = " "
        return "".join(chars)
    raise ValueError(f"unknown prompt kind {prompt['kind']!r}")


def build(mix: dict, prompt: dict, seed: int, seconds: float) -> dict:
    """The schedule the load generator replays: ``{"loop", "clients",
    "ramp_s", "seconds", "endpoint", "requests": [...]}``.  Open loop:
    one list ordered by ``due`` (seconds from the window's start,
    negative inside the ramp).  Closed loop: ``requests`` holds each
    client's own list under ``"client"``; a client cycles through its
    list until the window closes."""
    ramp = float(mix.get("ramp_s", 0.0))
    pool = int(mix.get("pool", 512))
    stream = mix["endpoint"] == "stream"
    if mix["loop"] == "open":
        # An open loop sends each request of its pool exactly once, so
        # the pool IS the run's requests: every seed offers the same
        # work, not another sample of it.
        pool = max(int(round(float(mix["rate_per_s"]) * (ramp + seconds))), 2)
    fixed = random.Random(int(mix.get("text_seed", 0)))  # the same for every seed
    p_lens = quantiles(mix["prompt_tokens"], pool)
    o_lens = quantiles(mix["output_tokens"], pool) if stream else [0] * pool
    fixed.shuffle(o_lens)  # prompt and answer lengths are independent
    requests = []
    for n, o in zip(p_lens, o_lens):
        body = {"text": prompt_text(n, prompt, fixed)}
        r = {"prompt_tokens": n, "body": body}
        if stream:
            body["stream"] = True
            body["max_tokens"] = o
            r["output_tokens"] = o
        requests.append(r)
    rng = random.Random(seed)
    rng.shuffle(requests)

    def request(i: int) -> dict:
        return requests[i % pool]

    out = {"loop": mix["loop"], "ramp_s": ramp, "seconds": float(seconds),
           "endpoint": mix["endpoint"], "clients": int(mix.get("clients", 0)),
           "barrier": bool(mix.get("barrier", False))}
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        gs = gaps(mix.get("arrivals", {}), rate, pool)
        rng.shuffle(gs)
        t, reqs = -ramp, []
        for i, g in enumerate(gs[:-1]):  # the gaps sum to ramp + window:
            t += g                       # the last one closes the window
            reqs.append({**request(i), "due": t})
        out["requests"] = reqs
    elif mix["loop"] == "closed":
        if "stagger_s" in mix:
            if out["barrier"]:
                raise ValueError("stagger_s is for callers without a barrier")
            out["stagger_s"] = float(mix["stagger_s"])
        c = out["clients"]
        per_client = max(pool // c, 1)
        out["requests"] = [
            {**request(k * c + j), "client": j}
            for j in range(c) for k in range(per_client)
        ]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return out
