"""The seeded generator: same seed -> same schedule; every seed the
same multiset of sizes and gaps; lengths inside their clips."""

import json
import os
import random

import pytest

from cellbench import spec, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")
PIECES = {"kind": "pieces", "vocab": 32000, "specials": 1}
BYTES = {"kind": "bytes", "specials": 2}


def mix(name):
    return spec.load_json(os.path.join(MIXES, name + ".json"))


@pytest.mark.parametrize("name,prompt", [
    ("decode-closed", PIECES), ("chat-open", PIECES), ("predict-closed", BYTES)])
def test_same_seed_same_schedule_other_seed_same_work(name, prompt):
    m = mix(name)
    big = 2**31 + 11  # the driver's seeds do not fit 32 signed bits
    a = traffic.build(m, prompt, big, 20)
    b = traffic.build(m, prompt, big, 20)
    c = traffic.build(m, prompt, 7, 20)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)
    assert len(a["requests"]) == len(c["requests"])
    texts = lambda s: sorted(r["body"]["text"] for r in s["requests"])  # noqa: E731
    if m["loop"] == "closed":  # the whole pool is dealt out: the same
        assert texts(a) == texts(c)  # requests, in another order
    else:  # an open loop sends its pool once (the last gap closes the window)
        assert len(texts(a) + texts(c)) - len(set(texts(a)) & set(texts(c))) * 2 <= 2
        assert sorted(r["due"] for r in a["requests"]) != sorted(
            r["due"] for r in c["requests"])
    lo, hi = m["prompt_tokens"]["lo"], m["prompt_tokens"]["hi"]
    assert all(lo <= r["prompt_tokens"] <= hi for r in a["requests"])
    if m["endpoint"] == "stream":
        o = m["output_tokens"]
        olo, ohi = o.get("lo", o.get("value")), o.get("hi", o.get("value"))
        assert all(olo <= r["output_tokens"] <= ohi for r in a["requests"])
        assert all(r["body"]["max_tokens"] == r["output_tokens"] and
                   r["body"]["stream"] for r in a["requests"])


def test_open_loop_arrivals():
    m = mix("chat-open")
    s = traffic.build(m, PIECES, 1, 30)
    dues = [r["due"] for r in s["requests"]]
    assert dues == sorted(dues) and dues[0] >= -m["ramp_s"] and dues[-1] < 30
    n_window = sum(1 for d in dues if d >= 0)
    assert n_window == pytest.approx(m["rate_per_s"] * 30, rel=0.15)
    g = traffic.gaps({"dist": "poisson"}, 5.0, 1000)
    assert sum(g) / len(g) == pytest.approx(0.2)
    g3 = traffic.gaps({"dist": "gamma", "cv": 3}, 5.0, 1000)
    assert sum(g3) / len(g3) == pytest.approx(0.2)
    var = lambda xs: sum((x - 0.2) ** 2 for x in xs) / len(xs)  # noqa: E731
    assert var(g3) > 4 * var(g)  # burstier at the same mean


def test_quantiles_cover_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 160, "sigma": 0.7,
                           "lo": 16, "hi": 480}, 512)
    assert min(q) >= 16 and max(q) == 480 and 150 <= sorted(q)[256] <= 170
    assert traffic.quantiles({"dist": "fixed", "value": 192}, 4) == [192] * 4
    u = traffic.quantiles({"dist": "uniform", "lo": 16, "hi": 48}, 512)
    assert min(u) == 16 and max(u) == 48


def test_prompt_text_has_the_stated_token_count():
    rng = random.Random(0)
    for n in (8, 9, 64, 512):
        t = traffic.prompt_text(n, BYTES, rng)
        assert len(t.encode()) == n - 2 and t == t.strip()
        w = traffic.prompt_text(n, PIECES, rng)
        assert len(w.split()) == n - 1
        assert all(3 <= int(x[1:]) < 32000 for x in w.split())
