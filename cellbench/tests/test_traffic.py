"""The seeded generator: same seed -> same schedule; every seed the
same multiset of sizes and gaps; lengths inside their clips."""

import json
import os
import random
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

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


def test_only_the_free_running_mix_staggers_its_callers():
    s = traffic.build(mix("longdoc-closed"), PIECES, 1, 40)
    assert s["stagger_s"] == 0.1 and not s["barrier"]
    # all in well inside the ramp, each first send past the loop's wait for a burst
    assert s["stagger_s"] * (s["clients"] - 1) < s["ramp_s"] / 2
    for name, prompt in (("decode-closed", PIECES), ("chat-open", PIECES),
                         ("predict-closed", BYTES)):
        assert "stagger_s" not in traffic.build(mix(name), prompt, 1, 20)
    with pytest.raises(ValueError, match="without a barrier"):
        traffic.build({**mix("decode-closed"), "stagger_s": 0.1}, PIECES, 1, 20)


class _Unary(BaseHTTPRequestHandler):
    def do_GET(self):
        self._say()

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self._say()

    def _say(self):
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"ok")

    def log_message(self, *a):
        pass


@pytest.mark.parametrize("stagger", [0.15, None])
def test_the_load_generator_staggers_the_first_requests_only(stagger, tmp_path):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Unary)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    m = {"loop": "closed", "clients": 4, "ramp_s": 1.0, "endpoint": "unary",
         "prompt_tokens": {"dist": "fixed", "value": 8}, "pool": 8}
    if stagger:
        m["stagger_s"] = stagger
    sched = {**traffic.build(m, BYTES, 5, 0.5), "url": base + "/predict",
             "ready_url": base + "/healthz"}
    (tmp_path / "s.json").write_text(json.dumps(sched))
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(HERE), "loadgen.py"),
         str(tmp_path / "s.json"), str(tmp_path / "r.jsonl")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ready"
        child.stdin.write(f"{time.monotonic() + 0.2 + m['ramp_s']!r}\n")
        child.stdin.flush()
        assert child.wait(timeout=60) == 0
    finally:
        child.kill()
        srv.shutdown()
    recs = [json.loads(ln) for ln in (tmp_path / "r.jsonl").read_text().splitlines()]
    first = {r["client"]: r["sent"] for r in recs if r["i"] == 0}
    for j in range(4):  # caller j leaves j x stagger after the ramp's start
        assert first[j] == pytest.approx(-1.0 + j * (stagger or 0.0), abs=0.05)
    assert all(r["status"] == 200 for r in recs)
    # and asks again at once: far more requests than callers
    assert len(recs) > 40


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
