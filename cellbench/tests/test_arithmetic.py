"""Percentiles, histogram deltas, token gaps, the trace reduction."""

import gzip
import json
import math
import os

import pytest

from cellbench import costs, reduce, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_pctile_nearest_rank():
    xs = list(range(1, 101))
    assert reduce.pctile(xs, 0.95) == 95
    assert reduce.pctile(xs, 0.5) == 50
    assert reduce.pctile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        reduce.pctile([], 0.5)


PROM = """# HELP batch_size Items
batch_size_bucket{model="m",le="1.0"} 2.0
batch_size_bucket{model="m",le="8.0"} 6.0
batch_size_bucket{model="m",le="+Inf"} 8.0
batch_size_count{model="m"} 8.0
batch_size_sum{model="m"} 40.0
prefill_stall_seconds_total{model="m"} 1.5
batch_queue_depth{model="m"} 3.0
"""


def test_parse_prom_delta_and_pctile():
    before = reduce.parse_prom(PROM)
    after = reduce.parse_prom(PROM.replace("8.0\n", "18.0\n").replace(
        " 40.0", " 100.0").replace(" 1.5", " 4.0"))
    assert before["batch_size"]["count"] == 8.0
    assert before["prefill_stall_seconds"]["value"] == 1.5
    assert before["batch_queue_depth"]["value"] == 3.0
    d = reduce.hist_delta(after["batch_size"], before["batch_size"])
    assert d["count"] == 10.0 and d["sum"] == 60.0
    assert d["buckets"][math.inf] == 10.0 and d["buckets"][1.0] == 0.0
    d2 = reduce.hist_delta(after["prefill_stall_seconds"],
                           before["prefill_stall_seconds"])
    assert d2["value"] == 2.5
    # a family that first appears inside the window has no 'before'
    assert reduce.hist_delta(after["batch_size"], None)["count"] == 18.0
    h = {"count": 10.0, "buckets": {1.0: 2.0, 8.0: 6.0, math.inf: 10.0}}
    assert reduce.hist_pctile(h, 0.5) == pytest.approx(1.0 + 7.0 * 3 / 4)
    assert reduce.hist_pctile(h, 0.95) == 8.0  # lands in +Inf: largest edge
    assert reduce.hist_pctile({"count": 0.0, "buckets": {}}, 0.5) is None


def test_token_gaps_and_failures():
    rec = {"status": 200, "done": 1.0, "tokens": 9,
           "events": [[0.10, 4], [0.16, 4], [0.25, 1]]}
    gaps = reduce.token_gaps(rec)
    assert len(gaps) == 8  # 9 tokens, 8 gaps
    assert sorted(g for g in gaps if g) == pytest.approx([0.06, 0.09])
    assert not reduce.failed(rec, stream=True)
    assert reduce.failed({**rec, "tokens": 8}, True)  # more words than tokens
    assert not reduce.failed({**rec, "tokens": 10}, True)  # a control token
    assert reduce.failed({**rec, "tokens": 14}, True)  # words went missing
    assert reduce.failed({**rec, "status": 503}, True)  # shed
    assert reduce.failed({k: v for k, v in rec.items() if k != "done"}, True)
    assert reduce.failed({"status": 200, "done": 1.0, "tokens": 0,
                          "events": []}, True)  # truncated
    assert not reduce.failed({"status": 200, "done": 0.2, "ok_body": True}, False)
    assert reduce.in_window({"due": 0.0}, 10) and not reduce.in_window(
        {"due": -0.1}, 10) and not reduce.in_window({"due": 10.0}, 10)


@pytest.fixture(scope="module")
def sample():
    with gzip.open(os.path.join(HERE, "trace_v5e_sample.json.gz")) as f:
        return json.load(f)


def test_trace_reduction_on_recorded_sample(sample):
    """A stretch of a real v5e trace (mistral-7b-d8, 64 streams, PR 24)."""
    s = trace.TraceSummary(sample)
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    sec, runs = s.module_time("jit_paged_chunk_fn")
    assert runs >= 1 and 0.04 < sec / runs < 0.08  # a 4-step chunk ~52-58 ms
    # nested operations: the while's body is taken out of the while
    assert s.ops.get("while", 0.0) < 0.2 * sum(s.ops.values())
    assert s.ops["paged_decode_attention"] > 0
    # self times add up to the busy time (nothing counted twice)
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=0.02)
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and v >= 0 for n, v in b["device_ops"])
    assert 0.0 <= s.idle_share() < 1.0


def test_trace_union_gaps_and_self_time():
    assert trace.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace.gaps([(5, 10), (20, 30)], 0, 40) == [(0, 5), (10, 20), (30, 40)]
    ev = [["%while.1 = x", 0.0, 100.0], ["%fusion.2 = y", 10.0, 30.0],
          ["%fusion.3 = y", 50.0, 20.0], ["%copy.1", 200.0, 10.0]]
    st = trace.self_times(ev)
    assert st == {"while": pytest.approx(50e-9), "fusion": pytest.approx(50e-9),
                  "copy": pytest.approx(10e-9)}
    with pytest.raises(ValueError):
        trace.TraceSummary({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "t", "events": [["x", 0.0, 1.0]]}]}]})


def test_decode_step_cost_mistral_7b_d8():
    from cellbench import spec

    c = spec.load_json(os.path.join(os.path.dirname(HERE), "configs",
                                    "mistral-7b-d8.json"))
    assert costs.decoder_layer_params(c) == 218_112_000
    p = costs.decoder_params(c)
    assert p["total"] == pytest.approx(2.007e9, rel=2e-3)
    assert costs.kv_bytes_per_token(c) == 32768  # 32 KB a token at 8 layers
    cost = costs.decode_step(c, batch=64, live_tokens=8192)
    assert cost["weight_bytes"] == pytest.approx(3.75e9, rel=0.01)
    least, bound = costs.roofline_seconds(
        cost, {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    assert bound == "hbm" and 4e-3 < least < 6e-3
