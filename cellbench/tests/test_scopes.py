"""The two readers PR 25 adds (device time per ``named_scope``, share
of idle time under the program's own span names), on a stretch of the
builder's own v5e trace recorded WITH the operations' scope paths, and
the wire-format reading of an xplane file on a hand-built one."""

import copy
import gzip
import json
import os
import types

import pytest

from cellbench import scopes, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
DECODE, CHAT = "mistral-7b-d8.decode-closed", "mistral-7b-d8.chat-open"
NEW = {  # per_layer entries of PR 25 -> the cells PR 25 listed each for
    "stream_queue_wait_ms.chat": [CHAT, DECODE],  # `.decode` was its twin until PR 55
    "stream_admit_ms.chat": [CHAT, DECODE],
    "prefill_fill_pct.chat": [CHAT], "prefill_stall_ms.chat": [CHAT],
    "decode_attn_ms.decode": [DECODE], "decode_mlp_ms.decode": [DECODE],
    "idle_named_pct.chat": [CHAT],
}


@pytest.fixture(scope="module")
def sample():
    """0.29 s of mistral-7b-d8.chat-open on one v5e (PR 25): a decode
    chunk, 77 ms with nothing to serve, a lone admission (``jit_start``
    + ``jit_insert``), two more chunks.  'XLA Ops' events carry the
    ``tf_op`` path as a fourth element, operation names are shortened."""
    with gzip.open(os.path.join(HERE, "trace_v5e_scopes_sample.json.gz")) as f:
        return json.load(f)


def three(planes: dict) -> dict:
    """The structure ``TraceSummary`` takes: no fourth element."""
    out = copy.deepcopy(planes)
    for p in out["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e[:3] for e in ln["events"]]
    return out


def reader(name: str):
    (m,) = [m for m in spec.resolve(NEW[name][0]).per_layer if m.name == name]
    return m


def test_every_new_entry_resolves():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, cells in NEW.items():
        e = entries[name]  # by NAME: later PRs append cells to its list
        assert set(cells) <= set(e["workloads"])
        assert e["layer"] and e["source"] in (
            "device_trace", "program_counter", "program_span", "host_clock")
        for cell in cells:
            assert cell in e2e[e["moves"]].get("workloads", [cell])
        assert callable(reader(name).read)


def test_scope_of_takes_the_innermost_part():
    f = scopes.scope_of
    assert f("jit(paged_chunk_fn)/decode_chunk/while/body/closed_call/attn/"
             "jit(paged_decode_attention)/reshape:") == "attn"
    assert f("jit(paged_chunk_fn)/decode_chunk/while/body/mlp/dot_general:") == "mlp"
    assert f("jit(start)/prefill_wave/while/body/closed_call/kv_write/scatter:") \
        == "kv_write"
    assert f("jit(paged_chunk_fn)/decode_chunk/while") == "decode_chunk"
    assert f("jit(insert)/slot_insert/dynamic_update_slice:") == "slot_insert"
    assert f("") == f("jit(f)/jit(main)/attention_like/mul") == "unscoped"


def test_scope_seconds_nesting_and_containment():
    mods = [["jit_x(1)", 0.0, 100.0], ["jit_x(1)", 200.0, 100.0]]
    ops = [["%while.1", 0.0, 100.0, "decode_chunk"],   # keeps 100-30-20 = 50
           ["%fusion.2", 10.0, 30.0, "attn"], ["%fusion.3", 50.0, 20.0, "mlp"],
           ["%copy.1", 150.0, 10.0, "attn"],            # outside every run
           ["%copy.2", 210.0, 40.0, "unscoped"]]
    got = scopes.scope_seconds(mods, ops)
    assert got == {"decode_chunk": pytest.approx(50e-9), "attn": pytest.approx(30e-9),
                   "mlp": pytest.approx(20e-9), "unscoped": pytest.approx(40e-9)}


def test_scope_table_on_recorded_sample(sample, monkeypatch):
    t = scopes.table(sample, "jit_paged_chunk_fn")
    assert t["runs"] == 3 and t["scoped"]
    # nothing counted twice, nothing lost: the scopes add up to the
    # executable's own time on the 'XLA Modules' line
    assert sum(t["seconds"].values()) == pytest.approx(t["module_seconds"], rel=0.02)
    sec = t["seconds"]
    assert sec["attn"] > sec["mlp"] > sec["unscoped"] > sec["qkv_rope"] > sec["kv_write"]
    # the prefill executable has its own step kind and the same parts
    p = scopes.table(sample, "jit_start")
    assert p["runs"] == 1 and p["seconds"]["mlp"] > p["seconds"]["attn"] > 0
    assert p["seconds"]["prefill_wave"] > 0 and "decode_chunk" not in p["seconds"]

    monkeypatch.setattr(scopes, "scope_table", lambda module: scopes.table(sample, module))
    ctx = types.SimpleNamespace(trace=object(), engine={"chunk_tokens": 4}, notes={})
    attn = reader("decode_attn_ms.decode")
    v = attn.read(ctx, **attn.args)
    steps = 3 * 4
    assert v == pytest.approx((sec["kv_write"] + sec["attn"]) / steps * 1000.0)
    assert 7.0 < v < 8.0  # of a 14.5 ms step (my chip run, PR 25)
    mlp = reader("decode_mlp_ms.decode")
    assert 3.5 < mlp.read(ctx, **mlp.args) < 4.2
    note = ctx.notes["scopes:jit_paged_chunk_fn"]
    assert set(note["ms_per_step"]) == set(sec) and "unscoped" in note["ms_per_step"]
    assert sum(note["ms_per_step"].values()) == pytest.approx(
        note["module_ms_per_step"], rel=0.02)


def test_scope_reader_finds_nothing_without_paths(sample, monkeypatch):
    """A program from before the scopes (the parent commit): the same
    trace with no path on any operation reads as no value, not as 0."""
    bare = copy.deepcopy(sample)
    for p in bare["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e[:3] + [""] if len(e) == 4 else e for e in ln["events"]]
    assert not scopes.table(bare, "jit_paged_chunk_fn")["scoped"]
    attn = reader("decode_attn_ms.decode")
    ctx = types.SimpleNamespace(trace=object(), engine={"chunk_tokens": 4}, notes={})
    monkeypatch.setattr(scopes, "scope_table", lambda module: scopes.table(bare, module))
    assert attn.read(ctx, **attn.args) is None
    assert "unscoped" in ctx.notes["scopes:jit_paged_chunk_fn"]["ms_per_step"]
    monkeypatch.setattr(scopes, "scope_table", lambda module: None)
    assert attn.read(ctx, **attn.args) is None
    ctx.trace = None
    assert attn.read(ctx, **attn.args) is None


def test_idle_named_share(sample, monkeypatch):
    s = trace.TraceSummary(three(sample))
    # the recorded gaps: 77 ms with nothing to serve, 4 ms of inserts
    assert set(s.idle_by_host) == {"loop/queue_pop", "loop/insert"}
    m = reader("idle_named_pct.chat")
    pre = tuple(m.args["prefixes"])
    assert {"loop/wave_dispatch", "loop/wave_fetch", "loop/insert", "loop/deliver",
            "loop/chunk_dispatch", "dispatch:chunk", "dispatch:insert"} \
        <= scopes.named_host_spans(sample, pre)
    monkeypatch.setattr(scopes, "host_names",
                        lambda prefixes: scopes.named_host_spans(sample, prefixes))
    ctx = types.SimpleNamespace(trace=s, notes={})
    assert m.read(ctx, **m.args) == pytest.approx(100.0)
    s.idle_by_host = {"loop/insert": 0.03, "PjitFunction(insert)": 0.006,
                      "unattributed": 0.004}
    assert m.read(ctx, **m.args) == pytest.approx(75.0)
    s.idle_by_host = {}
    assert m.read(ctx, **m.args) == 100.0  # no gap is left unnamed
    # the parent commit names no phase: nothing to read
    monkeypatch.setattr(scopes, "host_names", lambda prefixes: set())
    assert m.read(ctx, **m.args) is None
    ctx.trace = None
    assert m.read(ctx, **m.args) is None


# -- an xplane file, encoded by hand -------------------------------------


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def entry(key: int, msg: bytes) -> bytes:
    return field(1, key) + field(2, msg)


def test_xplane_wire_reading(tmp_path):
    stat_md = (field(5, entry(7, field(1, 7) + field(2, "tf_op")))
               + field(5, entry(8, field(1, 8) + field(2, "hlo_category")))
               + field(5, entry(9, field(1, 9) + field(2, "jit(f)/decode_chunk/mlp/dot:"))))
    ev_md = (
        field(4, entry(1, field(1, 1) + field(2, "%fusion.1 = f32[8] fusion(...)")
                       + field(5, field(1, 8) + field(5, "fusion"))
                       + field(5, field(1, 7) + field(5, "jit(f)/decode_chunk/attn/mul:"))))
        + field(4, entry(2, field(1, 2) + field(2, "%fusion.2 = f32[8] fusion(...)")
                         + field(5, field(1, 7) + field(7, 9))))   # by reference
        + field(4, entry(3, field(1, 3) + field(2, "%copy.3 = f32[8] copy(...)")))
        + field(4, entry(4, field(1, 4) + field(2, "jit_f(123)"))))

    def ev(mid, off_ps, dur_ps):
        return field(4, field(1, mid) + field(2, off_ps) + field(3, dur_ps))

    ops = field(3, field(2, "XLA Ops") + field(3, 1000) + ev(1, 0, 5000)
                + ev(2, 6000, 2000) + ev(3, 9000, 1000))
    mods = field(3, field(2, "XLA Modules") + field(3, 1000) + ev(4, 0, 10000))
    other = field(3, field(2, "Steps") + field(3, 1000) + ev(4, 0, 10000))
    device = field(1, field(2, "/device:TPU:0") + stat_md + ev_md + ops + mods + other)
    host = field(1, field(2, "/host:CPU")
                 + field(4, entry(1, field(1, 1) + field(2, "loop/deliver")))
                 + field(3, field(2, "python3") + field(3, 1000)
                         + field(4, field(1, 1) + field(2, 2000) + field(3, 3000))))
    skipped = field(1, field(2, "/device:CUSTOM:Megascale Trace") + ops)
    d = tmp_path / ".cellbench_work" / "trace_x" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "x.xplane.pb").write_bytes(device + host + skipped)
    assert scopes.newest_xplane(str(tmp_path)) == str(d / "x.xplane.pb")
    assert scopes.newest_xplane(str(tmp_path / "nothing-here")) is None
    got = scopes.load_xplane(str(d / "x.xplane.pb"))
    assert [p["name"] for p in got["planes"]] == ["/device:TPU:0", "/host:CPU"]
    lines = {ln["name"]: ln["events"] for ln in got["planes"][0]["lines"]}
    assert set(lines) == {"XLA Ops", "XLA Modules"}
    assert lines["XLA Ops"] == [
        ["%fusion.1 = f32[8] fusion(...)", 1000.0, 5.0, "jit(f)/decode_chunk/attn/mul:"],
        ["%fusion.2 = f32[8] fusion(...)", 1006.0, 2.0, "jit(f)/decode_chunk/mlp/dot:"],
        ["%copy.3 = f32[8] copy(...)", 1009.0, 1.0, ""]]
    assert lines["XLA Modules"] == [["jit_f(123)", 1000.0, 10.0, ""]]
    t = scopes.table(got, "jit_f")
    assert t["runs"] == 1 and t["scoped"]
    assert t["seconds"] == {"attn": pytest.approx(5e-9), "mlp": pytest.approx(2e-9),
                            "unscoped": pytest.approx(1e-9)}
    assert scopes.host_names(("loop/",), root=str(tmp_path)) == {"loop/deliver"}
    assert scopes.scope_table("jit_f", root=str(tmp_path))["runs"] == 1
