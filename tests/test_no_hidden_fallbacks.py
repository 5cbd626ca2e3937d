"""No fallback hides the device (PR 22): what used to warn, skip or
read 0 on the way to a CPU/reference path now fails loudly, and the
compile cache is placed from outside the program."""

from __future__ import annotations

import json
import os
import pathlib

import jax
import pytest

from mlmicroservicetemplate_tpu.ops import autotune
from mlmicroservicetemplate_tpu.runtime import device as device_mod
from mlmicroservicetemplate_tpu.utils.config import load_config

REPO = pathlib.Path(__file__).resolve().parent.parent

TINY_LLAMA = json.dumps({
    "vocab_size": 300, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
    "num_layers": 1, "d_ff": 64, "max_position": 128,
})


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls: dict = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


# -- DEVICE=tpu is verified ------------------------------------------------


def test_device_tpu_on_cpu_backend_raises():
    with pytest.raises(RuntimeError, match="DEVICE=tpu requested"):
        device_mod.apply_device_env("tpu", "0")


def test_device_cpu_still_selects_cpu():
    device_mod.apply_device_env("cpu", "0")
    assert jax.default_backend() == "cpu"


# -- explicit kernel requests ----------------------------------------------


@pytest.mark.parametrize("model", ["llama", "gpt2"])
def test_explicit_pallas_decode_off_tpu_raises(monkeypatch, model):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    monkeypatch.setenv("USE_PALLAS_DECODE", "1")
    monkeypatch.setenv("LLAMA_CONFIG", TINY_LLAMA)
    cfg = load_config({"DEVICE": "cpu", "MODEL_NAME": model, "PAGED_KV": "1"})
    with pytest.raises(RuntimeError, match="USE_PALLAS_DECODE=1 cannot"):
        build_model(cfg)


def test_pallas_interpret_is_the_explicit_cpu_escape(monkeypatch):
    from mlmicroservicetemplate_tpu.models.registry import build_model

    monkeypatch.setenv("USE_PALLAS_DECODE", "1")
    monkeypatch.setenv("LLAMA_CONFIG", TINY_LLAMA)
    cfg = load_config({"DEVICE": "cpu", "MODEL_NAME": "llama",
                       "PAGED_KV": "1", "PALLAS_INTERPRET": "1",
                       "SEQ_BUCKETS": "32"})
    bundle = build_model(cfg)
    assert bundle.cfg.pallas_decode and bundle.cfg.pallas_interpret


def test_pallas_decode_default_follows_backend(monkeypatch):
    """Unset (int8 KV would default the kernel on): no chip, no kernel,
    no error — only an EXPLICIT request must be honoured or fail."""
    from mlmicroservicetemplate_tpu.models.registry import build_model

    monkeypatch.delenv("USE_PALLAS_DECODE", raising=False)
    monkeypatch.setenv("LLAMA_CONFIG", TINY_LLAMA)
    cfg = load_config({"DEVICE": "cpu", "MODEL_NAME": "llama",
                       "QUANT_KV": "int8", "SEQ_BUCKETS": "32"})
    assert not build_model(cfg).cfg.pallas_decode


@pytest.mark.parametrize("value,want", [("1", RuntimeError), ("0", False),
                                        ("", False)])
def test_explicit_pallas_attention_off_tpu(monkeypatch, value, want):
    from mlmicroservicetemplate_tpu.ops.attention import use_pallas_attention

    monkeypatch.setenv("USE_PALLAS_ATTENTION", value)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="USE_PALLAS_ATTENTION=1"):
            use_pallas_attention(max_seq=128)
    else:
        assert use_pallas_attention(max_seq=128) is want


# -- autotune: no winner without a measurement -------------------------------


def test_all_error_sweep_raises_and_persists_nothing(tmp_path, monkeypatch):
    def refuse(kind, vkey, block_size, interpret):
        def call(*a, **k):
            raise ValueError(f"compiler refused {vkey}")
        return call

    monkeypatch.setattr(autotune, "_make_call", refuse)
    autotune.clear()
    table = tmp_path / "pallas_tune.json"
    shape = dict(b=2, kvh=2, n_rep=2, d=8, block_size=4, t=4)
    try:
        with pytest.raises(RuntimeError, match="none of 6 candidate"):
            autotune.ensure_tuned(
                "paged_decode", None, None, interpret=True,
                table_path=str(table), **shape,
            )
        assert not table.exists()
        stats = autotune.stats()
        assert stats["table"] == {} and stats["counts"]["installs"] == 0
        assert stats["counts"]["reject_error"] == 6
        assert autotune.lookup(
            "paged_decode", dtype="float32", quant=False, default="", **shape
        ) == ""
    finally:
        autotune.clear()


# -- the compile cache is placed from outside --------------------------------


def test_jax_cache_dir_env_wins_and_code_sets_no_dir(
        monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv("COMPILE_CACHE_DIR", "/other/dir")
    for dev in ("tpu", "cpu"):
        assert device_mod.enable_compilation_cache(dev, None) == "/some/dir"
        assert device_mod.enable_compilation_cache(dev, "/knob") == "/some/dir"
    assert "jax_compilation_cache_dir" not in config_updates
    assert not os.path.exists("/some/dir")  # JAX makes it, not this code


def test_unset_tpu_default_is_one_fixed_in_checkout_dir(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("COMPILE_CACHE_DIR", raising=False)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    want = str(REPO / ".xla_cache")
    assert device_mod.enable_compilation_cache("tpu") == want
    assert device_mod.enable_compilation_cache("tpu") == want  # no pid/time
    assert config_updates["jax_compilation_cache_dir"] == want
    assert ".xla_cache/" in (REPO / ".gitignore").read_text().splitlines()
    # CPU stays off; the knob keeps its meaning, "off" included.
    assert device_mod.resolve_cache_dir("cpu") is None
    assert device_mod.resolve_cache_dir("tpu", "off") is None
    assert device_mod.resolve_cache_dir("cpu", "/knob") == "/knob"


@pytest.mark.parametrize("env,device,knob,want", [
    ({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, "tpu", None,
     "/some/dir/pallas_tune.json"),
    ({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, "cpu", "/knob",
     "/some/dir/pallas_tune.json"),
    ({}, "tpu", None, str(REPO / ".xla_cache" / "pallas_tune.json")),
    ({}, "cpu", "/knob", "/knob/pallas_tune.json"),
    ({}, "cpu", None, None),
    ({}, "tpu", "0", None),
    ({"PALLAS_TUNE_TABLE": "/t.json"}, "cpu", None, "/t.json"),
])
def test_tuning_table_follows_the_cache_resolution(
        monkeypatch, env, device, knob, want):
    for k in ("JAX_COMPILATION_CACHE_DIR", "COMPILE_CACHE_DIR",
              "PALLAS_TUNE_TABLE"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert autotune.default_table_path(device, knob) == want


def test_no_libtpu_multi_load_switch_in_the_repo():
    """Letting several processes load libtpu at once is the driver's
    switch, for its own runs: no program or test file of the repo sets
    it — they lean on the fixture in test_chip_compile.py instead."""
    needle = "ALLOW_MULTIPLE_" + "LIBTPU_LOAD"
    hits = [
        str(p.relative_to(REPO))
        for pat in ("*.py", "*.sh", "*.toml", "*.yml", "*.cfg", "*.ini")
        for p in REPO.rglob(pat)
        if ".git" not in p.parts and needle in p.read_text(errors="ignore")
    ]
    assert hits == []
