"""The whole command end to end on the CPU at a tiny size: boot
through build_service, check, load from the child process, reduce,
print.  A rehearsal proves the path and never a number: its result
says platform cpu and carries no metric."""

import json
import os
import subprocess
import sys

import pytest

from cellbench import spec

HERE = os.path.dirname(os.path.abspath(__file__))


def run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", *args], cwd=spec.REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,over,trace", [
    ("mistral-7b-d8.decode-closed", "rehearse_mistral.json", 0),
    ("mistral-7b-d8.chat-open", "rehearse_mistral_open.json", 1),
    ("bert-base.predict-closed", "rehearse_bert.json", 0),
])
def test_rehearsal_end_to_end(cell, over, trace, tmp_path):
    from conftest import root_with_bert

    root = root_with_bert(tmp_path)  # the BERT cell is entries only
    r = run("--root", root, "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
            "--trace", str(trace), "--rehearse", os.path.join(HERE, over))
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {}  # nothing under a device metric's name
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    want = {m.name for m in (spec.resolve(cell, root).end_to_end if not trace else [])}
    assert want <= set(last["rehearsal_values"])
    assert "cellbench correct" in r.stdout and "cellbench boot" in r.stdout


def test_off_chip_the_command_fails_and_prints_no_result():
    r = run("--workload", "mistral-7b-d8.decode-closed", "--seed", "1",
            "--seconds", "1", "--trace", "0", timeout=120)
    assert r.returncode != 0
    assert "correct" not in r.stdout
    assert "need a TPU" in r.stderr


def test_unknown_workload_is_a_usage_error():
    r = run("--workload", "nope", "--seconds", "1", timeout=60)
    assert r.returncode == 2 and "unknown workload" in r.stderr
