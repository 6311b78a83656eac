"""chip_smoke.py's CPU rehearsal, phase by phase.

The script is the driver's proof that the system starts on the chip; these
tests prove its control flow without one: every phase at a tiny size with
the kernels in interpret mode, the four-device path on virtual CPU devices,
and the contract around it (no chip and no ``--rehearse`` is a non-zero exit
with no result line; alone in a directory it fails; the last line's form).
A rehearsal never says anything about the chip.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    m = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = m  # its dataclass looks its module up
    spec.loader.exec_module(m)
    return m


@pytest.fixture()
def rehearsal(smoke, monkeypatch):
    """Interpret-mode kernels and the fused-kernel flags for one test, all
    put back afterwards (the script sets them for the life of its own
    process)."""
    from paddle_tpu.ops import (decode_attention, flash_attention, fused_ce,
                                fused_norm, ssm_update, woq_matmul)
    from paddle_tpu.text import engine

    for m in (decode_attention, flash_attention, fused_ce, fused_norm,
              ssm_update, woq_matmul):
        monkeypatch.setattr(m, "_INTERPRET", m._INTERPRET)
    for flag in ("PADDLE_TPU_FUSED_LN", "PADDLE_TPU_FUSED_CE"):
        monkeypatch.setenv(flag, os.environ.get(flag, ""))
    smoke.interpret_kernels()
    yield smoke
    # executables traced with interpret-mode kernels must not outlive it
    for sz in (smoke.REHEARSAL, smoke.REHEARSAL4):
        engine.ENGINE.purge(sz.cfg(sz.serve_layers))


def test_rehearsal_kernels_phase(rehearsal, capsys):
    rehearsal.phase_kernels(rehearsal.REHEARSAL, seed=0)
    out = capsys.readouterr().out
    assert "21 checks passed" in out
    for name in ("flash dq", "ln dg", "ce dlogits", "w4",
                 "decode int8 Tq4", "paged int8 bs16", "ssm state", "ssm y"):
        assert f"[kernels] {name}: max abs err" in out


def test_rehearsal_train_phase(rehearsal, capsys):
    rehearsal.phase_train(rehearsal.REHEARSAL, jax.devices()[0], seed=0)
    out = capsys.readouterr().out
    assert "depth cut 24->2" in out and "losses" in out


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_rehearsal_serve_phase(rehearsal, capsys, layout):
    rehearsal.phase_serve(rehearsal.REHEARSAL, jax.devices()[0], seed=0,
                          layouts=(layout,))
    out = capsys.readouterr().out
    assert "executables compiled after warm-up: 0" in out
    assert "worst logit margin vs gpt.forward" in out


def test_rehearsal_four_devices_train(rehearsal, capsys):
    rehearsal.phase_train_sharded(rehearsal.REHEARSAL4, jax.devices()[:4],
                                  seed=0)
    out = capsys.readouterr().out
    assert "4 shards on 4 distinct devices" in out


def test_rehearsal_four_devices_serve(rehearsal, capsys):
    rehearsal.phase_serve_sharded(rehearsal.REHEARSAL4, jax.devices()[:4],
                                  seed=0)
    out = capsys.readouterr().out
    assert "four chips equal one chip token for token" in out


def test_served_check_refuses_a_wrong_token(rehearsal):
    """The serve phase's criterion has teeth: one corrupted token lies far
    below the reference argmax."""
    sz = rehearsal.REHEARSAL
    cfg = sz.cfg(sz.serve_layers)
    params = rehearsal.bf16_params(cfg, 0)
    prompts = rehearsal.make_prompts(sz, 0)[:1]
    from paddle_tpu.text import generate

    good = [list(map(int, np.asarray(generate.generate(
        params, cfg, prompts[0][None],
        max_new_tokens=sz.new_tokens))[0][len(prompts[0]):]))]
    assert rehearsal.forward_margins(params, cfg, prompts, good,
                                     sz.seq) <= rehearsal.LOGIT_TOL
    bad = [list(good[0])]
    bad[0][2] = (bad[0][2] + 1) % sz.vocab
    with pytest.raises(AssertionError, match="below the reference"):
        rehearsal.check_served(params, cfg, prompts, bad, sz, "corrupted")


def test_device_phase_refuses_missing_chips(smoke):
    with pytest.raises(SystemExit, match="need a tpu device"):
        smoke.phase_device(rehearse=False, chips=1)
    with pytest.raises(SystemExit, match="--chips 64"):
        smoke.phase_device(rehearse=True, chips=64)


def test_last_line_is_the_result_object(smoke, capsys):
    smoke.main(["--rehearse", "--phases", "none"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0].startswith("[device] platform=cpu")
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": jax.devices()[0]
                                           .device_kind, "count": 1}}
    assert not any(ln.startswith("{") for ln in lines[:-1])


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_and_no_rehearsal_exits_nonzero_without_a_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "need a tpu device" in out.stderr


def test_alone_without_the_program_it_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(tmp_path), "--rehearse")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
