"""A chip belongs to one process at a time, and a device that is not there
is an error.

The launcher and ``spawn`` start workers that need the chip only from a
process that has not initialised a backend; ``set_device`` does not clamp an
index to the last device present.
"""
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.core import place

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_set_device_index_that_is_not_there_raises():
    n = place.device_count("cpu")
    assert paddle.set_device(f"cpu:{n - 1}").device_id == n - 1
    for bad in (f"cpu:{n}", "cpu:99"):
        with pytest.raises(RuntimeError, match="No cpu:"):
            paddle.set_device(bad)
    paddle.set_device("cpu:0")


def test_missing_platform_and_tpu_9_raise():
    assert not place.is_compiled_with_tpu()  # conftest pins the CPU
    with pytest.raises(RuntimeError, match="No tpu device"):
        paddle.set_device("tpu:9")
    assert place.Place("tpu", 9).jax_device is None


def test_only_tpu_counts_as_tpu(monkeypatch):
    """A platform by any other name is not 'tpu'."""
    class _Dev:
        platform = "some_tunnel"
        id = 0

    monkeypatch.setattr(place.jax, "local_devices",
                        lambda backend=None: [_Dev()])
    place._platforms.cache_clear()
    try:
        assert "tpu" not in place._platforms()
        assert "some_tunnel" in place._platforms()
    finally:
        monkeypatch.undo()
        place._platforms.cache_clear()


def test_launcher_never_initialises_a_backend():
    """Importing the launcher and building a worker's environment leaves
    jax's backend registry empty: the workers get the chip."""
    code = (
        "import paddle_tpu.distributed.launch as L\n"
        "env = L._proc_env(0, 2, '127.0.0.1:1234', local_sim=False)\n"
        "assert env['PADDLE_TPU_NUM_PROCESSES'] == '2'\n"
        "assert 'JAX_PLATFORMS' not in env or env['JAX_PLATFORMS'] == "
        "__import__('os').environ.get('JAX_PLATFORMS')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]


def test_spawn_refuses_a_parent_that_holds_the_chip(monkeypatch):
    from jax._src import xla_bridge

    from paddle_tpu.distributed import spawn as sp

    assert not sp._holds_accelerator()  # only the CPU backend is up here
    monkeypatch.setitem(xla_bridge._backends, "tpu", object())
    assert sp._holds_accelerator()
    with pytest.raises(RuntimeError, match="holds the chip"):
        sp.spawn(print, nprocs=2)
