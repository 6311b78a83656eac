"""Import guards: the package imports in a fresh interpreter without
initializing a jax backend, and carries no shims for a jax that is not
installed (shard_map is ``jax.shard_map`` with ``check_vma``, the mesh-axis
size is ``jax.lax.axis_size``).
"""
import os
import subprocess
import sys

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")


def test_package_imports_under_pinned_jax():
    """A FRESH interpreter imports the whole package (conftest's own
    import already proves the current process; the subprocess guards
    against import-order luck)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import paddle_tpu; import paddle_tpu.text.gpt_hybrid; "
         "import paddle_tpu.distributed.pipeline; "
         "from jax import shard_map; "
         "assert callable(shard_map)"],
        capture_output=True, text=True, timeout=240,
        cwd=os.path.dirname(PKG), env=env)
    assert out.returncode == 0, out.stderr[-2000:]


def test_compat_carries_no_jax_shims():
    import paddle_tpu.compat as compat

    for name in ("shard_map", "axis_size", "_LEGACY_SHARD_MAP",
                 "_patch_legacy_shard_map_transpose"):
        assert not hasattr(compat, name), name


def test_import_never_initializes_a_jax_backend():
    """``import paddle_tpu`` (and the training/serving entry submodules)
    must not initialize ANY jax backend — no ``jax.devices()``, no
    ``PRNGKey`` at import time.  The bench harness depends on this
    lazy-RNG invariant: it pins the CPU for ``--cpu`` AFTER import, and
    an import-time backend would freeze platform selection before the
    caller can steer it (the RNG state's
    global key is lazy for exactly this reason — framework/random.py).

    Checked in a FRESH interpreter via jax's backend registry: the
    xla_bridge backend cache must still be empty after the imports."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import paddle_tpu\n"
         "import paddle_tpu.hapi, paddle_tpu.jit, paddle_tpu.io\n"
         "import paddle_tpu.optimizer, paddle_tpu.flags\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge._backends, (\n"
         "    'import initialized jax backend(s): '\n"
         "    + repr(list(xla_bridge._backends)))\n"],
        capture_output=True, text=True, timeout=240,
        cwd=os.path.dirname(PKG), env=env)
    assert out.returncode == 0, out.stderr[-2000:]


def test_no_legacy_shard_map_spellings_in_package():
    """Source-scan the package for the 0.4.x spellings: the experimental
    module and the ``check_rep`` keyword."""
    bad = []
    for root, _dirs, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            with open(path, encoding="utf-8") as fh:
                for i, line in enumerate(fh, 1):
                    if ("jax.experimental.shard_map" in line
                            or "jax.experimental import shard_map" in line
                            or "check_rep" in line):
                        bad.append(f"{path}:{i}")
    assert not bad, bad
