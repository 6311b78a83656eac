"""Test config: force CPU with 8 virtual devices so distributed (mesh) tests
run without TPU hardware (reference test_dist_base.py spawns localhost
multi-process clusters; the TPU-native analog is a virtual device mesh).

The shared ``paddle_tpu.framework.platform.force_cpu`` sets the platform and
the virtual-device count before any backend initializes (``import
paddle_tpu`` itself never touches a backend).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.framework.platform import force_cpu  # noqa: E402

force_cpu(8)


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def markov_gpt():
    """A tiny GPT trained (once per session) on the deterministic stream
    next = (tok * 3 + 1) % 13 until loss < 0.1 — the shared capstone model
    for decode/quantization/serving tests: its next token DEPENDS on the
    fed token, so wrong-input bugs can't hide behind attractor tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text import gpt, gpt_hybrid

    cfg = gpt.GPTConfig(vocab_size=16, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    opt = AdamW(learning_rate=3e-3)
    init_fn, step_fn, _ = gpt_hybrid.build_gpt_train_step(cfg, mesh, opt)
    state = init_fn(0)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    def stream(B, T):
        t = rng.integers(0, 13, (B, 1))
        rows = [t]
        for _ in range(T):
            t = (t * 3 + 1) % 13
            rows.append(t)
        return jnp.asarray(np.concatenate(rows, 1), jnp.int32)

    loss = None
    for i in range(150):
        state, loss = step_fn(state, stream(8, 31), key, 3e-3)
    assert float(loss) < 0.1, float(loss)
    return cfg, jax.device_get(state.params)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Reset jax's compilation caches after every test module.

    The full suite performs thousands of XLA:CPU compiles in one
    process; with the caches accumulating across all ~65 modules, the
    compiler segfaulted DETERMINISTICALLY at the same late-suite compile
    in two consecutive full runs (pytest_r05_full.log: decode_step via
    test_serving.py::test_mixed_greedy_and_sampled_batch) while the same
    tests pass in any shorter invocation.  Dropping the caches between
    modules bounds the accumulated compiler state; modules re-compile
    what they share (slightly slower, deterministic, and crash-free)."""
    yield
    import jax

    jax.clear_caches()
