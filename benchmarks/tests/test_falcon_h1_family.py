"""The ``falcon_h1`` family on the CPU: its counts to the digit (100% may
not move with the file), its weights' recipe, and the reader of the
mixers' roofline share.  Run from the root of the repo:

    python -m pytest benchmarks/tests -q

The cell itself is rehearsed by ``test_benchmark.py`` (every workload of
the manifest, traced and untraced)."""
import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.families import falcon_h1 as fam  # noqa: E402
from benchmarks.families import gpt as gpt_fam  # noqa: E402
from benchmarks.readers import ssm_roofline  # noqa: E402


@pytest.fixture(scope="module")
def config():
    return common.cell("falconh1-serve-chat")["config"]


def test_counts_to_the_digit(config):
    """From the shapes of the published config.json: q 5120x2560 + k, v
    2 x 5120x512 + o 2560x5120; in_proj 5120x9248 + out_proj 4096x5120 +
    conv 5120x4 + 5120 + 3 x 32 + 4096; MLP 3 x 5120x21504; two norms."""
    s = fam.sizes(config)
    assert (s["T"], s["V"], s["V_published"]) == (2048, 261120, 261120)
    assert fam.mixer_params(s) == 68_351_072
    assert fam.layer_params(s) == 430_120_032
    assert fam.slot_state_bytes(s) == 32 * 128 * 256 * 4 + 3 * 5120 * 2
    assert fam.decode_step_cost(s, 40, 20000.5) == {
        "bytes": 10109506688.0, "flops": 316151147520.0,
        "weight_bytes": 7835729024, "kv_bytes": 245766144.0,
        "state_bytes": 2028011520.0}
    assert fam.ssm_step_cost(s, 40) == {
        "bytes": 2848224384.0, "flops": 34318464000.0,
        "state_bytes": 2028011520.0}


def test_the_file_is_the_catalog_row_but_for_the_depth(config):
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.isfile(row):
        pytest.skip("no catalog beside the guides here")
    with open(row, encoding="utf-8") as f:
        want = next(json.loads(line) for line in f
                    if '"Falcon-H1-34B-Instruct"' in line)
    assert config["source"] == want["source_url"]
    differ = {k for k, v in want["config"].items() if config.get(k) != v}
    assert differ == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 6
    assert config["reduced"] == ["num_hidden_layers: 72 -> 6"]


def test_a_serving_family_names_the_role_it_lacks(config):
    for call in (lambda: fam.train_step(config, [], 0),
                 lambda: fam.reference_loss(config, 0, None),
                 lambda: fam.train_flops_per_token({}, 2048)):
        with pytest.raises(SystemExit, match="role serve"):
            call()


def test_weights_follow_the_recipes_and_the_programs_tree(config):
    """At the rehearsal's size: the tree ``gpt.init_params`` would make,
    bf16, every matrix at its recipe's spread (so that each multiplier
    meets the scale that cancels it), filled a block at a time."""
    from paddle_tpu.framework.platform import force_cpu

    force_cpu(1)
    small = common.merged(config, config["rehearse"])
    old = fam.FILL_ELEMENTS
    fam.FILL_ELEMENTS = 5000            # several blocks a leaf, a ragged end
    try:
        cfg, params = fam.weights(small, 7)
        _, again = fam.weights(small, 7)
    finally:
        fam.FILL_ELEMENTS = old
    assert cfg.ssm.d_state == 16 and cfg.head_dim == 64
    std = {k: float(np.std(np.asarray(v, np.float32)))
           for k, v in params["blocks"].items()}
    m = small
    assert std["fc_w"] == pytest.approx(0.02, rel=0.05)
    assert std["gate_w"] == pytest.approx(
        0.02 / m["mlp_multipliers"][0], rel=0.05)
    assert std["out_w"] == pytest.approx(
        0.02 / 2 / m["mlp_multipliers"][1], rel=0.05)
    k_w = np.asarray(params["blocks"]["kv_w"][:, 0], np.float32)
    assert float(k_w.std()) == pytest.approx(
        0.02 / m["key_multiplier"], rel=0.05)
    assert float(np.std(np.asarray(params["lm_head"], np.float32))) == \
        pytest.approx(0.02 / m["lm_head_multiplier"], rel=0.05)
    assert float(np.asarray(params["blocks"]["ssm_D"], np.float32).min()) \
        == 1.0
    a = np.exp(np.asarray(params["blocks"]["ssm_A_log"], np.float32))
    assert 0.99 <= a.min() and a.max() <= 16.1
    np.testing.assert_array_equal(np.asarray(params["wte"], np.float32),
                                  np.asarray(again["wte"], np.float32))


Sample = collections.namedtuple("Sample",
                                "t queue_depth slot_occupancy turn held")


def test_roofline_reader_leaves_out_what_it_cannot_read(config,
                                                        monkeypatch):
    s = fam.sizes(config)
    joined = [{"rid": i, "t_due": 0.0, "t_first": 0.5, "t_retire": 9.0,
               "tokens": 86, "prompt_len": 100, "out_len": 86}
              for i in range(40)]
    samples = [Sample(1.0 + 0.1 * i, 0, 40, i, 40) for i in range(30)]
    run = {"family": fam, "sizes": s, "joined": joined, "samples": samples,
           "stats_window": (0.0, 10.0),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    monkeypatch.setattr(ssm_roofline.scope_time, "read", lambda r, a: 8.0)
    want = 100.0 * (fam.ssm_step_cost(s, 40)["bytes"] / 819e9) * 1e3 / 8.0
    assert ssm_roofline.read(run, {}) == pytest.approx(want)
    assert 0 < want < 100
    # a family with no mixer, a program with no ``ssm`` scope (the parent:
    # 0.0 or None), a rehearsal with no peaks: nothing, and no raise
    assert ssm_roofline.read(dict(run, family=gpt_fam), {}) is None
    assert ssm_roofline.read(dict(run, peaks=None), {}) is None
    for nothing in (None, 0.0):
        monkeypatch.setattr(ssm_roofline.scope_time, "read",
                            lambda r, a, v=nothing: v)
        assert ssm_roofline.read(run, {}) is None
