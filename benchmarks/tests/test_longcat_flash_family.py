"""The ``longcat_flash`` family on the CPU: its counts to the digit (100%
may not move with the file), the file against the catalog row, its weights'
recipe, and the readers of the two roofline shares.  Run from the root of
the repo:

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
from benchmarks.families import gpt as gpt_fam  # noqa: E402
from benchmarks.families import longcat_flash as fam  # noqa: E402
from benchmarks.readers import mla_roofline, moe_roofline  # noqa: E402


@pytest.fixture(scope="module")
def config():
    return common.cell("longcat-serve-reason")["config"]


def test_counts_to_the_digit(config):
    """From the shapes of the published config.json: q_a 6144x1536 + q_b
    1536x12288 + kv_a 6144x576 + kv_b 512x16384 + o 8192x6144 + two latent
    norms; a dense FFN 3 x 6144x12288; four norms; the router 6144x768 and
    its selection bias; an expert 3 x 6144x2048."""
    s = fam.sizes(config)
    assert (s["T"], s["V"], s["V_published"]) == (4096, 16384, 16384)
    assert fam.mla_params(s) == 90_572_800
    assert fam.router_params(s) == 4_719_360
    assert fam.expert_params(s) == 37_748_736
    assert fam.dense_layer_params(s) == 638_874_368
    assert fam.total_params(s) == 5_172_749_312
    assert fam.row_values(s) == 576
    assert fam.experts_hit_even(s, 180) == pytest.approx(16 * 0.9414,
                                                         rel=1e-3)
    assert fam.mla_attn_cost(s, 150000.0, 180) == {
        "bytes": 1382400000.0 + 8 * 8388608 * 2,
        "flops": 8 * (2.0 * 64 * 1088 * 150000.0 + 2.0 * 8388608 * 180),
        "row_bytes": 1382400000.0}
    assert fam.moe_step_cost(s, 180, 15.0, 45.0) == {
        "bytes": 4 * 4_719_360 * 2 + 4 * 15.0 * 37_748_736 * 2,
        "flops": 4 * (2.0 * 6144 * 768 * 180 + 2.0 * 37_748_736 * 45.0),
        "expert_bytes": 4 * 15.0 * 37_748_736 * 2}
    cost = fam.decode_step_cost(s, 180, 150000.0)
    dense = 4 * (638_874_368 - 4_719_360) + 6144 + 16384 * 6144 + 180 * 6144
    assert cost["weight_bytes"] == 2 * dense
    assert cost["kv_bytes"] == 1382400000.0
    assert cost["bytes"] == pytest.approx(
        2 * dense + 4 * 4_719_360 * 2 + cost["expert_bytes"] + 1382400000.0)
    assert cost["expert_bytes"] == pytest.approx(
        4 * 16 * 0.9414 * 37_748_736 * 2, rel=1e-3)


def test_the_file_is_the_catalog_row_but_for_the_three_cuts(config):
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.isfile(row):
        pytest.skip("no catalog beside the guides here")
    with open(row, encoding="utf-8") as f:
        want = next(json.loads(line) for line in f
                    if '"LongCat-Flash-Omni"' in line)
    assert config["source"] == want["source_url"]
    differ = {k for k, v in want["config"].items() if config.get(k) != v}
    assert differ == {"num_layers", "n_routed_experts", "vocab_size"}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert config["n_routed_experts_published"] == 512
    assert config["held"] == [0, 16]
    assert [r.split(":")[0] for r in config["reduced"]] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    entry = next(c for c in common.manifest()["configs"]
                 if c["name"] == "longcat-flash-omni-serve")
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]


def test_a_serving_family_names_the_role_it_lacks(config):
    for call in (lambda: fam.train_step(config, [], 0),
                 lambda: fam.reference_loss(config, 0, None),
                 lambda: fam.train_flops_per_token({}, 2048)):
        with pytest.raises(SystemExit, match="role serve"):
            call()


def test_weights_follow_the_recipes_and_the_programs_tree(config):
    """At the rehearsal's size: the tree ``gpt.init_params`` would make
    (the experts' leaves a tuple of a leaf a layer), bf16, every matrix at
    its recipe's spread, the same from the same seed."""
    from paddle_tpu.framework.platform import force_cpu

    force_cpu(1)
    small = common.merged(config, config["rehearse"])
    cfg, params = fam.weights(small, 7)
    _, again = fam.weights(small, 7)
    assert cfg.mla.row_width == 24 and cfg.experts.held == (0, 4)
    blocks = params["blocks"]
    std = lambda x: float(np.std(np.asarray(x, np.float32)))  # noqa: E731
    assert std(blocks["attn0"]["q_b_w"]) == pytest.approx(0.02, rel=0.05)
    assert std(blocks["ffn1"]["out_w"]) == pytest.approx(0.02 / 4, rel=0.05)
    assert std(blocks["moe"]["router_w"]) == pytest.approx(0.2, rel=0.05)
    assert isinstance(blocks["moe"]["gate_w"], tuple) \
        and len(blocks["moe"]["gate_w"]) == 2
    assert blocks["moe"]["gate_w"][0].shape == (4, 128, 64)
    assert std(blocks["moe"]["down_w"][1]) == pytest.approx(0.02 / 4,
                                                            rel=0.1)
    assert not np.asarray(blocks["moe"]["router_b"], np.float32).any()
    assert float(np.asarray(blocks["ln_g"], np.float32).min()) == 1.0
    a, b = blocks["moe"]["up_w"]
    assert np.abs(np.asarray(a, np.float32)
                  - np.asarray(b, np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(params["wte"], np.float32),
                                  np.asarray(again["wte"], np.float32))


Sample = collections.namedtuple("Sample",
                                "t queue_depth slot_occupancy turn held")


def test_roofline_readers_leave_out_what_they_cannot_read(config,
                                                          monkeypatch):
    s = fam.sizes(config)
    joined = [{"rid": i, "t_due": 0.0, "t_first": 0.5, "t_retire": 9.0,
               "tokens": 86, "prompt_len": 100, "out_len": 86}
              for i in range(40)]
    samples = [Sample(1.0 + 0.1 * i, 0, 40, i, 40) for i in range(30)]
    run = {"family": fam, "sizes": s, "joined": joined, "samples": samples,
           "stats_window": (0.0, 10.0),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    for reader in (moe_roofline, mla_roofline):
        monkeypatch.setattr(reader.scope_time, "read", lambda r, a: 8.0)
    monkeypatch.setattr(moe_roofline, "program_counts",
                        lambda: (10.0, 1 / 48))
    want = 100.0 * (fam.moe_step_cost(s, 40, 10.0, 10.0)["bytes"]
                    / 819e9) * 1e3 / 8.0
    assert moe_roofline.read(run, {}) == pytest.approx(want)
    assert 0 < want < 100
    got = mla_roofline.read(run, {})
    assert 0 < got < 100
    # a family with no such cost, a program with no such scope or counters
    # (the parent), a rehearsal with no peaks: nothing, and no raise
    for reader in (moe_roofline, mla_roofline):
        assert reader.read(dict(run, family=gpt_fam), {}) is None
        assert reader.read(dict(run, peaks=None), {}) is None
    monkeypatch.setattr(moe_roofline, "program_counts", lambda: None)
    assert moe_roofline.read(run, {}) is None
    for nothing in (None, 0.0):
        for reader in (moe_roofline, mla_roofline):
            monkeypatch.setattr(reader.scope_time, "read",
                                lambda r, a, v=nothing: v)
            assert reader.read(run, {}) is None


def test_program_counts_read_what_the_program_published():
    from paddle_tpu import telemetry

    telemetry.reset()
    assert moe_roofline.program_counts() is None
    telemetry.count("moe.pairs_held", 10)
    telemetry.count("moe.pairs_zero", 30)
    telemetry.count("moe.pairs_absent", 80)
    assert moe_roofline.program_counts() is None       # no gauge yet
    telemetry.set_gauge("moe.experts_hit", 3.5)
    assert moe_roofline.program_counts() == (3.5, 10 / 120)
    telemetry.reset()
