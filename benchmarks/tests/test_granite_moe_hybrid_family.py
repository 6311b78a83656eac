"""The ``granite_moe_hybrid`` family on the CPU: its counts to the digit
(100% may not move with the file), the file against the catalog row, its
weights' recipe, the readers of its two roofline shares, and the cell's
rehearsal.  Run from the root of the repo:

    python -m pytest benchmarks/tests -q

The cell is rehearsed by ``test_benchmark.py`` too (every workload of the
manifest, traced and untraced) and, in tier-1, by
``tests/test_benchmark_seam.py``."""
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
from benchmarks.families import granite_moe_hybrid as fam  # noqa: E402
from benchmarks.readers import moe_roofline, ssm_roofline  # noqa: E402
from benchmarks.tests.test_benchmark import check_line, rehearse  # noqa: E402

CELL = "granite4h-serve-docqa"
MIXER, ATTN, ROUTER, SHARED, EXPERT = (102_286_976, 41_943_040, 294_912,
                                       18_874_368, 9_437_184)


@pytest.fixture(scope="module")
def config():
    return common.cell(CELL)["config"]


def test_counts_to_the_digit(config):
    """From the shapes of the published config.json: a mixer's in_proj
    4096 x 16,768 (8,192 z + 8,448 xBC + 128 dt), conv 8,448 x 4 and its
    bias, dt_bias, A_log, D, the gated norm's gain, out_proj 8,192 x 4,096;
    attention's q 4096 x 4096, k and v 4096 x 1024, o 4096 x 4096; in
    every layer two norms, the router 4096 x 72, the shared expert 3 x 4096
    x 1,536; an expert 3 x 4096 x 768."""
    s = fam.sizes(config)
    assert (s["T"], s["V"], s["V_published"]) == (4096, 50176, 50176)
    assert (s["L"], s["Lm"], s["La"], s["k"], s["E"]) == (10, 9, 1, 10, 36)
    assert fam.mixer_params(s) == MIXER
    assert fam.attn_params(s) == ATTN
    assert fam.router_params(s) == ROUTER
    assert fam.shared_params(s) == SHARED
    assert fam.expert_params(s) == EXPERT
    assert fam.layer_params(s, "mamba") == 121_464_448
    assert fam.layer_params(s, "attention") == 61_120_512
    assert fam.total_params(s) == 4_757_211_776
    assert 9 * fam.slot_state_bytes(s) == 38_204_928
    assert fam.kv_token_bytes(s) == 4096
    hit40 = 36 * (1 - (62 / 72) ** 40)
    assert fam.experts_hit_even(s, 40) == pytest.approx(hit40)
    assert hit40 == pytest.approx(35.91, abs=0.01)
    state40 = 2.0 * 38_204_928 * 40
    assert fam.ssm_step_cost(s, 40) == {
        "bytes": 2 * 9 * MIXER + state40,
        "flops": 40 * 9 * (2.0 * MIXER + 6.0 * 128 * 64 * 128),
        "state_bytes": state40}
    assert fam.moe_step_cost(s, 40, 30.0, 200.0) == {
        "bytes": 10 * (ROUTER + SHARED) * 2 + 10 * 30.0 * EXPERT * 2,
        "flops": 10 * (2.0 * (ROUTER + SHARED) * 40 + 2.0 * EXPERT * 200.0),
        "expert_bytes": 10 * 30.0 * EXPERT * 2}
    cost = fam.decode_step_cost(s, 40, 60000.0)
    dense = (9 * MIXER + ATTN + 10 * (2 * 4096 + ROUTER + SHARED) + 4096
             + 50176 * 4096 + 40 * 4096)
    assert cost["weight_bytes"] == 2 * dense
    assert cost["kv_bytes"] == 4096 * 60000.0
    assert cost["state_bytes"] == state40
    assert cost["expert_bytes"] == pytest.approx(10 * hit40 * EXPERT * 2)
    assert cost["bytes"] == pytest.approx(
        2 * dense + cost["expert_bytes"] + 4096 * 60000.0 + state40)
    # 200 of a step's 400 selections fall on held experts under even routing
    assert cost["flops"] == pytest.approx(
        2.0 * (ATTN + 50176 * 4096) * 40
        + 40 * 9 * (2.0 * MIXER + 6.0 * 128 * 64 * 128)
        + 10 * (2.0 * (ROUTER + SHARED) * 40 + 2.0 * EXPERT * 200.0)
        + 4.0 * 32 * 128 * 60000.0)
    # the step is bound by what it moves, by far
    assert cost["bytes"] / 819e9 > 5 * cost["flops"] / 197e12


def test_the_file_is_the_catalog_row_but_for_the_cuts(config):
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.isfile(row):
        pytest.skip("no catalog beside the guides here")
    with open(row, encoding="utf-8") as f:
        want = next(json.loads(line) for line in f
                    if '"granite-4.0-h-small"' in line)
    assert config["source"] == want["source_url"]
    differ = {k for k, v in want["config"].items() if config.get(k) != v}
    assert differ == {"num_hidden_layers", "layer_types",
                      "num_local_experts", "vocab_size"}
    assert config["layer_types"] == want["config"]["layer_types"][:10]
    assert config["layer_types"].index("attention") == 5
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 36, 50176)
    assert config["num_local_experts_published"] == 72
    assert config["held"] == [0, 36]
    cuts = ["num_hidden_layers", "layer_types", "num_local_experts",
            "vocab_size"]
    assert [r.split(":")[0] for r in config["reduced"]] == cuts
    entry = next(c for c in common.manifest()["configs"]
                 if c["name"] == "granite-4.0-h-small-serve")
    assert entry["reduced"] == cuts


def test_a_serving_family_names_the_role_it_lacks(config):
    for call in (lambda: fam.train_step(config, [], 0),
                 lambda: fam.reference_loss(config, 0, None),
                 lambda: fam.train_flops_per_token({}, 2048)):
        with pytest.raises(SystemExit, match="role serve"):
            call()


def test_weights_follow_the_recipes_and_the_programs_tree(config):
    """At the rehearsal's size: the tree ``gpt.init_params`` would make
    (each kind's mixer leaves as deep as the kind, the experts' leaves a
    tuple of a leaf a layer), bf16, every matrix at its recipe's spread,
    the same from the same seed."""
    from paddle_tpu.framework.platform import force_cpu

    force_cpu(1)
    small = common.merged(config, config["rehearse"])
    cfg, params = fam.weights(small, 7)
    _, again = fam.weights(small, 7)
    assert cfg.layer_types == ("mamba", "attention", "mamba", "mamba")
    assert cfg.experts.held == (0, 4) and cfg.experts.shared_size == 64
    assert cfg.experts.score == "topk_softmax" and cfg.pos_embed == "none"
    assert (cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.embedding_multiplier, cfg.lm_head_multiplier) == (
                0.03125, 0.22, 12.0, 1 / 16)
    a = config["assumed"]
    blocks = params["blocks"]
    std = lambda x: float(np.std(np.asarray(x, np.float32)))  # noqa: E731
    assert std(params["wte"]) == pytest.approx(0.02, rel=0.05)
    assert blocks["mamba"]["ssm_in_w"].shape[0] == 3
    assert blocks["attn"]["q_w"].shape == (1, 128, 128)
    assert std(blocks["mamba"]["ssm_in_w"]) == pytest.approx(0.02, rel=0.05)
    assert std(blocks["mamba"]["ssm_out_w"]) == pytest.approx(
        a["branch_std"]["mamba"], rel=0.05)
    # a score q . k x attention_multiplier spreads by score_std
    qk = np.sqrt(a["score_std"] / (0.03125 * np.sqrt(32) * 128))
    assert std(blocks["attn"]["q_w"]) == pytest.approx(qk, rel=0.05)
    assert std(blocks["attn"]["kv_w"][:, 0]) == pytest.approx(qk, rel=0.05)
    assert std(blocks["attn"]["kv_w"][:, 1]) == pytest.approx(0.02, rel=0.05)
    assert std(blocks["attn"]["proj_w"]) == pytest.approx(
        a["branch_std"]["attention"], rel=0.05)
    assert std(blocks["moe"]["router_w"]) == pytest.approx(
        a["router_std"], rel=0.05)
    assert isinstance(blocks["moe"]["gate_w"], tuple) \
        and len(blocks["moe"]["gate_w"]) == 4
    assert blocks["moe"]["gate_w"][0].shape == (4, 128, 32)
    assert std(blocks["moe"]["down_w"][1]) == pytest.approx(
        a["branch_std"]["routed"], rel=0.1)
    assert std(blocks["moe"]["shared_down_w"]) == pytest.approx(
        a["branch_std"]["shared"], rel=0.05)
    assert "router_b" not in blocks["moe"]
    assert float(np.asarray(blocks["ln1_g"], np.float32).min()) == 1.0
    x, y = blocks["moe"]["up_w"][:2]
    assert np.abs(np.asarray(x, np.float32)
                  - np.asarray(y, np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(params["wte"], np.float32),
                                  np.asarray(again["wte"], np.float32))


Sample = collections.namedtuple("Sample",
                                "t queue_depth slot_occupancy turn held")


def test_roofline_readers_hold_this_familys_costs(config, monkeypatch):
    """The two existing readers on this family's costs: under 100 at a
    plausible device time, and nothing (no raise) where the program has no
    such scope or counters (the parent), or the run no peaks."""
    s = fam.sizes(config)
    joined = [{"rid": i, "t_due": 0.0, "t_first": 0.5, "t_retire": 9.0,
               "tokens": 86, "prompt_len": 1000, "out_len": 86}
              for i in range(40)]
    samples = [Sample(1.0 + 0.1 * i, 0, 40, i, 40) for i in range(30)]
    run = {"family": fam, "sizes": s, "joined": joined, "samples": samples,
           "stats_window": (0.0, 10.0),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    monkeypatch.setattr(moe_roofline.scope_time, "read", lambda r, a: 12.0)
    monkeypatch.setattr(moe_roofline, "program_counts",
                        lambda: (35.0, 0.5))
    want = 100.0 * (fam.moe_step_cost(s, 40, 35.0, 200.0)["bytes"]
                    / 819e9) * 1e3 / 12.0
    assert moe_roofline.read(run, {}) == pytest.approx(want)
    assert 0 < want < 100
    want = 100.0 * (fam.ssm_step_cost(s, 40)["bytes"] / 819e9) * 1e3 / 12.0
    assert ssm_roofline.read(run, {}) == pytest.approx(want)
    assert 0 < want < 100
    for reader in (moe_roofline, ssm_roofline):
        assert reader.read(dict(run, family=gpt_fam), {}) is None
        assert reader.read(dict(run, peaks=None), {}) is None
    monkeypatch.setattr(moe_roofline, "program_counts", lambda: None)
    assert moe_roofline.read(run, {}) is None
    monkeypatch.setattr(moe_roofline.scope_time, "read", lambda r, a: None)
    assert ssm_roofline.read(run, {}) is None


def test_the_two_new_metrics_are_data_on_the_scope_reader():
    for name, kernel in (("prefill_run_dev_ms", None),
                         ("prefill_moe_dev_ms", "^moe$")):
        spec = common.load_json("layer_metrics", name + ".json")
        assert spec["reader"] == "scope_time"
        # ONE bucket: a median over whatever buckets a slice held follows
        # the arrivals, not the layer
        assert spec["args"]["step_scope"] == "^serving\\.paged_prefill_1024$"
        assert spec["args"].get("kernel") == kernel
        entry = next(m for m in common.manifest()["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tpot_p50_ms"


def test_the_fixed_schedule_puts_the_metrics_bucket_in_the_traced_slice():
    """The two prefill metrics read the 1,024 bucket's runs: the mix's one
    schedule has to admit such prompts well inside the traced slice, at
    the driver's ``run_seconds``, or a traced run's line lacks them."""
    from benchmarks.generators import open_loop
    from benchmarks.traffic_gen import warmup_buckets

    mix = common.cell(CELL)["traffic"]
    seconds = float(common.manifest()["run_seconds"])
    src = open_loop.Source(mix, 1, {"T": 4096, "V_published": 512},
                           seconds, False)
    end = float(mix["ramp_s"]) + seconds
    lo, hi = end - float(mix["trace_slice_s"]) + 0.1, end - 0.5
    inside = [t for t, (p, _) in src.rel
              if lo <= t <= hi and warmup_buckets([p]) == [1024]]
    assert len(inside) >= 2, inside


@pytest.mark.parametrize("trace_flag", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace_flag):
    listed = common.cell(CELL)["per_layer" if trace_flag else "end_to_end"]
    line, out = rehearse(ROOT, CELL, trace_flag)
    got = check_line(line, [m["name"] for m in listed])
    want = ({m["name"] for m in listed if m["source"].startswith("program_")}
            if trace_flag else {m["name"] for m in listed})
    assert got >= want, (sorted(want - got), out[-1500:])
    assert "[margins]" in out
