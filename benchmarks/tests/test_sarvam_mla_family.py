"""The ``sarvam_mla`` family on the CPU: its sizes and counts to the digit
(100% may not move with the file), the cost functions at the cell's sizes,
the expert layer's required work never above what the every-row form
computes, the file against the published row but for the three cuts, and
the reader of ``decode_moe_row_use``.  Run from the root of the repo:

    python -m pytest benchmarks/tests -q

The cell itself is rehearsed by ``test_benchmark.py`` (every workload of
the manifest, traced and untraced)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.families import sarvam_mla as fam  # noqa: E402
from benchmarks.readers import expert_rows  # noqa: E402

# the published row's numbers (config.json of sarvamai/sarvam-105b) that
# the cell's file keeps, and the three it cuts
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_size": 4096,
    "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "v_head_dim": 128, "default_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"}}
CUT = {"num_hidden_layers": (32, 5), "num_experts": (128, 32),
       "vocab_size": (262144, 65536)}


@pytest.fixture(scope="module")
def config():
    return common.cell("sarvam105b-serve-reason")["config"]


def test_sizes_and_counts_to_the_digit(config):
    """Wq 4096x12288 + Wkva 4096x576 + the latent gain 512 + Wkvb
    512x16384 + Wo 8192x4096; the dense FFN 3 x 4096x16384; the router
    4096x128 and its bias; the shared expert and a routed one 3 x
    4096x2048; two norms a layer."""
    s = fam.sizes(config)
    assert (s["L"], s["K"], s["E"], s["E_published"], s["k"]) == (
        5, 1, 32, 128, 8)
    assert (s["T"], s["V"], s["V_published"]) == (4096, 65536, 65536)
    assert fam.mla_params(s) == 94_634_496
    assert fam.dense_params(s) == 201_326_592
    assert fam.router_params(s) == 524_416
    assert fam.shared_params(s) == fam.expert_params(s) == 25_165_824
    assert fam.layer_params(s, True) == 295_969_280
    assert fam.layer_params(s, False) == 120_332_928
    assert fam.total_params(s) == 4_535_401_472
    assert fam.row_values(s) == 576
    # 32 of 128 held, 8 a token: at 90 rows every held expert is hit
    assert fam.experts_hit_even(s, 90) == pytest.approx(
        32 * (1 - (120 / 128) ** 90))
    assert fam.experts_hit_even(s, 90) > 31.8


def test_cost_functions_at_the_cells_sizes(config):
    s = fam.sizes(config)
    kv_b = 512 * 64 * 256
    assert fam.mla_attn_cost(s, 150000.0, 90) == {
        "bytes": 5 * 576 * 2 * 150000.0 + 5 * kv_b * 2,
        "flops": 5 * (2.0 * 64 * 1088 * 150000.0 + 2.0 * kv_b * 90),
        "row_bytes": 5 * 576 * 2 * 150000.0}
    assert fam.moe_step_cost(s, 90, 31.0, 45.0) == {
        "bytes": 4 * (524_416 + 25_165_824) * 2 + 4 * 31.0 * 25_165_824 * 2,
        "flops": 4 * (2.0 * (4096 * 128 + 25_165_824) * 90
                      + 2.0 * 25_165_824 * 45.0),
        "expert_bytes": 4 * 31.0 * 25_165_824 * 2}
    cost = fam.decode_step_cost(s, 90, 150000.0)
    dense = (5 * (94_634_496 + 8192) + 201_326_592 + 4096 + 65536 * 4096
             + 90 * 4096)
    assert cost["weight_bytes"] == 2 * dense
    assert cost["kv_bytes"] == 5 * 576 * 2 * 150000.0
    moe = fam.moe_step_cost(s, 90, fam.experts_hit_even(s, 90),
                            90 * 8 * 32 / 128)
    assert cost["bytes"] == pytest.approx(2 * dense + moe["bytes"]
                                          + cost["kv_bytes"])
    # the held experts are three quarters of the step's weight bytes
    share = moe["expert_bytes"] / (cost["bytes"] - cost["kv_bytes"])
    assert 0.70 < share < 0.76


@pytest.mark.parametrize("rows", [1, 16, 90, 256])
def test_required_expert_work_is_never_above_the_every_row_form(config,
                                                                rows):
    """What the expert layers must do (the hit experts read once, the
    selected pairs multiplied) against what the every-row form does (all
    held experts read, every row through every one of them)."""
    s = fam.sizes(config)
    need = fam.moe_step_cost(s, rows, fam.experts_hit_even(s, rows),
                             rows * 8 * 32 / 128)
    every = fam.moe_step_cost(s, rows, s["E"], rows * s["E"])
    assert need["bytes"] <= every["bytes"]
    assert need["flops"] <= every["flops"]
    if rows == 256:
        # at a full batch the every-row form computes 32 times the pairs
        # the routing asks for (8 of 128 selections, a quarter held here)
        assert every["flops"] > 10 * need["flops"]


def test_the_file_is_the_published_row_but_for_the_three_cuts(config):
    for k, v in PUBLISHED.items():
        assert config[k] == v, k
    assert config["model_type"] == "sarvam_mla"
    assert config["tie_word_embeddings"] is False
    assert config["use_qk_norm"] is True
    for k, (published, here) in CUT.items():
        assert config[k] == here
    assert config["num_experts_published"] == 128
    assert config["held"] == [0, 32]
    entry = next(c for c in common.manifest()["configs"]
                 if c["name"] == "sarvam-105b-serve")
    assert sorted(entry["reduced"]) == sorted(CUT)


def test_decode_moe_row_use_reads_the_programs_counters(monkeypatch):
    from paddle_tpu import telemetry

    telemetry.reset()
    assert expert_rows.read({}, {}) is None
    telemetry.count("moe.expert_rows", 8192)
    telemetry.count("moe.pairs_held", 180)
    assert expert_rows.read({}, {}) == pytest.approx(100 * 180 / 8192)
    telemetry.reset()


def test_the_fixed_schedule_puts_a_1024_admission_in_the_traced_slice():
    """``prefill_run_dev_ms`` and ``prefill_moe_dev_ms`` read the 1,024
    bucket's runs: the mix's one schedule has to admit such prompts well
    inside the traced slice, at the benchmark's ``run_seconds``, or a traced
    run's line lacks them."""
    from benchmarks.generators import open_loop
    from benchmarks.traffic_gen import warmup_buckets

    mix = common.cell("sarvam105b-serve-reason")["traffic"]
    seconds = float(common.manifest()["run_seconds"])
    src = open_loop.Source(mix, 1, {"T": 4096, "V_published": 512},
                           seconds, False)
    end = float(mix["ramp_s"]) + seconds
    lo, hi = end - float(mix["trace_slice_s"]) + 0.1, end - 0.5
    inside = [t for t, (p, _) in src.rel
              if lo <= t <= hi and warmup_buckets([p]) == [1024]]
    assert len(inside) >= 2, inside
