"""The token-sequence configuration of the benchmark: its cell, files,
driver and metrics found by name; ``opcount_lm``'s hand-counted numbers; the
configuration's file against the program's own defaults; the driver's
``Session`` at the small cut (CPU) correct, and not correct when one held
expert's contribution is dropped and when the forward runs a precision below.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_lm, opcount_lm, run  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CELL = "fused-lfm2moe-recall-128x256"
CONFIG = "lfm2-8b-a1b-recall-fused-a2c"
NEW_METRICS = ("lm_train_mfu", "moe_time_share", "moe_experts_roofline",
               "decode_weight_read_roofline", "mixer_time_share",
               "head_loss_time_share", "moe_load_max_over_mean")
SHARED_METRICS = ("first_dispatch_s", "update_device_ms", "rollout_time_share",
                  "env_time_share", "learner_fwd_time_share",
                  "learner_bwd_time_share", "optimizer_time_share",
                  "unscoped_time_share", "dispatch_host_ms", "interstep_gap_ms")
#: the published widths (LiquidAI/LFM2-8B-A1B config.json), by key
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 1792,
    "num_attention_heads": 32, "num_key_value_heads": 8, "conv_L_cache": 3,
    "num_experts_per_tok": 4, "num_dense_layers": 2, "norm_eps": 1e-5,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "norm_topk_prob": True,
    "use_expert_bias": True, "conv_bias": False, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe",
}
SEED = 2**31 + 77
#: the small cut's limits, set as the cell's are: between what the program
#: reads here on the CPU at this seed and what the two controls read (sound /
#: fp8_weights / drop_expert: loss gap 0.0066 / 0.049 / 0.0042, which the
#: dropped expert passes; first-gradient gap 0.0052 / 0.099 / 0.968;
#: parameter-change gap 0.0037 / 0.055 / 0.919; action flips 0.0117 / 0.098 /
#: 0.051; logit gap 0.0150 / 0.229 / 0.422; route flips 0 / 0.109 / 0.047)
TINY_LIMITS = {"loss_gap": 0.02, "first_grad_norm_gap": 0.03,
               "param_delta_norm_gap": 0.02, "state_mismatch_share": 0.0,
               "action_flip_share": 0.04}
TINY_LIMITS_LM = {"logit_gap": 0.06, "route_flip_share": 0.03}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


def test_the_cell_its_files_and_its_driver_are_found_by_name(bench, config):
    cell = bench.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "a2c-recall-128x256"
    assert set(cell["limits_lm"]) == set(check_lm.NUMBERS)
    assert config["driver"] == "fused_lm"
    driver = bench.driver(config["driver"])
    assert hasattr(driver, "setup") and set(driver.CONTROLS) == {
        "fp8_weights", "drop_expert"}
    argv = config["argv"] + cell["argv"]
    for flag, value in (("--model", "lfm2-moe"), ("--env", "jax:recall"),
                        ("--rollout_len", "256"), ("--batch_size", "32768"),
                        ("--grad_chunk_samples", "4096"), ("--steps_per_dispatch", "1")):
        assert argv[argv.index(flag) + 1] == value
    for path in config["reference"].split(", "):
        assert os.path.isfile(os.path.join(ROOT, path))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_alone_and_its_row_agrees(bench, name):
    # first in its list; which later cells are appended after it is not held
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"][0] == CELL
    assert entry[0]["workloads"].count(CELL) == 1
    assert entry[0]["moves"] == "env_steps_per_s_per_chip"
    module = bench.layer_metric(name)  # raises where ROW and entry differ
    assert callable(module.read)
    if "roofline" in name or "mfu" in name:
        assert module.ROW["unit"] == "%" and module.ROW["better"] == "higher"


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_a_shared_metric_has_the_new_cell_appended(bench, name):
    # once, after the three conv cells; later cells are appended after it
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name][0]
    assert entry["workloads"].count(CELL) == 1
    assert entry["workloads"][:4] == [
        "fused-pong-256x20", "fused-pong-4096x20", "fused-pong-4chip-1024x20", CELL]


@pytest.mark.parametrize("name", ["train_mfu", "conv_time_share",
                                  "pool_bwd_time_share", "conv_roofline",
                                  "allreduce_exposed_ms"])
def test_a_conv_policys_metric_is_left_alone(bench, name):
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name][0]
    assert CELL not in entry["workloads"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configurations_file_holds_the_published_value(config, key):
    assert config[key] == PUBLISHED[key]


def test_the_cut_is_written_down(bench, config):
    entry = [c for c in bench.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"].endswith("LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert doc["reduced"] == entry["reduced"]
    assert doc["published"] == {"num_hidden_layers": 24, "num_experts": 32,
                                "vocab_size": 65536}
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        5, 8, 16384)
    assert len(doc["layer_types"]) == 24  # the published pattern, whole
    assert doc["held"]["layers"] == [0, 2, 3, 4, 5]
    assert [doc["layer_types"][i] for i in doc["held"]["layers"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert doc["deployment"]["chips_sharing_each_layer"] == 4
    for key in ("assumed", "departures", "precision", "algorithm"):
        assert doc[key]
    # no width is among the keys cut
    assert not [k for k in doc["reduced"] if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]


def test_the_programs_defaults_are_the_configurations(config):
    from distributed_ba3c_tpu.models.lfm2_moe import ATTN, CONV, DENSE, EXPERTS, LFM2MoE

    model = LFM2MoE()
    for field in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                  "norm_eps", "num_experts_per_tok", "norm_topk_prob"):
        assert getattr(model, field) == config[field], field
    assert model.rope_theta == config["rope_theta"]
    assert model.routed_scaling_factor == config["routed_scaling_factor"]
    assert model.num_experts == config["published"]["num_experts"]
    assert model.experts_held == config["num_experts"]
    assert model.num_actions == config["vocab_size"]
    assert model.head_dim == config["hidden_size"] // config["num_attention_heads"]
    assert list(model.layer_ids) == config["held"]["layers"]
    kinds = {"conv": CONV, "full_attention": ATTN}
    for i, (op, ffn) in zip(model.layer_ids, model.layer_kinds, strict=True):
        assert op == kinds[config["layer_types"][i]]
        assert ffn == (DENSE if i < config["num_dense_layers"] else EXPERTS)


def test_hand_counted_parameters_and_operations(config):
    from distributed_ba3c_tpu.models.lfm2_moe import LFM2MoE

    rows = {l["layer"]: l for l in opcount_lm.layers(config)}
    d = 2048
    # layer 0: conv op 12.58 M in + 4.19 M out + 6 k taps; dense FFN 44.04 M
    assert rows[0]["op_macs"] == d * 6144 + d * d == 16_777_216
    assert rows[0]["ffn_params"] == 3 * d * 7168 == 44_040_192
    # attention: q 4.19 M + k, v 1.05 M each + o 4.19 M
    assert rows[2]["op_macs"] == 2 * d * d + 2 * d * 512 == 10_485_760
    # an expert 11.01 M, 8 held; a router 65.5 k over all 32
    assert rows[3]["ffn_macs"] == 3 * d * 1792 == 11_010_048
    assert rows[3]["ffn_params"] == 8 * 11_010_048
    assert rows[3]["router_macs"] == d * 32
    assert opcount_lm.params_held(config) == 507_822_337  # 507.8 M
    assert opcount_lm.params_held(config) == config["deployment"]["parameters_held"]
    macs = opcount_lm.forward_macs(config)
    assert macs["operators"] == 16_777_216 + 10_485_760 + 3 * 16_777_216
    assert macs["ffn_dense"] == macs["experts"] == 44_040_192  # one visit a layer
    assert macs["head"] == 16384 * d == 33_554_432
    assert sum(macs.values()) == 199_491_584  # 199.5 M a token forward
    assert opcount_lm.flops_per_env_step(config) == 8 * 199_491_584
    assert opcount_lm.decode_weight_bytes(config) == 2 * 507_822_337  # 1.02 GB
    assert opcount_lm.expert_layers(config) == 4
    # the program holds exactly what is counted
    shapes = jax.eval_shape(LFM2MoE().init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == 507_822_337


def test_the_two_numbers_of_check_lm_by_hand():
    ref = {"logits": np.zeros((1, 3, 4), np.float32), "routes": np.array(
        [[[[0, 1], [2, 3], [4, 5]]], [[[0, 1], [2, 3], [4, 5]]]])}
    ref["logits"][0, 0, 0] = 2.0
    ours = {"logits": ref["logits"].copy(), "routes": ref["routes"][..., ::-1].copy()}
    rows = check_lm.compare(ours, ref, {"logit_gap": 0.0, "route_flip_share": 0.0})
    assert [r["value"] for r in rows] == [0.0, 0.0] and all(r["ok"] for r in rows)
    ours["routes"][1, 0, 2] = [4, 6]     # one of 6 (token, layer) sets differs
    ours["logits"][0, 1, 3] = 0.5        # a quarter of the largest logit
    rows = check_lm.compare(ours, ref, {"logit_gap": 0.2, "route_flip_share": 0.2})
    assert rows[0]["value"] == 0.25 and not rows[0]["ok"]
    assert abs(rows[1]["value"] - 1 / 6) < 1e-9 and rows[1]["ok"]
    ours["logits"][0, 0, 0] = np.nan
    assert check_lm.compare(ours, ref, {"logit_gap": 9, "route_flip_share": 9})[0][
        "value"] == float("inf")


# -- the driver's Session at the small cut ---------------------------------------
@pytest.fixture(scope="module")
def tiny(bench, config):
    small = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                 num_attention_heads=4, num_key_value_heads=2, num_experts=2,
                 num_experts_per_tok=2, vocab_size=256)
    argv = list(config["argv"])
    for flag, value in (("--env", "jax:recall:256:4:16"), ("--rollout_len", "16"),
                        ("--grad_chunk_samples", "64")):
        argv[argv.index(flag) + 1] = value
    tiny_config = dict(
        config, **small, argv=argv + ["--model_cut", "tiny"],
        published=dict(config["published"], num_experts=8),
        held=dict(config["held"], layers=[0, 2, 3]))
    cell = dict(bench.cell(CELL), argv=["--batch_size", "128"], follow_updates=2,
                limits=TINY_LIMITS, limits_lm=TINY_LIMITS_LM, trace_seconds=1)
    return cell, tiny_config


@pytest.mark.timeout(900)
def test_a_run_at_the_small_cut_is_correct(bench, tiny, capsys):
    cell, tiny_config = tiny
    result = run.measure(bench, cell, tiny_config, jax.devices()[:1],
                         {"platform": "cpu", "kind": "cpu", "count": 1},
                         SEED, 1.0, False)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"env_steps_per_s_per_chip", "setup_s"}
    for number in list(TINY_LIMITS) + list(TINY_LIMITS_LM):
        assert f"compare {number}:" in out
    assert "assignments land here" in out


@pytest.mark.timeout(900)
@pytest.mark.parametrize("control,must_fail", [
    ("drop_expert", {"first_grad_norm_gap", "param_delta_norm_gap", "logit_gap",
                     "action_flip_share", "route_flip_share"}),
    ("fp8_weights", {"loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
                     "logit_gap", "route_flip_share", "action_flip_share"}),
])
def test_a_control_at_the_small_cut_is_not_correct(bench, tiny, control, must_fail):
    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    session = driver.setup(cell, tiny_config, jax.devices()[:1], SEED, control=control)
    session.release()
    rows = session.check()
    failed = {r["number"] for r in rows if not r["ok"]}
    assert must_fail <= failed, rows
    assert "state_mismatch_share" not in failed  # the envs are told the same actions
