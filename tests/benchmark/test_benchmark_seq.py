"""The dense token-sequence configuration of the benchmark (Phi-4-mini-flash-
reasoning): its cell, files, driver and metrics found by name; each ``ROW``
against its entry; ``opcount_phi4flash``'s hand-counted numbers; the
configuration's file against the catalog's published values and against the
program's own defaults; ``check_seq``'s numbers by hand; the driver's
``Session`` at the small cut (CPU) correct, and not correct under each
control (a precision below in the program, a halved window).
"""

import json
import math
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_seq, opcount_phi4flash as opcount, run  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CELL = "fused-phi4flash-recall-32x1024"
CONFIG = "phi4-mini-flash-recall-fused-a2c"
NEW_METRICS = ("seq_train_mfu", "ssm_time_share", "ssm_scan_roofline",
               "attn_time_share", "decode_read_roofline", "carry_copy_time_share")
ACCEPTED_CELLS = ("fused-pong-256x20", "fused-pong-4096x20",
                  "fused-pong-4chip-1024x20", "fused-lfm2moe-recall-128x256")
SHARED_METRICS = ("first_dispatch_s", "update_device_ms", "rollout_time_share",
                  "env_time_share", "learner_fwd_time_share",
                  "learner_bwd_time_share", "optimizer_time_share",
                  "unscoped_time_share", "dispatch_host_ms", "interstep_gap_ms",
                  "head_loss_time_share")
#: microsoft/Phi-4-mini-flash-reasoning config.json as the catalog has it,
#: without the two keys cut
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-5,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
}
SEED = 2**31 + 77
#: the small cut's limits, set as the cell's are: between what the program
#: reads here on the CPU at this seed and what the controls read (sound /
#: fp8_weights / window_256: loss gap 0.0009 / 0.046 / 0.033; first-gradient
#: gap 0.014 / 0.130 / 0.152; parameter-change gap 0.012 / 0.106 / 0.080;
#: action flips 0.013 / 0.135 / 0.237; logit gap 0.037 / 0.325 / 0.830)
TINY_LIMITS = {"loss_gap": 0.01, "first_grad_norm_gap": 0.05,
               "param_delta_norm_gap": 0.04, "state_mismatch_share": 0.0,
               "action_flip_share": 0.05}
TINY_LIMITS_SEQ = {"logit_gap": 0.1}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


def test_the_cell_its_files_and_its_driver_are_found_by_name(bench, config):
    cell = bench.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "a2c-recall-32x1024"
    assert set(cell["limits_seq"]) == set(check_seq.NUMBERS)
    assert set(cell["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
        "state_mismatch_share", "action_flip_share"}
    assert cell["limits"]["state_mismatch_share"] == 0.0
    assert cell["follow_updates"] == 1 and cell["decode_check_envs"] == 4
    assert config["driver"] == "fused_seq"
    driver = bench.driver(config["driver"])
    assert hasattr(driver, "setup") and set(driver.CONTROLS) == {
        "fp8_weights", "window_256"}
    argv = config["argv"] + cell["argv"]
    for flag, value in (("--model", "phi4-flash"),
                        ("--env", "jax:recall:25008:256:1024"),
                        ("--rollout_len", "1024"), ("--batch_size", "32768"),
                        ("--grad_chunk_samples", "4096"), ("--steps_per_dispatch", "1")):
        assert argv[argv.index(flag) + 1] == value
    assert "--model_cut" not in argv  # the default cut is the cell's
    for path in config["reference"].split(", "):
        assert os.path.isfile(os.path.join(ROOT, path))


def test_a_traced_run_holds_whole_updates(bench):
    """The window's dispatcher ticks the tracer only between dispatches,
    three in flight: with a span of 6 s or more of the 10 it starts by the
    second update's dispatch and is closed after the last completion."""
    assert 6 <= bench.cell(CELL)["trace_seconds"] <= bench.doc["run_seconds"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_and_its_row_agrees(bench, name):
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and CELL in entry[0]["workloads"]
    assert entry[0]["moves"] == "env_steps_per_s_per_chip"
    module = bench.layer_metric(name)  # raises where ROW and entry differ
    assert callable(module.read)
    assert set(entry[0]) == {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}
    if "roofline" in name or "mfu" in name:
        assert module.ROW["unit"] == "%" and module.ROW["better"] == "higher"
    layers = {m["layer"] for m in bench.doc["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert entry[0]["layer"] in layers  # a layer the benchmark already names


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing_to_read(bench, name):
    """On a program without the scopes or the counters (this PR's parent),
    on a cell of another configuration, with no capture: None, no raise."""
    module = bench.layer_metric(name)

    class NoTrace:
        ops = {}

        def env_steps(self, *_):
            return 0.0

        def module_runs(self, *_):
            return 0.0

        def window_s(self):
            return 1.0

    other = bench.config("lfm2-8b-a1b-recall-fused-a2c")
    for cfg in (other, bench.config(CONFIG)):
        ctx = {"trace": NoTrace(), "counters": {"work_per_update": 32768},
               "cell": {"name": "no-such-capture", "chips": 1}, "config": cfg,
               "peaks": bench.peaks("TPU v5e")}
        assert module.read(ctx) is None


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_a_shared_metric_lost_no_cell(bench, name):
    """Whether a shared metric lists this cell is for the reader's value on
    the chip to decide (PR 40 appended it to all eleven) and is not held
    here; that none lost a cell it had is."""
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name][0]
    had = set(ACCEPTED_CELLS) if name != "head_loss_time_share" else {
        "fused-lfm2moe-recall-128x256"}
    assert had <= set(entry["workloads"])
    assert entry["workloads"].count(CELL) <= 1


@pytest.mark.parametrize("name", [
    "train_mfu", "conv_time_share", "pool_bwd_time_share", "conv_roofline",
    "allreduce_exposed_ms", "lm_train_mfu", "moe_time_share",
    "moe_experts_roofline", "decode_weight_read_roofline", "mixer_time_share",
    "moe_load_max_over_mean"])
def test_another_policys_metric_is_left_alone(bench, name):
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name][0]
    assert CELL not in entry["workloads"]


def test_the_benchmark_has_what_this_cell_needs_and_lost_nothing(bench):
    """Only what this cell owns and what was there before it: a later cell,
    configuration or metric is no concern of this file."""
    doc = bench.doc
    assert {"ba3cnet-pong-fused-a2c", "lfm2-8b-a1b-recall-fused-a2c", CONFIG} <= {
        c["name"] for c in doc["configs"]}
    assert set(ACCEPTED_CELLS) | {CELL} <= {w["name"] for w in doc["workloads"]}
    assert set(NEW_METRICS) <= {m["name"] for m in doc["per_layer"]}
    assert bench.cell(CELL)["chips"] == 1
    for entry in doc["configs"] + doc["workloads"]:
        if entry["name"] in (CONFIG, CELL):
            assert 1 <= len(entry["why"]) <= 200


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configurations_file_holds_the_published_value(config, key):
    assert config[key] == PUBLISHED[key] and type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_written_down(bench, config):
    entry = [c for c in bench.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert "9:8:1:7:7" in entry["why"]  # the ratio of kinds against the published
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert doc["reduced"] == entry["reduced"]
    assert doc["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    assert (doc["num_hidden_layers"], doc["vocab_size"]) == (6, 25008)
    assert doc["vocab_size"] * 8 == doc["published"]["vocab_size"]
    assert doc["held"]["layers"] == [14, 15, 16, 17, 18, 19]
    assert doc["deployment"]["chips_sharing_each_layer"] == 8
    assert doc["state_space"] == {"d_inner": 5120, "d_state": 16, "d_conv": 4,
                                  "dt_rank": 160}
    for key in ("assumed", "departures", "precision", "algorithm"):
        assert doc[key]
    for item in ("mamba_sizes", "differential_attention", "qkv_bias", "sub_norm",
                 "cross_attention", "memory_unit", "layer_kinds", "weights"):
        assert item in doc["assumed"], item
    # no width is among the keys cut
    assert not [k for k in doc["reduced"] if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]


def test_the_programs_defaults_are_the_configurations(config):
    from distributed_ba3c_tpu.models.phi4_flash import Phi4Flash, kind_of

    model = Phi4Flash()
    for field in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "sliding_window", "layer_norm_eps"):
        assert getattr(model, field) == config[field], field
    for field, value in config["state_space"].items():
        assert getattr(model, field) == value, field
    assert model.num_hidden_layers == config["published"]["num_hidden_layers"]
    assert model.num_actions == config["vocab_size"]
    assert model.head_dim == config["hidden_size"] // config["num_attention_heads"]
    assert list(model.layer_ids) == config["held"]["layers"]
    assert len(model.layer_ids) == config["num_hidden_layers"]
    from benchmark.reference import phi4_flash as reference

    spec = reference.spec_of(config)
    assert tuple(k for _, k in spec["layers"]) == model.layer_kinds
    assert spec["memory_layer"] == model.layer_ids[model.memory_layer] == 16
    assert all(kind_of(i, 32) == k for i, k in spec["layers"])


def test_hand_counted_parameters_and_operations(config):
    rows = {l["layer"]: l for l in opcount.layers(config)}
    d, c = 2560, 5120
    # Mamba: in 26.21 M + x 0.98 M + dt 0.82 M + out 13.11 M; and the conv's
    # 4 taps + bias, dt's bias, A_log, D
    assert rows[14]["mixer_macs"] == d * 2 * c + c * 192 + 160 * c + c * d == 41_123_840
    assert rows[14]["mixer_params"] == 41_123_840 + 4 * c + c + c + 16 * c + c == 41_241_600
    assert rows[16] == dict(rows[14], layer=16)
    # attention: qkv 13.11 M + o 6.55 M; biases, four vectors of 64, a gain of 128
    assert rows[15]["mixer_macs"] == d * 5120 + d * d == 19_660_800
    assert rows[15]["mixer_params"] == 19_660_800 + 5120 + d + 4 * 64 + 128
    assert rows[17]["mixer_params"] == rows[15]["mixer_params"]
    assert rows[18]["mixer_params"] == 2 * d * c == 26_214_400       # memory unit
    assert rows[19]["mixer_macs"] == 2 * d * d == 13_107_200          # cross: q, o
    assert all(r["ffn_params"] == 3 * d * 10240 == 78_643_200 for r in rows.values())
    assert opcount.params_held(config) == 697_096_833  # 697 M
    assert opcount.params_held(config) == config["deployment"]["parameters_held"]
    macs = opcount.forward_macs(config, 1024)
    assert macs["mamba"] == 2 * 41_123_840 and macs["attention"] == 52_428_800
    assert macs["ffn"] == 6 * 78_643_200 and macs["head"] == 25008 * d
    assert macs["scan"] == 2 * 3 * c * 16
    # mean context of 1,024 positions: 384.25 rows of a 512 window, 512.5 of all
    assert opcount.mean_context("window_attention", 1024, 512) == 384.25
    assert opcount.mean_context("full_attention", 1024, 512) == 512.5
    assert macs["context"] == 3 * d * (384.25 + 2 * 512.5)
    assert opcount.flops_per_env_step(config, 1024) == 8 * sum(macs.values())
    assert 5.6e9 < opcount.flops_per_env_step(config, 1024) < 5.7e9
    assert opcount.decode_weight_bytes(config) == 2 * 697_096_833  # 1.39 GB
    # a position of a scan: 8 c + 6 n floats forward and back, two layers
    assert opcount.scan_bytes(config, 1.0) == 2 * (8 * c + 6 * 16) * 4


def test_the_decode_steps_carry_bytes_by_hand(config):
    from distributed_ba3c_tpu.models.phi4_flash import Phi4Flash

    carry = Phi4Flash().carry_bytes()  # what the step's metric reports
    ssm, ring, shared, pos = carry
    got = opcount.decode_carry_bytes(config, carry, envs=32, episode=1024)
    want = 32 * (2 * ssm                                  # read and written
                 + ring * (384.25 / 512 + 1 / 512)        # up to the window
                 + shared * (2 * 512.5 / 1024 + 1 / 1024)  # two readers
                 + 2 * pos)
    assert got == pytest.approx(want)
    assert 0.27e9 < got < 0.29e9  # 0.28 GB beside 1.39 GB of weights


def test_the_numbers_of_check_seq_by_hand():
    assert check_seq.loss_floor(0.01, 25008) == pytest.approx(0.01 * math.log(25008))
    # relative to the reference's loss where that is large, to the floor where not
    assert check_seq.loss_gap([1.1], [1.0], 0.1) == pytest.approx(0.1)
    assert check_seq.loss_gap([0.0011], [0.001], 0.1) == pytest.approx(0.001)
    assert check_seq.loss_gap([float("nan")], [1.0], 0.1) == float("inf")
    side = {"losses": [0.0011], "first_grad": {"a/b": 1.0}, "delta": {"a/b": 2.0},
            "states": [({"t": np.zeros(2)}, np.zeros(2))],
            "decode_logits": np.zeros((1, 4, 3), np.float32)}
    ref = dict(side, losses=[0.001], action_flips=0.0, action_margin=0.0,
               decode_logits=side["decode_logits"].copy())
    ref["decode_logits"][0, 0, 0] = 2.0
    side["decode_logits"][0, 0, 0] = 2.0
    side["decode_logits"][0, 3, 1] = 0.5  # a quarter of the largest logit, last quarter
    limits = dict.fromkeys(TINY_LIMITS, 0.0)
    rows = {r["number"]: r for r in check_seq.compare(
        side, ref, limits, {"logit_gap": 0.2}, 0.1)}
    assert rows["loss_gap"]["value"] == pytest.approx(0.001) and not rows["loss_gap"]["ok"]
    assert "floor 0.1" in rows["loss_gap"]["detail"]
    assert rows["logit_gap"]["value"] == 0.25 and not rows["logit_gap"]["ok"]
    assert rows["logit_gap"]["detail"].endswith("0 0 0 0.25")
    assert "(0, 3)" in rows["logit_gap"]["detail"]
    assert [r for r in rows.values() if r["number"] not in ("loss_gap", "logit_gap")
            and not r["ok"]] == []


def test_the_leaves_beside_the_worst_by_hand():
    """The worst leaf is held (``check.py``); the printed line also has the
    median leaf and the worst that is no ``lam`` vector."""
    ref = {"layer_1/lam_q1": 1.0, "layer_1/qkv": 2.0, "layer_1/o": 4.0}
    got = {"layer_1/lam_q1": 1.5, "layer_1/qkv": 2.2, "layer_1/o": 4.0}
    gaps = check_seq.leaf_gaps(got, ref)  # over the leaf's norm or the median leaf's, 2
    assert gaps == pytest.approx(
        {"layer_1/lam_q1": 0.25, "layer_1/qkv": 0.1, "layer_1/o": 0.0})
    assert max(gaps.values()) == check_seq.check.worst_leaf_gap(got, ref)[0]
    assert check_seq.beside_the_worst(got, ref) == (
        "; median leaf 0.1; worst leaf that is no lam vector 0.1 (layer_1/qkv)")
    side = {"losses": [0.0], "first_grad": got, "delta": got,
            "states": [({"t": np.zeros(2)}, np.zeros(2))],
            "decode_logits": np.ones((1, 4, 3), np.float32)}
    rows = {r["number"]: r for r in check_seq.compare(
        side, dict(side, first_grad=ref, delta=ref, action_flips=0.0, action_margin=0.0),
        dict.fromkeys(TINY_LIMITS, 1.0), {"logit_gap": 1.0}, 0.1)}
    for number in ("first_grad_norm_gap", "param_delta_norm_gap"):
        assert rows[number]["value"] == 0.25
        assert rows[number]["detail"].startswith("layer_1/lam_q1; median leaf 0.1;")


# -- the driver's Session at the small cut ---------------------------------------
@pytest.fixture(scope="module")
def tiny(bench, config):
    small = dict(hidden_size=64, intermediate_size=96, num_attention_heads=8,
                 num_key_value_heads=4, sliding_window=8, vocab_size=64,
                 state_space=dict(d_inner=128, d_state=4, d_conv=4, dt_rank=4))
    argv = list(config["argv"])
    for flag, value in (("--env", "jax:recall:64:4:24"), ("--rollout_len", "24"),
                        ("--grad_chunk_samples", "96")):
        argv[argv.index(flag) + 1] = value
    tiny_config = dict(
        config, **small, argv=argv + ["--model_cut", "tiny"],
        published=dict(config["published"], num_hidden_layers=8),
        held=dict(config["held"], layers=[2, 3, 4, 5, 6, 7]))
    cell = dict(bench.cell(CELL), argv=["--batch_size", "192"], follow_updates=2,
                limits=TINY_LIMITS, limits_seq=TINY_LIMITS_SEQ, trace_seconds=1)
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
    for number in list(TINY_LIMITS) + list(TINY_LIMITS_SEQ):
        assert f"compare {number}:" in out
    assert "largest |s| of a state-space state" in out


@pytest.mark.timeout(900)
@pytest.mark.parametrize("control,must_fail", [
    ("fp8_weights", {"loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
                     "action_flip_share", "logit_gap"}),
    ("window_256", {"logit_gap", "first_grad_norm_gap", "action_flip_share"}),
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
    if control == "window_256":  # the first window's positions still agree
        detail = [r for r in rows if r["number"] == "logit_gap"][0]["detail"]
        first_quarter = float(detail.split("quarter of the episode ")[1].split()[0])
        assert first_quarter < TINY_LIMITS_SEQ["logit_gap"]


@pytest.mark.timeout(900)
def test_the_references_float8_control_is_not_correct(bench, tiny):
    """The reference with its matrix operands in float8 in the program's
    place, against the float32 reference playing the same actions."""
    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    session = driver.setup(cell, tiny_config, jax.devices()[:1], SEED)
    session.release()
    actions = session.program["actions"]
    sound = session.reference_readings(actions=actions)
    lowered = session.reference_readings(lower="fp8", actions=actions)
    rows = session.compare(dict(lowered, actions=actions), sound)
    failed = {r["number"] for r in rows if not r["ok"]}
    assert {"logit_gap", "first_grad_norm_gap"} <= failed, rows
