"""The linear-attention hybrid's configuration of the benchmark
(Olmo-Hybrid-7B): its cell, files, driver and metrics found by name; each
``ROW`` against its entry; the configuration's file against the catalog's
published values, its ``reduced`` / ``published`` / ``deployment`` against
each other and against the program's own defaults; ``opcount_olmohybrid``'s
numbers by hand, at the cell's size and at the small cut; the driver's
``Session`` at the small cut (CPU) correct, and not correct under each
control. Holds only what this cell owns, and that nothing the benchmark had
lost a cell.
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

from benchmark import check_seq, opcount_olmohybrid as opcount, run  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CELL = "fused-olmohybrid-recall-32x2048"
CONFIG = "olmo-hybrid-7b-recall-fused-a2c"
NEW_METRICS = ("linattn_train_mfu", "linattn_time_share", "full_attn_time_share",
               "delta_rule_roofline", "linattn_decode_read_roofline")
ACCEPTED_CELLS = ("fused-pong-256x20", "fused-pong-4096x20",
                  "fused-pong-4chip-1024x20", "fused-lfm2moe-recall-128x256",
                  "fused-phi4flash-recall-32x1024", "fused-keyevl2-recall-16x4096")
#: what each list of the accepted benchmark held before this cell
STARTUP_METRICS = ("setup_until_first_trace_s", "setup_trace_lower_s",
                   "setup_compile_load_s", "setup_cache_misses", "step_first_call_s")
SHARED_METRICS = ("first_dispatch_s", "update_device_ms", "rollout_time_share",
                  "env_time_share", "learner_fwd_time_share",
                  "learner_bwd_time_share", "optimizer_time_share",
                  "unscoped_time_share", "dispatch_host_ms", "interstep_gap_ms")
HEAD_CELLS = ("fused-lfm2moe-recall-128x256", "fused-phi4flash-recall-32x1024",
              "fused-keyevl2-recall-16x4096")
#: allenai/Olmo-Hybrid-7B config.json as the catalog has it, without the six
#: keys cut
PUBLISHED = {
    "model_type": "olmo_hybrid", "hidden_size": 3840, "intermediate_size": 11008,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention",
                    "full_attention"] * 8,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
CUT = {"num_hidden_layers": (4, 32), "num_attention_heads": (10, 30),
       "num_key_value_heads": (10, 30), "linear_num_key_heads": (10, 30),
       "linear_num_value_heads": (10, 30), "vocab_size": (12544, 100352)}
SEED = 2**31 + 77
#: the small cut's limits, set as the cell's are: between what the program
#: reads here on the CPU at this seed and what the controls read (sound /
#: fp8_weights / state_bf16: loss gap 0.0035 / 0.033 / 0.0016; first-gradient
#: gap 0.065 / 0.50 / 0.35; parameter-change gap 0.037 / 0.20 / 0.37; action
#: flips 0.026 / 0.18 / 0.031; logit gap 0.075 / 0.76 / 0.068). A state kept
#: in bfloat16 rounds what every product downstream rounds again, so over 24
#: positions the forward's numbers read as sound; the gates' gradients (sums
#: over every position of what the state held) do not. On the next seed the
#: sound run reads 0.0021 / 0.121 / 0.045 / 0.018 / 0.098.
TINY_LIMITS = {"loss_gap": 0.01, "first_grad_norm_gap": 0.2,
               "param_delta_norm_gap": 0.1, "state_mismatch_share": 0.0,
               "action_flip_share": 0.08}
TINY_LIMITS_SEQ = {"logit_gap": 0.25}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


def _entry(bench, group, name):
    found = [e for e in bench.doc[group] if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


# -- what the cell owns ---------------------------------------------------------
def test_the_cell_its_files_and_its_driver_are_found_by_name(bench, config):
    cell = bench.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "a2c-recall-32x2048"
    assert set(cell["limits_seq"]) == set(check_seq.NUMBERS)
    assert set(cell["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
        "state_mismatch_share", "action_flip_share"}
    assert cell["limits"]["state_mismatch_share"] == 0.0
    assert set(cell["limits_why"]) >= set(cell["limits"]) | set(cell["limits_seq"])
    assert cell["follow_updates"] == 1 and cell["decode_check_envs"] == 4
    assert config["driver"] == "fused_olmohybrid"
    driver = bench.driver(config["driver"])
    assert hasattr(driver, "setup") and set(driver.CONTROLS) == {
        "fp8_weights", "state_bf16"}
    argv = config["argv"] + cell["argv"]
    for flag, value in (("--model", "olmo-hybrid"),
                        ("--env", "jax:recall:12544:512:2048"),
                        ("--rollout_len", "2048"), ("--batch_size", "65536"),
                        ("--grad_chunk_samples", "4096"), ("--steps_per_dispatch", "1")):
        assert argv[argv.index(flag) + 1] == value
    assert "--model_cut" not in argv  # the default cut is the cell's
    for path in config["reference"].split(", "):
        assert os.path.isfile(os.path.join(ROOT, path))
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "calibrate_olmohybrid.py"))
    for entry in (_entry(bench, "configs", CONFIG), _entry(bench, "workloads", CELL)):
        assert 1 <= len(entry["why"]) <= 200
    why = _entry(bench, "workloads", CELL)["why"]
    assert "a third of the heads" in why and "host" in why  # what the issue asks it to say


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    with open(os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py")) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert "distributed_ba3c_tpu" not in code
    assert 'jax.default_matmul_precision("highest")' in code
    # the recurrence one position at a time: no chunk, no triangular solve
    assert "triangular" not in code and "chunk" not in code.replace("block", "")
    assert "jax.lax.scan(\n        position" in code


def test_a_traced_run_holds_whole_updates(bench):
    assert 6 <= bench.cell(CELL)["trace_seconds"] <= bench.doc["run_seconds"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_and_its_row_agrees(bench, name):
    entry = _entry(bench, "per_layer", name)
    assert entry["workloads"][0] == CELL  # first in its own list
    assert entry["moves"] == "env_steps_per_s_per_chip"
    module = bench.layer_metric(name)  # raises where ROW and entry differ
    assert callable(module.read)
    assert module.ROW == {k: v for k, v in entry.items() if k != "workloads"}
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    if "roofline" in name or "mfu" in name:
        assert entry["better"] == "higher"
    layers = {m["layer"] for m in bench.doc["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert entry["layer"] in layers  # a layer the benchmark already names


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

        def module_ms(self, *_):
            return None

        def window_s(self):
            return 1.0

    for cfg in (bench.config("phi4-mini-flash-recall-fused-a2c"), bench.config(CONFIG)):
        ctx = {"trace": NoTrace(), "counters": {"work_per_update": 65536},
               "cell": {"name": "no-such-capture", "chips": 1}, "config": cfg,
               "peaks": bench.peaks("TPU v5e")}
        assert module.read(ctx) is None


def test_the_train_mfu_by_hand(bench, config, capsys):
    """One update of 65,536 env-steps in 10 s: the count over the peak."""

    class Trace:
        def module_ms(self, name):
            assert name == "jit_multi_step"
            return 10_000.0

    ctx = {"trace": Trace(), "config": config, "cell": {"chips": 1},
           "counters": {"work_per_update": 65536, "rollout_len": 2048},
           "peaks": bench.peaks("TPU v5e")}
    got = bench.layer_metric("linattn_train_mfu").read(ctx)
    want = 100 * 65536 * opcount.flops_per_env_step(config, 2048) / (10 * 197e12)
    assert got == pytest.approx(want) and 17 < got < 18.5
    assert "MFLOP an env-step" in capsys.readouterr().out


# -- no list that was there lost a cell ---------------------------------------------
@pytest.mark.parametrize("name", STARTUP_METRICS + SHARED_METRICS)
def test_a_shared_metric_lists_this_cell_and_lost_none(bench, name):
    entry = _entry(bench, "per_layer", name)
    assert set(ACCEPTED_CELLS) <= set(entry["workloads"])
    assert entry["workloads"].count(CELL) == 1
    assert entry["workloads"].index(CELL) > max(
        entry["workloads"].index(c) for c in ACCEPTED_CELLS)  # appended


def test_the_heads_metric_lists_this_cell_and_lost_none(bench):
    entry = _entry(bench, "per_layer", "head_loss_time_share")
    assert entry["workloads"][:3] == list(HEAD_CELLS)
    assert entry["workloads"].count(CELL) == 1


@pytest.mark.parametrize("name", [
    "train_mfu", "conv_time_share", "pool_bwd_time_share", "conv_roofline",
    "allreduce_exposed_ms", "lm_train_mfu", "moe_time_share",
    "moe_experts_roofline", "decode_weight_read_roofline", "mixer_time_share",
    "moe_load_max_over_mean", "seq_train_mfu", "ssm_time_share",
    "ssm_scan_roofline", "attn_time_share", "decode_read_roofline",
    "carry_copy_time_share", "sparse_train_mfu", "sparse_attn_time_share",
    "indexer_time_share", "sparse_decode_read_roofline", "select_kept_share"])
def test_another_policys_metric_is_left_alone(bench, name):
    assert CELL not in _entry(bench, "per_layer", name)["workloads"]


def test_the_benchmark_has_what_this_cell_needs_and_lost_nothing(bench):
    """Only what this cell owns and what was there before it: a later cell,
    configuration or metric is no concern of this file."""
    doc = bench.doc
    assert {"ba3cnet-pong-fused-a2c", "lfm2-8b-a1b-recall-fused-a2c",
            "phi4-mini-flash-recall-fused-a2c", "keye-vl2-30b-a3b-recall-fused-a2c",
            CONFIG} <= {c["name"] for c in doc["configs"]}
    cells = [w["name"] for w in doc["workloads"]]
    assert cells[:6] == list(ACCEPTED_CELLS) and cells.index(CELL) == 6
    assert [c["name"] for c in doc["configs"]].index(CONFIG) == 4
    names = [m["name"] for m in doc["per_layer"]]
    first = min(names.index(n) for n in NEW_METRICS)
    assert names[first:first + 5] == list(NEW_METRICS)  # together, in order
    assert first == names.index("step_first_call_s") + 1  # after what was there
    # one cell in seven may take four chips, and that one is taken
    assert sum(w["chips"] == 4 for w in doc["workloads"][:7]) == 1


# -- the configuration's file -----------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configurations_file_holds_the_published_value(config, key):
    assert config[key] == PUBLISHED[key] and type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_written_down(bench, config):
    entry = _entry(bench, "configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert doc["reduced"] == entry["reduced"] and set(doc["published"]) == set(CUT)
    for key, (held, published) in CUT.items():
        assert (doc[key], doc["published"][key]) == (held, published), key
    assert doc["source"].startswith(entry["source"])
    # a third of every mixer's heads, an eighth of the vocabulary, one period
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert doc[key] * 3 == doc["published"][key]
    assert doc["vocab_size"] * 8 == doc["published"]["vocab_size"]
    assert doc["held"]["layers"] == [0, 1, 2, 3]
    assert [doc["layer_types"][i] for i in doc["held"]["layers"]] == [
        "linear_attention"] * 3 + ["full_attention"]
    assert len(doc["held"]["layers"]) == doc["num_hidden_layers"]
    deployment = doc["deployment"]
    assert deployment["chips_sharing_each_layer_by_heads"] == 3
    assert deployment["chips_sharing_the_vocabulary"] == 8
    assert deployment["bytes_a_parameter"] == 18
    assert deployment["parameters_held"] == opcount.params_held(doc) == 712_039_037
    assert f"{deployment['parameters_held'] * 18 / 1e9:.2f} GB" in deployment["state_bytes"]
    assert doc["head_dim"] == 128 == doc["hidden_size"] // doc["published"][
        "num_attention_heads"]
    for key in ("assumed", "departures", "precision", "algorithm", "control"):
        assert doc[key]
    for item in ("head_dim", "block_norms", "qk_norm", "linear_attention",
                 "conv_taps", "positions_encoding", "untied_head", "weights"):
        assert item in doc["assumed"], item
    for item in ("value_head", "vocabulary", "positions", "head_share"):
        assert item in doc["departures"], item
    # no width is among the keys cut
    assert not [k for k in doc["reduced"] if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]


def test_the_programs_defaults_are_the_configurations(config):
    from benchmark.reference import olmo_hybrid as reference
    from distributed_ba3c_tpu.models.olmo_hybrid import OlmoHybrid

    model = OlmoHybrid()
    for field in ("hidden_size", "intermediate_size", "num_attention_heads",
                  "head_dim", "linear_key_head_dim", "linear_value_head_dim",
                  "linear_conv_kernel_dim", "rms_norm_eps"):
        assert getattr(model, field) == config[field], field
    assert model.linear_num_heads == config["linear_num_key_heads"]
    assert list(model.layer_types) == config["layer_types"]
    assert list(model.layer_ids) == config["held"]["layers"]
    assert model.num_actions == config["vocab_size"]
    spec = reference.spec_of(config)
    assert tuple(k for _, k in spec["layers"]) == model.layer_kinds
    ours = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: reference.init_params(k, spec),
                            jax.random.PRNGKey(0))
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(ours))
    assert held == opcount.params_held(config)


# -- the counts by hand -------------------------------------------------------------
def test_hand_counted_parameters_and_operations(config):
    rows = {l["layer"]: l for l in opcount.layers(config)}
    d, f = 3840, 11008
    # linear: W_qkv 3840 x 10 (96 + 96 + 192) = 14.75 M, W_z and W_o 7.37 M
    # each, W_a and W_b 38,400 each; the conv's 4 taps, A_log, dt_bias, a gain
    assert rows[0]["mixer_macs"] == d * 3840 + 2 * d * 1920 + 2 * d * 10 == 29_568_000
    assert rows[0]["mixer_params"] == 29_568_000 + 4 * 3840 + 20 + 192 == 29_583_572
    assert rows[1] == dict(rows[0], layer=1) and rows[2] == dict(rows[0], layer=2)
    # full: four products of 3840 x 1280; the q and the k norm's gains
    assert rows[3]["mixer_macs"] == 4 * d * 1280 == 19_660_800
    assert rows[3]["mixer_params"] == 19_660_800 + 2 * 1280
    assert all(r["ffn_params"] == 3 * d * f == 126_812_160 for r in rows.values())
    assert all(r["norm_params"] == 2 * d for r in rows.values())
    assert opcount.params_held(config) == (
        2 * 12544 * d + 3 * 29_583_572 + 19_663_360 + 4 * (126_812_160 + 2 * d)
        + d + d + 1) == 712_039_037
    macs = opcount.forward_macs(config, 2048)
    assert macs["linear"] == 3 * 29_568_000 and macs["attention"] == 19_660_800
    assert macs["ffn"] == 4 * 126_812_160 and macs["head"] == 12544 * d
    # the recurrence: three products of a 96 x 192 state a head a position
    assert opcount.delta_rule_macs(config) == 3 * 10 * 96 * 192 == 552_960
    assert macs["delta"] == 3 * 552_960
    # ten heads of 128 against 1,024.5 keys and as many values, the mean context
    assert macs["context"] == 2 * 1280 * 1024.5
    assert opcount.flops_per_env_step(config, 2048) == 8 * sum(macs.values())
    assert 5.3e9 < opcount.flops_per_env_step(config, 2048) < 5.4e9
    # the feed-forwards are whole beside a third of the heads: three quarters
    assert 0.75 < macs["ffn"] / sum(macs.values()) < 0.77
    assert opcount.decode_weight_bytes(config) == 2 * 712_039_037  # 1.42 GB
    # a position of the delta rule's least work, three layers of ten heads
    assert opcount.delta_rule_flops(config, 1.0) == 2 * 3 * 552_960 * 3
    a_token = (2 * 96 + 2 * 192 + 2) * 2 + (2 * 96 + 192 + 2)
    assert opcount.delta_rule_bytes(config, 1.0) == 3 * 10 * (
        a_token + 2 * 96 * 192 / 64) * 4
    # by bytes, not by operations: 0.31 us against 0.05 us a position
    assert (opcount.delta_rule_bytes(config, 1.0) / 819e9
            > 5 * opcount.delta_rule_flops(config, 1.0) / 197e12)


def test_the_counts_at_the_small_cut_are_the_models_own_leaves(config):
    from distributed_ba3c_tpu.models.olmo_hybrid import CUTS, OlmoHybrid

    small = dict(config, hidden_size=64, intermediate_size=96, vocab_size=32,
                 num_attention_heads=2, head_dim=16, linear_num_key_heads=2,
                 linear_key_head_dim=8, linear_value_head_dim=16)
    model = OlmoHybrid(**CUTS["tiny"], num_actions=32, max_positions=24)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert opcount.params_held(small) == size(shapes)
    rows = {l["layer"]: l for l in opcount.layers(small)}
    mixer = ("wqkv", "wz", "wa", "wb", "conv_w", "A_log", "dt_bias", "o_norm",
             "wo", "wq", "wk", "wv", "q_norm", "k_norm")
    for i in range(4):
        leaves = shapes[f"layer_{i}"]
        assert rows[i]["mixer_params"] == size(
            {k: v for k, v in leaves.items() if k in mixer})
        assert rows[i]["ffn_params"] == size(
            [leaves[k] for k in ("w_gate", "w_up", "w_down")])
        assert rows[i]["norm_params"] == size([leaves["mix_norm"], leaves["ffn_norm"]])
        matrices = size({k: v for k, v in leaves.items()
                         if k in mixer and len(v.shape) == 2 and k != "conv_w"})
        assert rows[i]["mixer_macs"] == matrices
    assert opcount.delta_rule_macs(small) == 3 * 2 * 8 * 16


def test_the_decode_steps_carry_bytes_by_hand(config):
    from distributed_ba3c_tpu.models.olmo_hybrid import OlmoHybrid

    carry = OlmoHybrid().carry_bytes()  # what the step's metric reports
    states, tails, kv, small = carry
    assert states == 3 * 10 * 96 * 192 * 4 == 2_211_840  # 0.74 MB a layer an env
    got = opcount.decode_carry_bytes(config, carry, envs=32, episode=2048)
    want = 32 * (2 * states + 2 * tails      # read and written whole
                 + kv * (1024.5 / 2048 + 1 / 2048)  # up to the position; a row
                 + 2 * small)
    assert got == pytest.approx(want)
    assert 0.31e9 < got < 0.33e9  # 0.32 GB beside 1.42 GB of weights
    # the states' traffic is constant over the episode: 141 MB a step
    assert 32 * 2 * states == 141_557_760


# -- the driver's Session at the small cut ---------------------------------------
@pytest.fixture(scope="module")
def tiny(bench, config):
    small = dict(hidden_size=64, intermediate_size=96, vocab_size=32,
                 num_attention_heads=2, num_key_value_heads=2, head_dim=16,
                 linear_num_key_heads=2, linear_num_value_heads=2,
                 linear_key_head_dim=8, linear_value_head_dim=16)
    argv = list(config["argv"])
    for flag, value in (("--env", "jax:recall:32:4:24"), ("--rollout_len", "24"),
                        ("--grad_chunk_samples", "48")):
        argv[argv.index(flag) + 1] = value
    tiny_config = dict(config, **small, argv=argv + ["--model_cut", "tiny"])
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
    assert "largest |S| of a delta-rule state" in out and "mean gate alpha" in out


@pytest.mark.timeout(900)
@pytest.mark.parametrize("control,must_fail", [
    ("fp8_weights", {"loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
                     "action_flip_share", "logit_gap"}),
    ("state_bf16", {"first_grad_norm_gap", "param_delta_norm_gap"}),
])
def test_a_control_at_the_small_cut_is_not_correct(bench, tiny, control, must_fail):
    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    session = driver.setup(cell, tiny_config, jax.devices()[:1], SEED, control=control)
    session.release()
    rows = session.check()
    failed = {r["number"] for r in rows if not r["ok"]}
    assert failed and must_fail <= failed, rows
    assert "state_mismatch_share" not in failed  # the envs are told the same actions
