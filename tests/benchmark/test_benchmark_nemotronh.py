"""The Mamba-2 and sparse-expert hybrid's configuration of the benchmark
(Nemotron-3-Nano-30B-A3B): its cell, files, driver and metrics found by
name; each ``ROW`` against its entry; the configuration's file against the
catalog's published values, its ``reduced`` / ``published`` / ``deployment``
against each other and against the program's own defaults;
``opcount_nemotronh``'s numbers by hand, at the cell's size and at the small
cut; the driver's ``Session`` at the small cut (CPU) correct, and not correct
under each control. Holds only what this cell owns, and that nothing the
benchmark had lost a cell.
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

from benchmark import check_seq, opcount_nemotronh as opcount, run  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CELL = "fused-nemotron3nano-recall-32x2048"
CONFIG = "nemotron3-nano-30b-a3b-recall-fused-a2c"
NEW_METRICS = ("mamba2_train_mfu", "mamba2_time_share", "ssd_roofline",
               "relu2_experts_roofline", "mamba2_decode_read_roofline",
               "nemotronh_attn_time_share")
ACCEPTED_CELLS = ("fused-pong-256x20", "fused-pong-4096x20",
                  "fused-pong-4chip-1024x20", "fused-lfm2moe-recall-128x256",
                  "fused-phi4flash-recall-32x1024", "fused-keyevl2-recall-16x4096",
                  "fused-olmohybrid-recall-32x2048")
#: what each list of the accepted benchmark held before this cell
STARTUP_METRICS = ("setup_until_first_trace_s", "setup_trace_lower_s",
                   "setup_compile_load_s", "setup_cache_misses", "step_first_call_s")
SHARED_METRICS = ("first_dispatch_s", "update_device_ms", "rollout_time_share",
                  "env_time_share", "learner_fwd_time_share",
                  "learner_bwd_time_share", "optimizer_time_share",
                  "unscoped_time_share", "dispatch_host_ms", "interstep_gap_ms")
HEAD_CELLS = ("fused-lfm2moe-recall-128x256", "fused-phi4flash-recall-32x1024",
              "fused-keyevl2-recall-16x4096", "fused-olmohybrid-recall-32x2048")
MOE_CELLS = ("fused-lfm2moe-recall-128x256", "fused-keyevl2-recall-16x4096")
#: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json as the catalog has
#: it, without the three keys cut
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True,
}
CUT = {"num_hidden_layers": (9, 52), "n_routed_experts": (8, 128),
       "vocab_size": (16384, 131072)}
SEED = 2**31 + 79
#: the small cut's limits, set as the cell's are: between what the program
#: reads here on the CPU and what ``fp8_weights`` reads (sound / fp8_weights
#: on this seed and the next: loss gap 0.0002, 0.00002 / 0.0027, 0.0038;
#: first-gradient gap 0.0089, 0.0042 / 0.102, 0.051; parameter-change gap
#: 0.0045, 0.0034 / 0.030, 0.068; action flips 0.0069, 0.0087 / 0.066, 0.049;
#: logit gap 0.016, 0.018 / 0.45, 0.22; route flips 0.017, 0.018 / 0.21, 0.18).
#: ``state_bf16`` reads as sound here on every number (0.0002 / 0.011 / 0.0045
#: / 0.0052 / 0.016 / 0.020): over 24 positions a state rounded to one part in
#: 256 has nothing to drift through; the cell's 2,048 positions are where it
#: is told apart (PERF.md section 4)
TINY_LIMITS = {"loss_gap": 0.001, "first_grad_norm_gap": 0.025,
               "param_delta_norm_gap": 0.015, "state_mismatch_share": 0.0,
               "action_flip_share": 0.025}
TINY_LIMITS_SEQ = {"logit_gap": 0.07, "route_flip_share": 0.06}


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
    assert set(cell["limits_seq"]) == set(check_seq.NUMBERS) | {"route_flip_share"}
    assert set(cell["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
        "state_mismatch_share", "action_flip_share"}
    assert cell["limits"]["state_mismatch_share"] == 0.0
    assert set(cell["limits_why"]) >= set(cell["limits"]) | set(cell["limits_seq"])
    assert cell["follow_updates"] == 1 and cell["decode_check_envs"] == 4
    assert config["driver"] == "fused_nemotronh"
    driver = bench.driver(config["driver"])
    assert hasattr(driver, "setup") and set(driver.CONTROLS) == {
        "fp8_weights", "state_bf16"}
    assert set(driver.FAULTS) == {"half_batch", "no_reset"}
    argv = config["argv"] + cell["argv"]
    for flag, value in (("--model", "nemotron-h"),
                        ("--env", "jax:recall:16384:512:2048"),
                        ("--rollout_len", "2048"), ("--batch_size", "65536"),
                        ("--steps_per_dispatch", "1")):
        assert argv[argv.index(flag) + 1] == value
    # the issue's learner chunk: 16 chunks of 2 envs, whole episodes
    assert int(argv[argv.index("--grad_chunk_samples") + 1]) == 4096
    assert "--model_cut" not in argv  # the default cut is the cell's
    for path in config["reference"].split(", "):
        assert os.path.isfile(os.path.join(ROOT, path))
    for name in ("calibrate_nemotronh.py", "opcount_nemotronh.py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmark", name))
    for entry in (_entry(bench, "configs", CONFIG), _entry(bench, "workloads", CELL)):
        assert 1 <= len(entry["why"]) <= 200
    why = _entry(bench, "workloads", CELL)["why"]
    # what the issue asks it to say
    assert "16x their share" in why and "5 %" in why and "host" in why


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    with open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py")) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert "distributed_ba3c_tpu" not in code
    assert 'jax.default_matmul_precision("highest")' in code
    # the recurrence one position at a time, every expert a plain product
    # over every token: no chunked form, no grouped product, no sort
    for word in ("chunk", "ragged", "argsort", "cumsum"):
        assert word not in code, word
    assert "jax.lax.scan(position, H, at)" in code
    assert "allowed = at[None, :] <= at[:, None]" in code  # the T x T mask


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

    for cfg in (bench.config("olmo-hybrid-7b-recall-fused-a2c"), bench.config(CONFIG)):
        ctx = {"trace": NoTrace(), "counters": {"work_per_update": 65536},
               "cell": {"name": "no-such-capture", "chips": 1}, "config": cfg,
               "peaks": bench.peaks("TPU v5e")}
        assert module.read(ctx) is None


def test_the_train_mfu_by_hand(bench, config, capsys):
    """One update of 65,536 env-steps in 10 s: the count over the peak, at
    the visits the router made (here an even router's 0.375 a token a block)."""

    class Trace:
        def module_ms(self, name):
            assert name == "jit_multi_step"
            return 10_000.0

    even = [[65536 * 6 // 128] * 8] * 4
    ctx = {"trace": Trace(), "config": config, "cell": {"chips": 1},
           "counters": {"work_per_update": 65536, "rollout_len": 2048,
                        "moe_tokens_per_expert": even},
           "peaks": bench.peaks("TPU v5e")}
    got = bench.layer_metric("mamba2_train_mfu").read(ctx)
    want = 100 * 65536 * opcount.flops_per_env_step(config, 2048) / (10 * 197e12)
    assert got == pytest.approx(want) and 8.5 < got < 9.2
    assert "MFLOP an env-step (0.3750 visits" in capsys.readouterr().out
    # a router that sends nothing here: the routed experts' 4.5 % less
    ctx["counters"]["moe_tokens_per_expert"] = [[0] * 8] * 4
    assert bench.layer_metric("mamba2_train_mfu").read(ctx) == pytest.approx(
        want * (1 - 0.0449), rel=1e-3)


# -- no list that was there lost a cell ---------------------------------------------
@pytest.mark.parametrize("name", STARTUP_METRICS + SHARED_METRICS)
def test_a_shared_metric_lists_this_cell_and_lost_none(bench, name):
    entry = _entry(bench, "per_layer", name)
    assert set(ACCEPTED_CELLS) <= set(entry["workloads"])
    assert entry["workloads"].count(CELL) == 1
    assert entry["workloads"].index(CELL) > max(
        entry["workloads"].index(c) for c in ACCEPTED_CELLS)  # appended


@pytest.mark.parametrize("name,before", [
    ("head_loss_time_share", HEAD_CELLS), ("moe_time_share", MOE_CELLS),
    ("moe_load_max_over_mean", MOE_CELLS)])
def test_the_heads_and_the_experts_metrics_list_this_cell_and_lost_none(
        bench, name, before):
    entry = _entry(bench, "per_layer", name)
    assert entry["workloads"][:len(before)] == list(before)
    assert entry["workloads"].count(CELL) == 1


@pytest.mark.parametrize("name", [
    "train_mfu", "conv_time_share", "pool_bwd_time_share", "conv_roofline",
    "allreduce_exposed_ms", "lm_train_mfu", "moe_experts_roofline",
    "decode_weight_read_roofline", "mixer_time_share", "seq_train_mfu",
    "ssm_time_share", "ssm_scan_roofline", "attn_time_share",
    "decode_read_roofline", "carry_copy_time_share", "sparse_train_mfu",
    "sparse_attn_time_share", "indexer_time_share", "sparse_decode_read_roofline",
    "select_kept_share", "linattn_train_mfu", "linattn_time_share",
    "full_attn_time_share", "delta_rule_roofline", "linattn_decode_read_roofline"])
def test_another_policys_metric_is_left_alone(bench, name):
    """(``full_attn_time_share`` too: its reader reads the linear-attention
    hybrid's configuration and returns nothing here; this cell's attention
    block is printed on ``mamba2_time_share``'s line.)"""
    assert CELL not in _entry(bench, "per_layer", name)["workloads"]


def test_the_benchmark_has_what_this_cell_needs_and_lost_nothing(bench):
    """Only what this cell owns and what was there before it: a later cell,
    configuration or metric is no concern of this file."""
    doc = bench.doc
    assert {"ba3cnet-pong-fused-a2c", "lfm2-8b-a1b-recall-fused-a2c",
            "phi4-mini-flash-recall-fused-a2c", "keye-vl2-30b-a3b-recall-fused-a2c",
            "olmo-hybrid-7b-recall-fused-a2c", CONFIG} <= {
        c["name"] for c in doc["configs"]}
    cells = [w["name"] for w in doc["workloads"]]
    assert cells[:7] == list(ACCEPTED_CELLS) and cells.index(CELL) == 7
    assert [c["name"] for c in doc["configs"]].index(CONFIG) == 5
    names = [m["name"] for m in doc["per_layer"]]
    first = min(names.index(n) for n in NEW_METRICS)
    assert names[first:first + len(NEW_METRICS)] == list(NEW_METRICS)  # together, in order
    assert first == names.index("linattn_decode_read_roofline") + 1  # after what was there
    # one cell in eight may take four chips, and that one is taken
    assert sum(w["chips"] == 4 for w in doc["workloads"][:8]) == 1


# -- the configuration's file -----------------------------------------------------
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configurations_file_holds_the_published_value(config, key):
    assert config[key] == PUBLISHED[key] and type(config[key]) is type(PUBLISHED[key])


def test_the_catalogs_row_is_the_published_table():
    """Where the catalog is beside the guides: every key of its ``config``
    is in the file, and differs only where ``reduced`` says so."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if "Nemotron-3-Nano-30B-A3B" in line]
    row = rows[0]
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        doc = json.load(f)
    assert doc["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key in CUT:
            assert (doc[key], value) == CUT[key], key
        else:
            assert doc[key] == value, key
    assert {k: v for k, v in row["config"].items() if k not in CUT} == PUBLISHED


def test_the_cut_is_written_down(bench, config):
    entry = _entry(bench, "configs", CONFIG)
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == ("https://huggingface.co/nvidia/"
                               "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert doc["reduced"] == entry["reduced"] and set(doc["published"]) == set(CUT)
    for key, (held, published) in CUT.items():
        assert (doc[key], doc["published"][key]) == (held, published), key
    assert doc["source"].startswith(entry["source"])
    # a sixteenth of the routed experts, an eighth of the vocabulary, one period
    assert doc["n_routed_experts"] * 16 == doc["published"]["n_routed_experts"]
    assert doc["vocab_size"] * 8 == doc["published"]["vocab_size"]
    assert doc["held"]["layers"] == list(range(9)) and doc["held"]["expert_offset"] == 0
    pattern = doc["hybrid_override_pattern"]
    assert len(pattern) == doc["published"]["num_hidden_layers"]
    assert "".join(pattern[i] for i in doc["held"]["layers"]) == "MEMEM*EME"
    assert len(doc["held"]["layers"]) == doc["num_hidden_layers"]
    # within the floors: 8 routed experts a layer, an eighth of the ids, a period
    assert doc["n_routed_experts"] >= 8 and "-" not in pattern
    deployment = doc["deployment"]
    assert deployment["chips_sharing_each_layer_expert_parallel"] == 16
    assert deployment["chips_sharing_the_vocabulary"] == 8
    assert deployment["bytes_a_parameter"] == 18
    assert deployment["parameters_held"] == opcount.params_held(doc) == 666_966_145
    assert f"{deployment['parameters_held'] * 18 / 1e9:.2f} GB" in deployment["state_bytes"]
    for key in ("assumed", "departures", "precision", "algorithm", "control"):
        assert doc[key]
    for item in ("block", "expand", "mamba2", "conv_taps", "experts", "attention",
                 "weights"):
        assert item in doc["assumed"], item
    assert "NO rotary embedding" in doc["assumed"]["attention"]
    assert "arXiv:2504.03624" in doc["assumed"]["attention"]
    for item in ("value_head", "vocabulary", "positions", "expert_share",
                 "norm_topk_eps"):
        assert item in doc["departures"], item
    assert "mamba2" in doc["precision"]
    # no width is among the keys cut
    assert not [k for k in doc["reduced"] if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]


def test_the_programs_defaults_are_the_configurations(config):
    from benchmark.reference import nemotron_h as reference
    from distributed_ba3c_tpu.models.nemotron_h import NemotronH

    model = NemotronH()
    for field in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                  "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                  "time_step_min", "time_step_max", "time_step_floor",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "num_experts_per_tok", "moe_intermediate_size",
                  "moe_shared_expert_intermediate_size", "norm_topk_prob",
                  "routed_scaling_factor", "layer_norm_epsilon",
                  "hybrid_override_pattern"):
        assert getattr(model, field) == config[field], field
    assert model.experts_held == config["n_routed_experts"]
    assert model.n_routed_experts == config["published"]["n_routed_experts"]
    assert model.expert_offset == config["held"]["expert_offset"]
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
    d = 2688
    # Mamba-2: W_in 2688 x (4096 + 6144 + 64) = 27.70 M, W_out 11.01 M; the
    # conv's 4 taps and bias over 6,144 channels, A_log, D, dt_bias, the gated
    # norm's gain, the block's norm
    assert rows[0]["macs"] == d * 10304 + 4096 * d == 38_707_200
    assert rows[0]["params"] == 38_707_200 + 5 * 6144 + 3 * 64 + 4096 + d == 38_744_896
    for i in (2, 4, 7):
        assert rows[i] == dict(rows[0], layer=i)
    # attention: W_q and W_o 11.01 M each, W_k and W_v 0.69 M each
    assert rows[5]["macs"] == 2 * d * 4096 + 2 * d * 256 == 23_396_352
    assert rows[5]["params"] == 23_396_352 + d
    # experts: the router's 128 outputs and bias, the shared expert's two
    # matrices of 3,712, eight routed experts of two matrices of 1,856
    assert rows[1]["macs"] == d * 128 + 2 * d * 3712 == 20_299_776
    assert rows[1]["visit_macs"] == 2 * d * 1856 == 9_977_856
    assert rows[1]["params"] == 20_299_776 + 128 + 8 * 9_977_856 + d == 100_125_440
    for i in (3, 6, 8):
        assert rows[i] == dict(rows[1], layer=i)
    assert opcount.params_held(config) == (
        2 * 16384 * d + 4 * 38_744_896 + 23_399_040 + 4 * 100_125_440
        + d + d + 1) == 666_966_145
    macs = opcount.forward_macs(config, 2048)
    assert macs["mamba"] == 4 * 38_707_200 and macs["attention"] == 23_396_352
    assert macs["shared"] == 4 * 20_299_776 and macs["head"] == 16384 * d
    # the recurrence: three products of a 64 x 128 state a head a position
    assert opcount.ssd_macs(config) == 3 * 64 * 64 * 128 == 1_572_864
    assert macs["ssd"] == 4 * 1_572_864
    # 32 heads of 128 against 1,024.5 keys and as many values, the mean context
    assert macs["context"] == 2 * 4096 * 1024.5
    # 6 of 128 chosen, 8 of 128 held: 0.375 visits a token a block
    assert opcount.even_visits(config) == 0.375
    assert macs["experts"] == 0.375 * 4 * 9_977_856
    assert opcount.forward_macs(config, 2048, 0.5)["experts"] == 2 * 9_977_856
    assert opcount.flops_per_env_step(config, 2048) == 8 * sum(macs.values())
    assert 2.6e9 < opcount.flops_per_env_step(config, 2048) < 2.7e9
    # what the cell's ``why`` says: the held routed experts are 5 % of the
    # products beside mixers, shared experts and a head at 16 times their share
    total = sum(macs.values())
    assert 0.04 < macs["experts"] / total < 0.05
    assert 0.46 < macs["mamba"] / total < 0.47 and 0.24 < macs["shared"] / total < 0.25
    assert 0.13 < macs["head"] / total < 0.14
    assert 0.09 < (macs["attention"] + macs["context"]) / total < 0.10
    assert opcount.decode_weight_bytes(config) == 2 * 666_966_145  # 1.33 GB
    # a position of the recurrence's least work, four blocks
    assert opcount.ssd_flops(config, 1.0) == 2 * 3 * 1_572_864 * 4
    read = 4096 + 64 + 2 * 8 * 128
    a_token = 2 * (read + 4096) + read
    assert opcount.ssd_bytes(config, 1.0) == 4 * (a_token + 2 * 64 * 64 * 128 / 128) * 4
    # by bytes, not by operations: 0.68 us against 0.19 us a position
    assert (opcount.ssd_bytes(config, 1.0) / 819e9
            > 3 * opcount.ssd_flops(config, 1.0) / 197e12)
    # a visit of a routed expert: forward, dW and dx of two matrices
    assert opcount.routed_expert_flops(config, 1.0) == 2 * 3 * 9_977_856
    weights = 4 * 8 * 9_977_856 * 2  # every held matrix, bfloat16
    assert opcount.routed_expert_bytes(config, 0.0, 1.0) == weights
    assert opcount.routed_expert_bytes(config, 1.0, 0.0) == 2 * (d + 1856) * 2


def test_the_counts_at_the_small_cut_are_the_models_own_leaves(config):
    from distributed_ba3c_tpu.models.nemotron_h import CUTS, NemotronH

    small = _small(config)
    model = NemotronH(**CUTS["tiny"], num_actions=32, max_positions=24)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert opcount.params_held(small) == size(shapes)
    rows = {l["layer"]: l for l in opcount.layers(small)}
    assert [rows[i]["kind"] for i in (0, 1, 4, 5, 6)] == list("MEM*E")
    for i, row in rows.items():
        leaves = shapes[f"layer_{i}"]
        assert row["params"] == size(leaves), i
        every_token = size({k: v for k, v in leaves.items()
                            if len(v.shape) == 2 and k != "conv_w"})
        assert row["macs"] == every_token, i
        if row["kind"] == "E":
            assert row["visit_macs"] * 2 == size([leaves["w1"], leaves["w2"]])
    assert opcount.ssd_macs(small) == 3 * 4 * 8 * 16
    assert opcount.even_visits(small) == 3 * 2 / 32


def test_the_decode_steps_carry_bytes_by_hand(config):
    from distributed_ba3c_tpu.models.nemotron_h import NemotronH

    carry = NemotronH().carry_bytes()  # what the step's metric reports
    states, tails, kv, small = carry
    assert states == 4 * 64 * 64 * 128 * 4 == 8_388_608  # 2.1 MB a layer an env
    got = opcount.decode_carry_bytes(config, carry, envs=32, episode=2048)
    want = 32 * (2 * states + 2 * tails      # read and written whole
                 + kv * (1024.5 / 2048 + 1 / 2048)  # up to the position; a row
                 + 2 * small)
    assert got == pytest.approx(want)
    # the states' traffic is constant over the episode: 537 MB a step read
    # and written, beside 1.33 GB of weights: 29 % of the step's bytes
    assert 32 * 2 * states == 536_870_912
    share = 32 * 2 * states / (got + opcount.decode_weight_bytes(config))
    assert 0.27 < share < 0.30
    assert 0.58e9 < got < 0.60e9


# -- the driver's Session at the small cut ---------------------------------------
def _small(config):
    return dict(
        config, hidden_size=64, mamba_num_heads=4, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=2,
        num_experts_per_tok=3, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, vocab_size=32,
        published=dict(config["published"], n_routed_experts=32),
        held=dict(config["held"], layers=[0, 1, 4, 5, 6]))


@pytest.fixture(scope="module")
def tiny(bench, config):
    argv = list(config["argv"])
    for flag, value in (("--env", "jax:recall:32:4:24"), ("--rollout_len", "24"),
                        ("--grad_chunk_samples", "288")):
        argv[argv.index(flag) + 1] = value
    tiny_config = dict(_small(config), argv=argv + ["--model_cut", "tiny"])
    # 24 envs in chunks of 12: 288 tokens a chunk, the experts' sorted rows
    cell = dict(bench.cell(CELL), argv=["--batch_size", "576"], follow_updates=1,
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
    assert "largest |H| of a Mamba-2 state" in out and "mean step size dt" in out
    assert "tokens routed to the held experts a block" in out


@pytest.mark.timeout(900)
def test_fp8_weights_at_the_small_cut_is_not_correct(bench, tiny):
    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    session = driver.setup(
        cell, tiny_config, jax.devices()[:1], SEED, control="fp8_weights")
    session.release()
    failed = {r["number"] for r in session.check() if not r["ok"]}
    assert {"loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
            "action_flip_share", "logit_gap", "route_flip_share"} <= failed
    assert "state_mismatch_share" not in failed  # the envs are told the same actions


def test_state_bf16_keeps_the_recurrences_state_in_bfloat16(bench, tiny):
    """The control is another program (its step's carry and the learner's
    chunk boundaries in bfloat16); what it fails is for the cell's 2,048
    positions to say (24 positions tell it from nothing)."""
    import jax.numpy as jnp

    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    session = driver.setup(
        cell, tiny_config, jax.devices()[:1], SEED, control="state_bf16")
    session.release()
    assert session.model.state_dtype == jnp.bfloat16
    state = session.model.init_carry(1).mamba[0][0]
    assert state.dtype == jnp.bfloat16 and state.shape == (1, 4, 8, 16)


@pytest.mark.timeout(900)
def test_the_planted_faults_are_told_by_the_numbers_they_are_planted_for(bench, tiny):
    """What the numbers no precision moves are held against, through the
    calibrator as the chip runs it: a gradient that leaves half the
    transitions out (the two norms, and nothing the forward decides), a
    decode that opens on another episode's state (``logit_gap``, and nothing
    else). At this cut the clipped gradient's leaves move by a fifth (the
    cell's 0.86 is the chip's to read: PERF.md section 4); the stale state
    reads over the CELL'S limit here too."""
    from benchmark import calibrate_nemotronh as calibrate

    cell, tiny_config = tiny
    got = calibrate.readings(
        bench, cell, tiny_config, jax.devices()[:1], SEED,
        ("half_batch", "no_reset"))
    value = lambda side, number: next(  # noqa: E731
        r["value"] for r in got[side] if r["number"] == number)
    failed = lambda side: {r["number"] for r in got[side] if not r["ok"]}  # noqa: E731
    assert not failed("program"), got["program"]
    assert failed("half_batch") == {"first_grad_norm_gap", "param_delta_norm_gap"}
    for number in ("first_grad_norm_gap", "param_delta_norm_gap"):
        assert value("half_batch", number) > 10 * value("program", number)
        assert value("half_batch", number) > 0.1
    assert failed("no_reset") == {"logit_gap"}
    assert value("no_reset", "logit_gap") > bench.cell(CELL)["limits_seq"]["logit_gap"]
    for side in ("half_batch", "no_reset"):  # what the forward decides is the sound run's
        for number in ("loss_gap", "action_flip_share", "route_flip_share"):
            assert value(side, number) == value("program", number)


def test_the_driver_follows_one_update(bench, tiny):
    cell, tiny_config = tiny
    driver = bench.driver(tiny_config["driver"])
    with pytest.raises(ValueError, match="follows one update"):
        driver.setup(dict(cell, follow_updates=2), tiny_config,
                     jax.devices()[:1], SEED)
    with pytest.raises(ValueError, match="control"):
        driver.setup(cell, tiny_config, jax.devices()[:1], SEED, control="window_256")
