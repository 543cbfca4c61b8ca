"""The sparse-attention configuration of the benchmark (Keye-VL-2.0-30B-A3B's
language model): its cell, files, driver and metrics found by name; each
``ROW`` against its entry; ``opcount_keyevl2``'s hand-counted numbers; the
configuration's file against the catalog's published values and against the
program's own defaults; ``check_sparse``'s numbers by hand; the driver's
``Session`` at the small cut (CPU) correct, and not correct under each
control (a precision below in the program, half the top-k). Holds only what
this cell owns, and that nothing the benchmark had lost a cell.
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

from benchmark import check_sparse, opcount_keyevl2 as opcount, run  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CELL = "fused-keyevl2-recall-16x4096"
CONFIG = "keye-vl2-30b-a3b-recall-fused-a2c"
NEW_METRICS = ("sparse_train_mfu", "sparse_attn_time_share", "indexer_time_share",
               "sparse_decode_read_roofline", "select_kept_share")
ACCEPTED_CELLS = ("fused-pong-256x20", "fused-pong-4096x20",
                  "fused-pong-4chip-1024x20", "fused-lfm2moe-recall-128x256",
                  "fused-phi4flash-recall-32x1024")
#: Kwai-Keye/Keye-VL-2.0-30B-A3B config.json (the language model's settings)
#: as the catalog has it, without the three keys cut
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False,
}
SEED = 2**31 + 77
#: the small cut's limits, set as the cell's are: between what the program
#: reads here on the CPU at this seed and what the controls read (sound /
#: fp8_weights / topk_1024, here a top-k of 4 for 8: loss gap 0.00002 / 0.0028
#: / 0.00001; first-gradient gap 0.0039 / 0.082 / 0.0040; parameter-change
#: gap 0.0019 / 0.023 / 0.0034; action flips 0.0039 / 0.039 / 0.0039; logit
#: gap 0.34 / 0.63 / 0.79 (one key of 8 flipped is an eighth of what a query
#: reads: at this cut the number only catches a wrong selection); route flips
#: 0.012 / 0.100 / 0.004; selection flips 0.0052 / 0.063 / 0.334). The halved
#: top-k passes every number the reference computes WITH the program's
#: selection, as it must: the two that do not force it catch it.
TINY_LIMITS = {"loss_gap": 0.0005, "first_grad_norm_gap": 0.02,
               "param_delta_norm_gap": 0.008, "state_mismatch_share": 0.0,
               "action_flip_share": 0.015}
TINY_LIMITS_SPARSE = {"logit_gap": 0.5, "route_flip_share": 0.04,
                      "select_flip_share": 0.02}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


def test_the_cell_its_files_and_its_driver_are_found_by_name(bench, config):
    cell = bench.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "a2c-recall-16x4096"
    assert set(cell["limits_sparse"]) == set(check_sparse.NUMBERS) == {
        "logit_gap", "route_flip_share", "select_flip_share"}
    assert set(cell["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
        "state_mismatch_share", "action_flip_share"}
    assert cell["limits"]["state_mismatch_share"] == 0.0
    assert cell["follow_updates"] == 1 and cell["decode_check_envs"] == 2
    assert config["driver"] == "fused_sparse"
    driver = bench.driver(config["driver"])
    assert hasattr(driver, "setup") and set(driver.CONTROLS) == {
        "fp8_weights", "topk_1024"}
    argv = config["argv"] + cell["argv"]
    for flag, value in (("--model", "keye-vl2"),
                        ("--env", "jax:recall:18992:1024:4096"),
                        ("--rollout_len", "4096"), ("--batch_size", "65536"),
                        ("--grad_chunk_samples", "8192"), ("--steps_per_dispatch", "1")):
        assert argv[argv.index(flag) + 1] == value
    assert "--model_cut" not in argv  # the default cut is the cell's
    for path in config["reference"].split(", "):
        assert os.path.isfile(os.path.join(ROOT, path))
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "calibrate_sparse.py"))


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    with open(os.path.join(ROOT, "benchmark", "reference", "keye_vl2.py")) as f:
        source = f.read()
    assert "distributed_ba3c_tpu" not in source.split('"""', 2)[2]
    assert 'jax.default_matmul_precision("highest")' in source
    assert "approx_max_k" not in source and "jnp.argsort" in source


def test_a_traced_run_holds_whole_updates(bench):
    assert 6 <= bench.cell(CELL)["trace_seconds"] <= bench.doc["run_seconds"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_alone_and_its_row_agrees(bench, name):
    # first in its list; which later cells are appended after it is not held
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"][0] == CELL
    assert entry[0]["workloads"].count(CELL) == 1
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

        def module_ms(self, *_):
            return None

        def window_s(self):
            return 1.0

    for other in ("lfm2-8b-a1b-recall-fused-a2c", "phi4-mini-flash-recall-fused-a2c",
                  CONFIG):
        ctx = {"trace": NoTrace(), "counters": {"work_per_update": 65536},
               "cell": {"name": "no-such-capture", "chips": 1},
               "config": bench.config(other), "peaks": bench.peaks("TPU v5e")}
        assert module.read(ctx) is None


def test_the_kept_share_is_read_off_the_programs_counters(bench):
    module = bench.layer_metric("select_kept_share")
    ctx = {"counters": {"dsa_keys_selected": [1536.0, 768.0],
                        "dsa_keys_live": [2048.0, 1024.0]}}
    assert module.read(ctx) == 75.0
    assert module.read({"counters": {}}) is None


#: the eleven metrics every fused cell's capture gives (PR 24's, PR 26's head)
SHARED_METRICS = ("first_dispatch_s", "update_device_ms", "rollout_time_share",
                  "env_time_share", "learner_fwd_time_share",
                  "learner_bwd_time_share", "optimizer_time_share",
                  "unscoped_time_share", "dispatch_host_ms", "interstep_gap_ms",
                  "head_loss_time_share")
#: what each accepted metric listed when this cell came (PR 34), by metric
LISTED_AT_PR34 = {
    **dict.fromkeys(SHARED_METRICS[:10], ACCEPTED_CELLS[:4]),
    **dict.fromkeys(("train_mfu", "conv_time_share", "pool_bwd_time_share",
                     "conv_roofline"), ACCEPTED_CELLS[:3]),
    "allreduce_exposed_ms": ACCEPTED_CELLS[2:3],
    **dict.fromkeys(("lm_train_mfu", "moe_time_share", "moe_experts_roofline",
                     "decode_weight_read_roofline", "mixer_time_share",
                     "head_loss_time_share", "moe_load_max_over_mean"),
                    ACCEPTED_CELLS[3:4]),
    **dict.fromkeys(("seq_train_mfu", "ssm_time_share", "ssm_scan_roofline",
                     "attn_time_share", "decode_read_roofline",
                     "carry_copy_time_share"), ACCEPTED_CELLS[4:5]),
}
#: whose readers find this cell's scopes and counters (``moe``, ``head``,
#: ``moe_tokens_per_expert``); every other metric counts another model's work
MAY_LIST_THIS_CELL = SHARED_METRICS + ("moe_time_share", "moe_load_max_over_mean")


@pytest.mark.parametrize("name", list(LISTED_AT_PR34))
def test_an_accepted_metric_is_left_as_it_was(bench, name):
    """None lost a cell it listed or had one put before them; cells appended
    since are not held, but that another model's metric does not list this
    cell is."""
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name][0]
    had = list(LISTED_AT_PR34[name])
    assert entry["workloads"][:len(had)] == had
    assert entry["workloads"].count(CELL) <= 1
    assert name in MAY_LIST_THIS_CELL or CELL not in entry["workloads"]


def test_the_benchmark_has_what_this_cell_needs_and_lost_nothing(bench):
    doc = bench.doc
    assert {"ba3cnet-pong-fused-a2c", "lfm2-8b-a1b-recall-fused-a2c",
            "phi4-mini-flash-recall-fused-a2c", CONFIG} <= {
        c["name"] for c in doc["configs"]}
    assert set(ACCEPTED_CELLS) | {CELL} <= {w["name"] for w in doc["workloads"]}
    assert set(NEW_METRICS) <= {m["name"] for m in doc["per_layer"]}
    assert [w["name"] for w in doc["workloads"] if w["chips"] == 4] == [
        "fused-pong-4chip-1024x20"]
    for entry in doc["configs"] + doc["workloads"]:
        if entry["name"] in (CONFIG, CELL):
            assert 1 <= len(entry["why"]) <= 200
    assert "indexer" in bench.cell(CELL)["why"] and "top-k" in bench.cell(CELL)["why"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configurations_file_holds_the_published_value(config, key):
    assert config[key] == PUBLISHED[key] and type(config[key]) is type(PUBLISHED[key])


def test_the_cut_is_written_down(bench, config):
    entry = [c for c in bench.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert doc["reduced"] == entry["reduced"]
    assert doc["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        4, 16, 18992)
    assert doc["vocab_size"] * 8 == doc["published"]["vocab_size"]
    assert doc["num_experts"] * 8 == doc["published"]["num_experts"]
    assert doc["held"]["layers"] == [0, 1, 2, 3] and doc["held"]["expert_offset"] == 0
    assert doc["deployment"]["chips_sharing_each_layer"] == 8
    assert doc["algorithm"]["indexer_loss_coef"] == 1.0
    for key in ("assumed", "departures", "precision", "algorithm"):
        assert doc[key]
    for item in ("qk_norm", "rope", "mrope", "indexer_k_norm", "indexer_activation",
                 "indexer_scales", "indexer_input", "chunk_sizes", "selection",
                 "indexer_loss", "router_aux_loss", "weights"):
        assert item in doc["assumed"], item
    for item in ("vision_tower", "value_head", "partial_expert_sums", "vocabulary",
                 "positions"):
        assert item in doc["departures"], item
    # no width is among the keys cut
    assert not [k for k in doc["reduced"] if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]


def test_the_programs_defaults_are_the_configurations(config):
    from benchmark.reference import keye_vl2 as reference
    from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2

    model = KeyeVL2()
    for field in ("hidden_size", "moe_intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "rms_norm_eps",
                  "num_experts_per_tok", "norm_topk_prob"):
        assert getattr(model, field) == config[field], field
    assert model.rope_theta == float(config["rope_theta"])
    sa = config["sa_config"]
    assert (model.indexer_num_heads, model.indexer_head_dim, model.index_topk,
            model.q_chunk_size) == (sa["indexer_num_heads"], sa["indexer_head_dim"],
                                    sa["topk"], sa["q_chunk_size"])
    assert model.num_experts == config["published"]["num_experts"]
    assert model.experts_held == config["num_experts"]
    assert model.expert_offset == config["held"]["expert_offset"]
    assert model.num_actions == config["vocab_size"]
    assert list(model.layer_ids) == config["held"]["layers"]
    assert len(model.layer_ids) == config["num_hidden_layers"]
    assert model.indexer_loss_coef == config["algorithm"]["indexer_loss_coef"]
    spec = reference.spec_of(config)
    assert spec["layers"] == model.layer_ids and spec["index_topk"] == 2048
    ours = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: reference.init_params(k, spec),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda s: s.shape, ours) == \
        jax.tree_util.tree_map(lambda s: s.shape, theirs)
    n = sum(x.size for x in jax.tree_util.tree_leaves(ours))
    assert n == opcount.params_held(config) == config["deployment"]["parameters_held"]


def test_hand_counted_parameters_and_operations(config):
    a = opcount.layer(config)
    d = 2048
    # q 8.39 M + k, v 2 x 1.05 M + o 8.39 M, and two gains of 128
    assert a["attention"] == d * 4096 + 2 * d * 512 + 4096 * d + 256 == 18_874_624
    # W_q^I 2.10 M + W_k^I 0.13 M + W_w 0.03 M, and the key norm's gain and bias
    assert a["indexer"] == d * 1024 + d * 64 + d * 16 + 128 == 2_261_120
    assert a["router"] == d * 128 == 262_144
    assert a["expert"] == 3 * d * 768 == 4_718_592
    a_layer = a["attention"] + a["indexer"] + a["router"] + a["norms"] + 16 * a["expert"]
    assert a_layer == 96_899_456                                   # 96.9 M
    assert opcount.params_held(config) == 4 * a_layer + 2 * 18992 * d + d + d + 1
    assert opcount.params_held(config) == 465_393_153              # 465.4 M
    # an episode of twice the top-k: mean 1,536.25 keys selected of 2,048.5 live
    assert opcount.mean_selection(4096, 2048) == 1536.25
    assert opcount.mean_context(4096) == 2048.5
    macs = opcount.forward_macs(config, 4096)
    assert macs["attention"] == 4 * 18_874_368
    assert macs["selected"] == 4 * 2 * 32 * 128 * 1536.25             # 12.6 M a layer
    assert macs["indexer"] == 4 * 2_260_992
    assert macs["index_scores"] == 4 * 16 * 64 * 2048.5               # 2.1 M a layer
    assert macs["experts"] == 4 * 1.0 * 4_718_592                     # one visit a token
    assert macs["head"] == 18992 * d                                  # 38.9 M
    assert 201e6 < sum(macs.values()) < 203e6                         # the issue's 202 M
    assert opcount.flops_per_env_step(config, 4096) == 8 * sum(macs.values())
    assert 1.61e9 < opcount.flops_per_env_step(config, 4096) < 1.63e9
    counted = opcount.forward_macs(config, 4096, visits_per_token=1.25)
    assert counted["experts"] == 4 * 1.25 * 4_718_592
    assert opcount.decode_weight_bytes(config) == 2 * 465_393_153     # 0.93 GB


def test_the_decode_steps_carry_bytes_by_hand(config):
    from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2

    carry = KeyeVL2().carry_bytes()  # what the step's metric reports
    kv, index_keys, pos = carry
    assert (kv, index_keys, pos) == (33_554_432, 2_097_152, 4)        # 35.7 MB an env
    got = opcount.decode_carry_bytes(config, carry, envs=16, episode=4096)
    want = 16 * (kv * (1536.25 + 1) / 4096           # the selected rows, and one written
                 + index_keys * (2048.5 + 1) / 4096  # up to the position, and one written
                 + 2 * pos)
    assert got == pytest.approx(want)
    assert 0.21e9 < got < 0.23e9  # 0.22 GB beside 0.93 GB of weights


def test_the_numbers_of_check_sparse_by_hand():
    own = np.zeros((2, 4, 4), bool)
    forced = np.zeros((2, 4, 4), bool)
    own[0, 3, :2] = True      # keys 0, 1
    forced[0, 3, 1:3] = True  # keys 1, 2: one pair on each side alone, of 2 + 2
    assert check_sparse.select_flip_share(own, forced) == 0.5
    assert check_sparse.select_flip_share(own, own) == 0.0
    half = own.copy()
    half[0, 3, 1] = False     # half as many keys: 1 of 2 + 1
    assert check_sparse.select_flip_share(own, half) == pytest.approx(1 / 3)
    from benchmark.reference import keye_vl2 as reference

    one_side, either = reference.flips(own, forced)
    assert (int(one_side), int(either)) == (2, 4)
    side = {"losses": [0.0011], "first_grad": {"a/b": 1.0}, "delta": {"a/b": 2.0},
            "states": [({"t": np.zeros(2)}, np.zeros(2))],
            "decode_logits": np.zeros((1, 4, 3), np.float32)}
    ref = dict(side, losses=[0.001], action_flips=0.0, action_margin=0.0,
               decode_logits=side["decode_logits"].copy(), a2c_losses=[0.0005],
               indexer_kl=[[0.0005]], route_flip_share=0.02,
               route_flips_by_layer=[0.02], select_flip_share=0.25,
               select_flips_by_layer=[0.25])
    ref["decode_logits"][0, 0, 0] = 2.0
    side["decode_logits"][0, 0, 0] = 2.0
    side["decode_logits"][0, 3, 1] = 0.5
    limits = dict.fromkeys(TINY_LIMITS, 0.0)
    rows = {r["number"]: r for r in check_sparse.compare(
        side, ref, limits,
        {"logit_gap": 0.2, "route_flip_share": 0.05, "select_flip_share": 0.1}, 0.1)}
    assert list(rows) == ["loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
                          "state_mismatch_share", "action_flip_share", "logit_gap",
                          "route_flip_share", "select_flip_share"]
    assert rows["loss_gap"]["value"] == pytest.approx(0.001) and not rows["loss_gap"]["ok"]
    assert "indexer KL a layer" in rows["loss_gap"]["detail"]
    assert rows["logit_gap"]["value"] == 0.25 and not rows["logit_gap"]["ok"]
    assert rows["route_flip_share"]["value"] == 0.02 and rows["route_flip_share"]["ok"]
    assert rows["select_flip_share"]["value"] == 0.25
    assert not rows["select_flip_share"]["ok"]
    assert rows["select_flip_share"]["detail"] == "by layer 0.25000"


# -- the driver's Session at the small cut ---------------------------------------
@pytest.fixture(scope="module")
def tiny(bench, config):
    small = dict(hidden_size=64, moe_intermediate_size=32, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, num_experts=2,
                 num_experts_per_tok=2, vocab_size=64,
                 sa_config=dict(config["sa_config"], indexer_num_heads=2,
                                indexer_head_dim=8, topk=8))
    argv = list(config["argv"])
    for flag, value in (("--env", "jax:recall:64:4:32"), ("--rollout_len", "32"),
                        ("--grad_chunk_samples", "64")):
        argv[argv.index(flag) + 1] = value
    tiny_config = dict(
        config, **small, argv=argv + ["--model_cut", "tiny"],
        published=dict(config["published"], num_experts=16),
        held=dict(config["held"], layers=[0, 1]))
    cell = dict(bench.cell(CELL), argv=["--batch_size", "256"],
                limits=TINY_LIMITS, limits_sparse=TINY_LIMITS_SPARSE, trace_seconds=1)
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
    for number in list(TINY_LIMITS) + list(TINY_LIMITS_SPARSE):
        assert f"compare {number}:" in out
    assert "keys selected / live a layer" in out and "indexer KL a layer" in out


@pytest.mark.timeout(900)
@pytest.mark.parametrize("control,must_fail", [
    ("fp8_weights", {"loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
                     "action_flip_share", "logit_gap", "route_flip_share",
                     "select_flip_share"}),
    ("topk_1024", {"select_flip_share", "logit_gap"}),
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
    if control == "topk_1024":  # half as many keys: a third of the pairs one-sided
        value = [r for r in rows if r["number"] == "select_flip_share"][0]["value"]
        assert 0.3 < value < 0.36
