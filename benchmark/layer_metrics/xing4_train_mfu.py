"""Model FLOP/s utilization of one update of the latent-attention policy:
the operations a token needs (rollout forward with the absorbed attention;
learner forward, dW and dx of every product with the expanded one; the
attentions' products against their context at the episode's mean; the
hyper-connections' projection, read and write; the routed experts at the
visits the router made, ``moe_tokens_per_expert``:
``benchmark/opcount_xing4.py``) times the env-steps an update trains on, over
the update's device time (``update_device_ms``) and the chip's bf16 peak:
the share of the whole step. Recomputed forwards are not counted."""

from benchmark import opcount_xing4 as opcount
from benchmark import scopes_lm

ROW = {
    "name": "xing4_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "kv_lora_rank" not in cfg or "rollout_len" not in c:
        return None
    update_ms = tr.module_ms(cfg["trace"]["update_module"])
    if not update_ms:
        return None
    env_steps = c["work_per_update"] / ctx["cell"]["chips"]
    visited = scopes_lm.visits_per_update(ctx)
    expert_layers = opcount.layer_kinds(cfg).count(opcount.EXPERTS)
    visits = None if visited is None else visited / (
        c["work_per_update"] * expert_layers)
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]), visits)
    print(f"xing4_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step "
          f"({'an even router' if visits is None else f'{visits:.4f}'} visits a "
          f"token an expert layer), {env_steps:.0f} env-steps a chip in an "
          f"update of {update_ms:.1f} ms")
    return 100.0 * env_steps * a_step / (
        update_ms / 1e3 * ctx["peaks"]["bf16_flops_per_s"])
