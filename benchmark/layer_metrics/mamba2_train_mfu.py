"""Model FLOP/s utilization of one update of the Mamba-2 and sparse-expert
hybrid: the operations a token needs (rollout forward; learner forward, dW
and dx of every product; the attention block's products against its keys
and values at the episode's mean context; the Mamba-2 recurrence as three
products a state a position; the routed experts at the visits the router
made, ``moe_tokens_per_expert``: ``benchmark/opcount_nemotronh.py``) times
the env-steps an update trains on, over the update's device time
(``update_device_ms``) and the chip's bf16 peak: the share of the whole
step. Recomputed forwards are not counted."""

from benchmark import opcount_nemotronh as opcount
from benchmark import scopes_lm

ROW = {
    "name": "mamba2_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "mamba_num_heads" not in cfg or "rollout_len" not in c:
        return None
    update_ms = tr.module_ms(cfg["trace"]["update_module"])
    if not update_ms:
        return None
    env_steps = c["work_per_update"] / ctx["cell"]["chips"]
    visited = scopes_lm.visits_per_update(ctx)
    blocks = sum(l["kind"] == opcount.EXPERTS for l in opcount.layers(cfg))
    visits = None if visited is None else visited / (c["work_per_update"] * blocks)
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]), visits)
    print(f"mamba2_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step "
          f"({'an even router' if visits is None else f'{visits:.4f}'} visits a "
          f"token an expert block), {env_steps:.0f} env-steps a chip in an "
          f"update of {update_ms:.1f} ms")
    return 100.0 * env_steps * a_step / (
        update_ms / 1e3 * ctx["peaks"]["bf16_flops_per_s"])
