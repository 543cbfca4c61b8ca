"""Model FLOP/s utilization of the traced window for the sparse-attention,
routed-expert token-sequence policy: the operations a token needs (rollout
forward; learner forward, dW and dx of every product; the main attention at
the episode's mean SELECTION, the indexer's scores at its mean context, the
experts by the (token, held expert) visits the program counted:
``benchmark/opcount_keyevl2.py``) times the env-steps the window's updates
trained on, over the window and the chip's bf16 peak: the share of the whole
step. Recomputed forwards and the masked-out products of a masked-dense
learner are not counted.

The update's device time, the trainer's phases and the shared layers
(``moe``, ``head``) on this cell are the shared metrics' (``update_device_ms``,
``rollout_time_share``, .., ``moe_time_share``, ``head_loss_time_share``),
which list it since PR 40."""

from benchmark import opcount_keyevl2 as opcount
from benchmark import scopes_lm

ROW = {
    "name": "sparse_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "sa_config" not in cfg or "rollout_len" not in c:
        return None
    env_steps = tr.env_steps(cfg["trace"]["update_module"], c["work_per_update"])
    if not env_steps:
        return None
    visits = scopes_lm.visits_per_update(ctx)
    per_token = None
    if visits is not None:  # a token a layer
        per_token = visits / c["work_per_update"] / len(cfg["held"]["layers"])
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]), per_token)
    print(f"sparse_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step "
          f"({'no count of visits' if per_token is None else f'{per_token:.4f}'} "
          f"expert visits a token a layer), {env_steps:.0f} env-steps in "
          f"{tr.window_s():.3f} s")
    return 100.0 * env_steps * a_step / (
        tr.window_s() * ctx["peaks"]["bf16_flops_per_s"])
