"""Model FLOP/s utilization of the traced window for the token-sequence
policy: the matrix operations a token needs (rollout forward; learner
forward, dW and dx; ``benchmark/opcount_lm.py``, the expert layers at the
visits the router was counted to make) times the env-steps the window's
updates trained on, over the window and the chip's bf16 peak. Recomputed
forwards are not counted."""

from benchmark import opcount_lm, scopes_lm

ROW = {
    "name": "lm_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    work = ctx["counters"]["work_per_update"]
    env_steps = tr.env_steps(cfg["trace"]["update_module"], work)
    visits = scopes_lm.visits_per_update(ctx)
    if not env_steps or visits is None:
        return None
    a_token_a_layer = visits / (work * opcount_lm.expert_layers(cfg))
    flops = env_steps * opcount_lm.flops_per_env_step(cfg, a_token_a_layer)
    print(f"lm_train_mfu: {a_token_a_layer:.4f} visits a token an expert layer, "
          f"{opcount_lm.flops_per_env_step(cfg, a_token_a_layer) / 1e6:.1f} MFLOP "
          f"an env-step, {env_steps:.0f} env-steps")
    return 100.0 * flops / (tr.window_s() * ctx["peaks"]["bf16_flops_per_s"])
