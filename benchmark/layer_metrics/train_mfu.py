"""Model FLOP/s utilization of the traced window: the matrix operations an
env-step needs (rollout forward; learner forward, dW and dx; counted from
shapes, ``benchmark/opcount.py``) times the env-steps the window's updates
trained on, over the window and the chip's bf16 peak."""

from benchmark import opcount

ROW = {
    "name": "train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg = ctx["trace"], ctx["config"]
    env_steps = tr.env_steps(
        cfg["trace"]["update_module"], ctx["counters"]["work_per_update"]
    )
    if not env_steps:
        return None
    flops = env_steps * opcount.flops_per_env_step(cfg)
    return 100.0 * flops / (tr.window_s() * ctx["peaks"]["bf16_flops_per_s"])
