"""Model FLOP/s utilization of the traced window for the dense
token-sequence policy: the operations a token needs (rollout forward;
learner forward, dW and dx of every product; the attention layers' products
against their keys and values at the episode's mean context; the scans'
``c x n`` states: ``benchmark/opcount_phi4flash.py``) times the env-steps
the window's updates trained on, over the window and the chip's bf16 peak.
Recomputed forwards are not counted.

The update's device time and the trainer's phases on this cell are the
shared metrics' (``update_device_ms``, ``rollout_time_share``, ..), which
list it since PR 40."""

from benchmark import opcount_phi4flash as opcount

ROW = {
    "name": "seq_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "state_space" not in cfg or "rollout_len" not in c:
        return None
    env_steps = tr.env_steps(cfg["trace"]["update_module"], c["work_per_update"])
    if not env_steps:
        return None
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]))
    print(f"seq_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step, "
          f"{env_steps:.0f} env-steps in {tr.window_s():.3f} s")
    return 100.0 * env_steps * a_step / (
        tr.window_s() * ctx["peaks"]["bf16_flops_per_s"])
