"""Model FLOP/s utilization of one update of the linear-attention hybrid:
the operations a token needs (rollout forward; learner forward, dW and dx of
every product; the full layer's products against its keys and values at the
episode's mean context; the delta rule as its recurrence, three products a
state a position: ``benchmark/opcount_olmohybrid.py``) times the env-steps
an update trains on, over the update's device time (``update_device_ms``)
and the chip's bf16 peak: the share of the whole step. Recomputed forwards
and what the chunked form spends beyond the recurrence are not counted."""

from benchmark import opcount_olmohybrid as opcount

ROW = {
    "name": "linattn_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "linear_key_head_dim" not in cfg or "rollout_len" not in c:
        return None
    update_ms = tr.module_ms(cfg["trace"]["update_module"])
    if not update_ms:
        return None
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]))
    env_steps = c["work_per_update"] / ctx["cell"]["chips"]
    print(f"linattn_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step, "
          f"{env_steps:.0f} env-steps a chip in an update of {update_ms:.1f} ms")
    return 100.0 * env_steps * a_step / (
        update_ms / 1e3 * ctx["peaks"]["bf16_flops_per_s"])
