"""The Mamba-2 hybrid's decode steps against the time the bytes they must
move alone need: every parameter held read once a step from the bfloat16
snapshot, the recurrence's states and the convs' tails read and written
whole, the one K/V read up to the position (``benchmark/
opcount_nemotronh.py:decode_carry_bytes``, from the PROGRAM's own count of
the carry's bytes an env by kind, the step's metric ``carry_bytes_per_env``);
times the decode steps the traced window executed, over the HBM peak.

Over the device time under ``rollout`` outside sample, env_step, stack and
weights_bf16, as ``decode_read_roofline.py`` reckons it and for its reason:
the waits for the weights the compiler fetches ahead carry the loop's name
and no layer's."""

from benchmark import opcount_nemotronh as opcount
from benchmark import scopes, scopes_lm

#: the parts of ``rollout`` that are not the decode step
NOT_DECODE = ("ROLLOUT_SAMPLE", "ROLLOUT_ENV_STEP", "ROLLOUT_STACK",
              "ROLLOUT_WEIGHTS_BF16")
ROW = {
    "name": "mamba2_decode_read_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap, cfg, c = scopes.capture(ctx), ctx["config"], ctx["counters"]
    carry = c.get("carry_bytes_per_env")
    if cap is None or carry is None or "mamba_num_heads" not in cfg:
        return None
    prof, seconds = cap["profiling"], cap["seconds"]
    under_policy = seconds[prof.ROLLOUT_POLICY]
    taken = seconds[prof.ROLLOUT] - sum(
        seconds[getattr(prof, part)] for part in NOT_DECODE)
    episode = int(c["rollout_len"])
    steps = scopes_lm.updates(ctx) * episode
    if not taken or not steps:
        return None
    weights = opcount.decode_weight_bytes(cfg)
    moved = opcount.decode_carry_bytes(cfg, carry, c["envs_per_chip"], episode)
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    print(f"mamba2_decode_read_roofline: {steps:.0f} decode steps, "
          f"{1e3 * taken / steps:.4f} ms a step taken "
          f"({1e3 * under_policy / steps:.4f} under rollout/policy), "
          f"{1e3 * (weights + moved) / peak:.4f} ms by bytes "
          f"({weights / 1e6:.1f} MB of weights, {moved / 1e6:.1f} MB of carry)")
    return 100.0 * steps * (weights + moved) / peak / taken
