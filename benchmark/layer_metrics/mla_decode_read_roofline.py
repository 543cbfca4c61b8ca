"""The latent-attention policy's decode steps against the time the bytes
they must move alone need: every parameter held read once a step from the
rollout's snapshot, every layer's latent rows up to the position read once
and the position's row written (``benchmark/opcount_xing4.py``: 1,152 bytes a
row, counted once however the program lays the rows out or however often its
kernel fetches them); times the decode steps the traced window executed,
over the HBM peak.

Over the device time under ``rollout`` outside sample, env_step, stack and
weights_bf16, as ``mamba2_decode_read_roofline.py`` reckons it and for its
reason: the waits for the weights the compiler fetches ahead carry the
loop's name and no layer's."""

from benchmark import opcount_xing4 as opcount
from benchmark import scopes, scopes_lm

#: the parts of ``rollout`` that are not the decode step
NOT_DECODE = ("ROLLOUT_SAMPLE", "ROLLOUT_ENV_STEP", "ROLLOUT_STACK",
              "ROLLOUT_WEIGHTS_BF16")
ROW = {
    "name": "mla_decode_read_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap, cfg, c = scopes.capture(ctx), ctx["config"], ctx["counters"]
    if cap is None or "kv_lora_rank" not in cfg or "rollout_len" not in c:
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
    rows = sum(opcount.decode_latent_bytes(
        cfg, c["envs_per_chip"], episode).values())
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    print(f"mla_decode_read_roofline: {steps:.0f} decode steps, "
          f"{1e3 * taken / steps:.4f} ms a step taken "
          f"({1e3 * under_policy / steps:.4f} under rollout/policy), "
          f"{1e3 * (weights + rows) / peak:.4f} ms by bytes "
          f"({weights / 1e6:.1f} MB of weights, {rows / 1e6:.1f} MB of latent rows)")
    return 100.0 * steps * (weights + rows) / peak / taken
