"""The rollout's decode steps against the time the bytes they must move
alone need: every parameter held read once a step from the bfloat16
snapshot, and each kind of carry moved as ``benchmark/opcount_phi4flash.py:
decode_carry_bytes`` counts it (state-space state and conv tail read and
written; the ring read up to the window; the shared K/V read up to the
position, once a reader), from the PROGRAM's own count of the carry's bytes
an env by kind (the step's metric ``carry_bytes_per_env``); times the decode
steps the traced window executed, over the HBM peak.

Over the device time under ``rollout/policy`` AND the time under ``rollout``
that lies in none of its parts: the compiler fetches slices of the
feed-forward weights ahead of their products in asynchronous copies, and
the waits for those carry the loop's name and no layer's (PERF.md section 6,
PR 31: 0.68 ms a step beside 2.29 under ``rollout/policy``). Left out, a
step's weight reads would be timed without part of their time."""

from benchmark import opcount_phi4flash as opcount
from benchmark import scopes, scopes_lm

#: the parts of ``rollout`` that are not the decode step
NOT_DECODE = ("ROLLOUT_SAMPLE", "ROLLOUT_ENV_STEP", "ROLLOUT_STACK",
              "ROLLOUT_WEIGHTS_BF16")
ROW = {
    "name": "decode_read_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap, cfg, c = scopes.capture(ctx), ctx["config"], ctx["counters"]
    carry = c.get("carry_bytes_per_env")
    if cap is None or carry is None or "state_space" not in cfg:
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
    print(f"decode_read_roofline: {steps:.0f} decode steps, "
          f"{1e3 * taken / steps:.4f} ms a step taken "
          f"({1e3 * under_policy / steps:.4f} under rollout/policy), "
          f"{1e3 * (weights + moved) / peak:.4f} ms by bytes "
          f"({weights / 1e6:.1f} MB of weights, {moved / 1e6:.1f} MB of carry)")
    return 100.0 * steps * (weights + moved) / peak / taken
