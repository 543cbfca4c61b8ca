"""The rollout's decode steps against the time their weight reads alone
need: every parameter held is read once a step from the bfloat16 snapshot
(``benchmark/opcount_lm.py``: 1.02 GB), times the decode steps the traced
window executed, over the HBM peak, over the device time under
``rollout/policy``. Bound by bytes by construction: 128 rows a step."""

from benchmark import opcount_lm, scopes, scopes_lm

ROW = {
    "name": "decode_weight_read_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap = scopes.capture(ctx)
    if cap is None or scopes_lm.seconds(ctx, "MOE") is None:
        return None
    taken = cap["seconds"][cap["profiling"].ROLLOUT_POLICY]
    steps = scopes_lm.updates(ctx) * ctx["counters"]["rollout_len"]
    if not taken or not steps:
        return None
    least = steps * opcount_lm.decode_weight_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"decode_weight_read_roofline: {steps:.0f} decode steps, "
          f"{1e3 * taken / steps:.4f} ms a step taken, "
          f"{1e3 * least / steps:.4f} ms by bytes")
    return 100.0 * least / taken
