"""The decode's attention over the latent rows against the time reading
those rows alone needs: every layer's live rows (``[0, t]`` of every env,
1,152 bytes each: ``benchmark/opcount_xing4.py``) ONCE a step at the
episode's mean position, times the decode steps the traced window executed,
over the HBM peak and the device time under ``rollout/policy/op_mla/attend``
(scores, softmax and the weighted sum over the cache, whatever implements
them). It counts the work and not the implementation: a program that pads
its rows, or fetches them once as keys and once as values, reads under 50 %
here, and a later kernel that reads each row once is judged by the same
number."""

from benchmark import opcount_xing4 as opcount
from benchmark import scopes_lm

ROW = {
    "name": "latent_attend_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    if "kv_lora_rank" not in cfg or "rollout_len" not in c:
        return None
    taken = scopes_lm.seconds(ctx, "OP_MLA_ATTEND", under=scopes_lm.UNDER[:1])
    episode = int(c["rollout_len"])
    steps = scopes_lm.updates(ctx) * episode
    if not taken or not steps:
        return None
    rows = opcount.decode_latent_bytes(cfg, c["envs_per_chip"], episode)["read"]
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    print(f"latent_attend_roofline: {steps:.0f} decode steps, "
          f"{1e3 * taken / steps:.4f} ms a step under rollout/policy/op_mla/attend, "
          f"{1e3 * rows / peak:.4f} ms by the live rows' bytes "
          f"({rows / 1e6:.1f} MB a step at the mean position)")
    return 100.0 * steps * rows / peak / taken
