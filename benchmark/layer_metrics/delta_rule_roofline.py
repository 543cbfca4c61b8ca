"""The learner's delta rules against the least time they need: the larger
of their operations over the bf16 peak (the recurrence, three products a
state a position, forward and twice backward) and of their bytes over the
HBM peak (``q``, ``k``, ``v``, the gates and ``o`` once each way, the state
at the chunk boundaries once: ``benchmark/opcount_olmohybrid.py``), over the
device time under ``learner/op_linattn/delta``, forward and backward. The
chunks the backward recomputes and the products the chunked form spends
beyond the recurrence take time there and count for nothing."""

from benchmark import opcount_olmohybrid as opcount
from benchmark import scopes_lm

ROW = {
    "name": "delta_rule_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    if "linear_key_head_dim" not in cfg:
        return None
    taken = scopes_lm.seconds(ctx, "OP_LINATTN_DELTA", under=("LEARNER",))
    if not taken:
        return None
    n = scopes_lm.updates(ctx)
    tokens = n * c["work_per_update"] / ctx["cell"]["chips"]
    by_ops = opcount.delta_rule_flops(cfg, tokens) / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = opcount.delta_rule_bytes(cfg, tokens) / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"delta_rule_roofline: least {by_ops:.4f} s by operations, "
          f"{by_bytes:.4f} s by bytes; {taken:.4f} s taken over {n:.2f} updates "
          f"of {c['work_per_update'] / ctx['cell']['chips']:.0f} positions a chip")
    return 100.0 * max(by_ops, by_bytes) / taken
