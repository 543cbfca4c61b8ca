"""The learner's Mamba-2 recurrences against the least time they need: the
larger of their operations over the bf16 peak (the recurrence, three
products a state a position, forward and twice backward) and of their bytes
over the HBM peak (``x``, ``dt``, ``B``, ``C`` and ``y`` once each way, the
state at the chunk boundaries once: ``benchmark/opcount_nemotronh.py``),
over the device time under ``learner/op_mamba2/ssd``, forward and backward.
The chunks the backward recomputes take time there and count for nothing."""

from benchmark import opcount_nemotronh as opcount
from benchmark import scopes_lm

ROW = {
    "name": "ssd_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    if "mamba_num_heads" not in cfg:
        return None
    taken = scopes_lm.seconds(ctx, "OP_MAMBA2_SSD", under=("LEARNER",))
    if not taken:
        return None
    n = scopes_lm.updates(ctx)
    tokens = n * c["work_per_update"] / ctx["cell"]["chips"]
    by_ops = opcount.ssd_flops(cfg, tokens) / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = opcount.ssd_bytes(cfg, tokens) / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"ssd_roofline: least {by_ops:.4f} s by operations, "
          f"{by_bytes:.4f} s by bytes; {taken:.4f} s taken over {n:.2f} updates "
          f"of {c['work_per_update'] / ctx['cell']['chips']:.0f} positions a chip")
    return 100.0 * max(by_ops, by_bytes) / taken
