"""The routed relu² experts' grouped products against their roofline, at the
published width 1,856 (14.5 lanes).

The least time the chip could take for the learner's grouped products
(``ops/grouped_matmul.py:grouped_dot``, its Pallas kernels or
``jax.lax.ragged_dot``, whichever ran: the line says which) that the traced
window executed, over the device time under ``learner/moe/experts``: the
larger of their operations over the bf16 peak (forward, dW and dx of the two
matrices of every counted visit) and their bytes over the HBM peak (every
held expert's two matrices read once a pass, three passes a chunk; each
visit's rows read and written once a product). Counted by
``benchmark/opcount_nemotronh.py`` from the visits the router really made
here (``moe_tokens_per_expert``). The recomputed forward and the products'
own elementwise work take time there and count for nothing."""

from benchmark import opcount_nemotronh as opcount
from benchmark import scopes_lm

ROW = {
    "name": "relu2_experts_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, peaks, c = ctx["config"], ctx["peaks"], ctx["counters"]
    if "mamba_num_heads" not in cfg:
        return None
    taken = scopes_lm.seconds(ctx, "MOE_EXPERTS", under=("LEARNER",))
    visits = scopes_lm.visits_per_update(ctx)
    if not taken or visits is None:
        return None
    n = scopes_lm.updates(ctx)
    by_ops = opcount.routed_expert_flops(cfg, n * visits) / peaks["bf16_flops_per_s"]
    by_bytes = opcount.routed_expert_bytes(
        cfg, 3 * n * visits, 3 * n * c["learner_chunks"]
    ) / peaks["hbm_bytes_per_s"]
    kernel = scopes_lm.seconds(ctx, "MOE_EXPERTS_GMM", under=("LEARNER",))
    print(f"relu2_experts_roofline: bound by "
          f"{'operations' if by_ops >= by_bytes else 'bytes'} (least "
          f"{by_ops:.4f} s by operations, {by_bytes:.4f} s by bytes, "
          f"{taken:.4f} s taken over {n:.2f} updates of {visits:.0f} visits; "
          f"{kernel or 0.0:.4f} s of it in the Pallas kernels: "
          f"{'the kernels ran' if kernel else 'ragged_dot ran'})")
    return 100.0 * max(by_ops, by_bytes) / taken
