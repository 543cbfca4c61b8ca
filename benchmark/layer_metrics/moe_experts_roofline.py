"""The grouped expert products' share of their roofline.

The least time the chip could take for the grouped products
(``jax.lax.ragged_dot``: the learner's; a decode step's few rows go through
every held expert in plain batched products, which
``decode_weight_read_roofline`` covers) that the traced window executed,
over the device time under ``learner/moe/experts``: the larger of their
operations over the bf16 peak (forward, dW and dx of the three matrices of
every counted visit) and their bytes over the HBM peak (every held expert's
matrices read once a pass, three passes a chunk; each visit's rows read and
written once a product). Counted by ``benchmark/opcount_lm.py`` from the
visits the router made. The recomputed forward (a layer is rematerialised
in the backward) and the products' own elementwise work take time there and
count for nothing."""

from benchmark import opcount_lm, scopes_lm

ROW = {
    "name": "moe_experts_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, peaks, c = ctx["config"], ctx["peaks"], ctx["counters"]
    taken = scopes_lm.seconds(ctx, "MOE_EXPERTS", under=("LEARNER",))
    visits = scopes_lm.visits_per_update(ctx)
    if not taken or visits is None:
        return None
    n = scopes_lm.updates(ctx)
    by_ops = 3 * opcount_lm.experts_flops(cfg, n * visits) / peaks["bf16_flops_per_s"]
    by_bytes = opcount_lm.experts_bytes(
        cfg, 3 * n * visits, 3 * n * c["learner_chunks"]
    ) / peaks["hbm_bytes_per_s"]
    print(f"moe_experts_roofline: bound by "
          f"{'operations' if by_ops >= by_bytes else 'bytes'} (least "
          f"{by_ops:.4f} s by operations, {by_bytes:.4f} s by bytes, "
          f"{taken:.4f} s taken over {n:.2f} updates of {visits:.0f} visits)")
    return 100.0 * max(by_ops, by_bytes) / taken
