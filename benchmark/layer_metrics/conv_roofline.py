"""The matrix-unit fusions' share of their roofline.

The least time the chip could take for the convolutions and fc products the
window executed (the larger of their operations over the bf16 peak and their
bytes over the HBM peak, both counted from shapes by ``benchmark/opcount.py``
for the env-steps the window's updates trained on) over the device time of
those fusions. An earlier line of the run says which of the two bounds."""

from benchmark import opcount
from benchmark.trace import MATMUL

ROW = {
    "name": "conv_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, peaks = ctx["trace"], ctx["config"], ctx["peaks"]
    env_steps = tr.env_steps(
        cfg["trace"]["update_module"], ctx["counters"]["work_per_update"]
    )
    seconds = tr.kind_seconds(MATMUL)
    if not env_steps or not seconds:
        return None
    by_ops = env_steps * opcount.flops_per_env_step(cfg) / peaks["bf16_flops_per_s"]
    by_bytes = env_steps * opcount.bytes_per_env_step(cfg) / peaks["hbm_bytes_per_s"]
    print(f"conv_roofline: bound by {'operations' if by_ops >= by_bytes else 'bytes'} "
          f"(least {by_ops:.4f} s by operations, {by_bytes:.4f} s by bytes, "
          f"{seconds:.4f} s taken, {env_steps:.0f} env-steps)")
    return 100.0 * max(by_ops, by_bytes) / seconds
