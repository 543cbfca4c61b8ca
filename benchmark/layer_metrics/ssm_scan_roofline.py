"""The learner's selective scans against the time their bytes alone need.

The least HBM traffic of the scans the traced window's updates ran
(``benchmark/opcount_phi4flash.py:scan_bytes``: forward reads ``u``, ``dt``,
``B``, ``C`` and writes ``y``; backward reads those and ``dy`` and writes
four gradients; the ``[c, n]`` state never needs to leave fast memory) over
the HBM peak, over the device time under ``learner/op_ssm/scan``. By bytes:
no kernel computes the scan yet (``ops/ssm.py`` is ``jax.numpy``), and the
chip's vector unit has no peak in ``peaks.json`` to count its operations
against. The chunks the backward recomputes take time there and count for
nothing."""

from benchmark import opcount_phi4flash as opcount
from benchmark import scopes_lm

ROW = {
    "name": "ssm_scan_roofline", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "kernels",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cfg, c = ctx["config"], ctx["counters"]
    taken = scopes_lm.seconds(ctx, "OP_SSM_SCAN", under=("LEARNER",))
    if not taken or "state_space" not in cfg:
        return None
    n = scopes_lm.updates(ctx)
    tokens = n * c["work_per_update"] / ctx["cell"]["chips"]
    least = opcount.scan_bytes(cfg, tokens) / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"ssm_scan_roofline: bound by bytes (least {least:.4f} s, "
          f"{taken:.4f} s taken over {n:.2f} updates of "
          f"{c['work_per_update'] / ctx['cell']['chips']:.0f} positions a chip)")
    return 100.0 * least / taken
