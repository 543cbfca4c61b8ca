"""Model FLOP/s utilization of the traced window for the sparse-attention,
routed-expert token-sequence policy: the operations a token needs (rollout
forward; learner forward, dW and dx of every product; the main attention at
the episode's mean SELECTION, the indexer's scores at its mean context, the
experts by the (token, held expert) visits the program counted:
``benchmark/opcount_keyevl2.py``) times the env-steps the window's updates
trained on, over the window and the chip's bf16 peak: the share of the whole
step. Recomputed forwards and the masked-out products of a masked-dense
learner are not counted.

Beside it the line prints the update's device time, the trainer's phases and
the shared layers (``moe``, ``head``, ``embed``) on this cell: the shared
metrics that report them cannot list this cell until a ``benchmark`` issue
relaxes ``tests/benchmark/test_benchmark_lm.py`` (PERF.md section 7), and a
later change to this cell has to start from them."""

from benchmark import opcount_keyevl2 as opcount
from benchmark import scopes, scopes_lm

PHASES = ("ROLLOUT", "RETURNS", "LEARNER_FWD", "LEARNER_BWD", "GRAD_REDUCE",
          "OPTIMIZER", "METRICS", "UNSCOPED")
SHARED_LAYERS = ("MOE", "MOE_ROUTER", "MOE_DISPATCH", "MOE_EXPERTS",
                 "MOE_EXPERTS_GMM", "MOE_COMBINE", "HEAD", "EMBED")

ROW = {
    "name": "sparse_train_mfu", "unit": "%", "better": "higher",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr, cfg, c = ctx["trace"], ctx["config"], ctx["counters"]
    if "sa_config" not in cfg or "rollout_len" not in c:
        return None
    env_steps = tr.env_steps(cfg["trace"]["update_module"], c["work_per_update"])
    if not env_steps:
        return None
    visits = scopes_lm.visits_per_update(ctx)
    per_token = None
    if visits is not None:  # a token a layer
        per_token = visits / c["work_per_update"] / len(cfg["held"]["layers"])
    a_step = opcount.flops_per_env_step(cfg, int(c["rollout_len"]), per_token)
    print(f"sparse_train_mfu: {a_step / 1e6:.1f} MFLOP an env-step "
          f"({'no count of visits' if per_token is None else f'{per_token:.4f}'} "
          f"expert visits a token a layer), {env_steps:.0f} env-steps in "
          f"{tr.window_s():.3f} s")
    update_ms = tr.module_ms(cfg["trace"]["update_module"])
    if scopes.capture(ctx) is not None and update_ms is not None:
        try:
            phases = scopes.shares_line(ctx, *PHASES)
            shared = scopes_lm.line(ctx, *SHARED_LAYERS)
        except (AttributeError, KeyError, TypeError):
            phases, shared = "no phases: a program from before these scopes", ""
        print(f"sparse_train_mfu: an update {update_ms:.1f} ms on the chip, busy "
              f"{tr.busy_s():.3f} s of the window; {phases}")
        if shared:
            print(f"sparse_train_mfu: {shared}")
    return 100.0 * env_steps * a_step / (
        tr.window_s() * ctx["peaks"]["bf16_flops_per_s"])
