"""Share of the device's op time under ``rollout`` and under none of its
parts (``rollout/policy``, ``/sample``, ``/env_step``, ``/stack``, and the
once-an-update ``/weights_bf16``): what the rollout's loop does beside the
work its body names, which no other record decomposes. It was added to
catch copies of a policy's carried state from one step to the next; what it
found (PERF.md section 6, PR 31) is the waits of the compiler's own
asynchronous copies: slices of the feed-forward weights fetched ahead of
their products, one ring buffer copied out of fast memory a step."""

from benchmark import scopes

PARTS = ("ROLLOUT_POLICY", "ROLLOUT_SAMPLE", "ROLLOUT_ENV_STEP",
         "ROLLOUT_STACK", "ROLLOUT_WEIGHTS_BF16")
ROW = {
    "name": "carry_copy_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap = scopes.capture(ctx)
    if cap is None:
        return None
    try:
        parts = [scopes.share(ctx, a) for a in PARTS]
    except (AttributeError, KeyError):
        return None  # a program from before these scopes
    print("carry_copy_time_share: rollout "
          f"{scopes.share(ctx, 'ROLLOUT'):.3f} %, of it "
          + scopes.shares_line(ctx, *PARTS))
    return scopes.share(ctx, "ROLLOUT") - sum(parts)
