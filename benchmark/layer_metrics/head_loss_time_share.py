"""Share of the device's op time under ``head`` (final norm, the tied
product against 16,384 embedding rows, the value head) and ``learner/loss``
(the A2C loss over ``[4096, 16384]`` float32 logits a chunk)."""

from benchmark import scopes, scopes_lm

ROW = {
    "name": "head_loss_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    head = scopes_lm.share(ctx, "HEAD")
    if head is None:
        return None
    loss = scopes.share(ctx, "LEARNER_LOSS")
    print("head_loss_time_share: " + scopes_lm.line(ctx, "HEAD")
          + f", learner/loss {loss:.3f} %")
    return head + loss
