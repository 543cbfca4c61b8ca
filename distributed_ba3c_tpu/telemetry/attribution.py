"""Reads of the live registries that say WHERE a plane stands: progress
summed over a role's fleets or hosts, and the dead stage named from the
counters when a plane produces nothing (docs/observability.md). The plane
instruments (``scripts/plane_bench.py``, ``chaos_bench.py``, ``pod_bench.py``,
``netchaos/bench.py``) raise with :func:`stall_attribution` in the message.
"""

from __future__ import annotations

from distributed_ba3c_tpu.telemetry import metrics


def tele_snapshot() -> dict:
    """Compact final telemetry snapshot embedded in every bench JSON:
    counters/gauges as scalars per role (histograms as _count/_sum)."""
    snap = {
        role: reg.scalars()
        for role, reg in sorted(metrics.all_registries().items())
    }
    return {role: s for role, s in snap.items() if s}


def role_scalars(base: str) -> dict:
    """Summed counters/gauges over ``base`` AND its dotted sub-roles
    (``master`` + ``master.f0``/``master.f1``/... — telemetry.fleet_role;
    ``pod`` + ``pod.host0``/``pod.host1``/... — pod/wire.py pod_role):
    the bench's progress/attribution reads must see the WHOLE plane, not
    one fleet (or one actor host) of it."""
    out: dict = {}
    for role, reg in metrics.all_registries().items():
        if role != base and not role.startswith(f"{base}."):
            continue
        for name, v in reg.scalars().items():
            out[name] = out.get(name, 0.0) + v
    return out


def master_progress() -> tuple:
    """(wire messages, datapoints) from the master registries — the plane's
    provable forward motion, read lock-free off the live counters."""
    s = role_scalars("master")
    msgs = (
        s.get("per_env_msgs_total", 0)
        + s.get("block_msgs_total", 0)
        + s.get("block_shm_msgs_total", 0)
    )
    return msgs, s.get("datapoints_total", 0)


def stall_attribution() -> str:
    """Name the dead stage from the real counters (the bare time threshold
    used to be the whole diagnosis; now it only opens the case)."""
    m = role_scalars("master")
    p = role_scalars("predictor")
    msgs, dps = master_progress()
    depth = m.get("train_queue_depth", 0)
    parts = (
        f"wire_msgs={msgs:.0f} datapoints={dps:.0f} "
        f"train_queue_depth={depth:.0f} "
        f"predictor_batches={p.get('batches_total', 0):.0f} "
        f"blocked_puts={m.get('queue_blocked_puts_total', 0):.0f}"
    )
    if not metrics.enabled():
        return f"telemetry disabled, no attribution ({parts})"
    if msgs == 0:
        return f"no wire traffic: env servers never connected or died ({parts})"
    if p.get("batches_total", 0) == 0:
        return f"wire traffic but predictor never served ({parts})"
    if dps == 0:
        return f"predictor serving but no datapoints: flush path stalled ({parts})"
    return f"plane went quiet after progress ({parts})"
