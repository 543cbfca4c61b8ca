"""Multi-fleet actor-plane assembly (docs/actor_plane.md, ISSUE 10).

One learner, N independent actor fleets: each fleet is a complete plane —
its own pipe pair, its own master (receive loop + train queue), its own
batched predictor, its own supervisor/autoscaler, its own telemetry
identity (``telemetry.fleet_role``) — and the fleet-merge layer
(data/dataflow.py ``FleetMergeFeed``) drains the per-fleet queues into one
macro-batch train stream. Why whole planes instead of one wider plane: the
macro steps (parallel/train_step.py ``make_macro_train_step`` and friends)
shard the FLEET axis over the mesh's data axis, so a data-parallel
deployment assigns fleets — not batch slivers — to chips and every chip
steps at its full-occupancy batch while the per-fleet recipe stays fixed
(the PERF.md 65.6k -> ~38k shard-ladder fix, ROADMAP item 1).

Isolation comes from the addressing scheme, not new machinery:

- **pipes**: :func:`fleet_pipes` derives per-fleet endpoints (fleet 0 keeps
  the base addresses, so single-fleet runs are byte-identical);
- **ring names**: ``utils/shm.py ring_name`` hashes the fleet's c2s
  address, so per-fleet pipes namespace the /dev/shm rings with the SAME
  formula the supervisor reclaims by — nothing new to drift;
- **idents**: callers tag server ident prefixes with ``f<k>-`` so the
  telemetry sender table (telemetry/wire.py) keeps per-fleet senders
  distinct;
- **telemetry**: per-fleet roles ``master.f<k>`` / ``predictor.f<k>`` /
  ``fleet.f<k>`` — the scrape label one ``http_signals`` consumer uses to
  address one master among several on a host.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, List, Optional, Tuple

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.utils import logger

_TCP_RE = re.compile(r"^(tcp://[^:]+:)(\d+)$")


def fleet_pipes(pipe_c2s: str, pipe_s2c: str, fleet: int) -> Tuple[str, str]:
    """Per-fleet wire addresses derived from the base pipe pair.

    Fleet 0 keeps the base addresses unchanged — a single-fleet run and
    fleet 0 of a multi-fleet run bind identically, so external env-server
    launch lines keep working. ``tcp://host:port`` endpoints step the port
    by ``2 * fleet`` (even stride so the conventional adjacent c2s/s2c
    pair — e.g. 5555/5556 — never collides across fleets; operators open
    the contiguous range); every other transport (ipc://, inproc://) gets
    a ``-f<k>`` path suffix. :func:`build_fleet_planes` validates the
    derived set for collisions, so an unconventional base spacing fails
    loudly at assembly, not as a silent double-bind.
    """
    if fleet == 0:
        return pipe_c2s, pipe_s2c

    def derive(addr: str) -> str:
        m = _TCP_RE.match(addr)
        if m:
            return f"{m.group(1)}{int(m.group(2)) + 2 * fleet}"
        return f"{addr}-f{fleet}"

    return derive(pipe_c2s), derive(pipe_s2c)


class FanoutPredictors:
    """The learner-side facade over K per-fleet predictors.

    ``update_params`` fans the publish out to every fleet WITHOUT
    blocking the caller: one latest-wins pump thread per predictor
    (utils/concurrency.py :class:`LatestWinsPump`), so a slow or wedged
    fleet's predictor stalls only its own pump — never the learner's
    publish path, and never the OTHER fleets' publishes. Skipped
    intermediate versions are correct by construction (latest wins per
    policy: nobody should ever serve a version the learner has already
    superseded) and counted as ``fanout_publishes_coalesced_total``.
    Synchronous reads (``predict_batch`` — the Evaluator path) delegate
    to fleet 0, whose policy is identical after any settled publish.
    ``flush()`` is the barrier for callers that need settledness (tests,
    checkpoint-restore republish); ``close()`` stops the pumps.
    """

    def __init__(self, predictors: List[Any]):
        if not predictors:
            raise ValueError("FanoutPredictors needs at least one predictor")
        self.predictors = list(predictors)
        from distributed_ba3c_tpu.utils.concurrency import LatestWinsPump

        tele = telemetry.registry("learner")
        self._c_publishes = tele.counter("fanout_publishes_total")
        self._c_coalesced = tele.counter("fanout_publishes_coalesced_total")
        self._c_errors = tele.counter("fanout_publish_errors_total")
        # fan-out facade, not a new publish path: the ONE sanctioned
        # caller (Trainer._publish_params) owns the version accounting;
        # the pumps only multiply its publish across fleets
        self._pumps = [
            LatestWinsPump(
                apply=lambda policy, params, _p=pred: _p.update_params(
                    params, policy=policy
                ),
                name=f"param-fanout-{k}",
                on_coalesce=self._c_coalesced.inc,
                on_error=lambda e, _k=k: self._publish_error(_k, e),
            )
            for k, pred in enumerate(self.predictors)
        ]
        for p in self._pumps:
            p.start()

    def _publish_error(self, fleet: int, e: Exception) -> None:
        # a failing publish means this fleet's actors keep sampling a
        # FROZEN policy — counted, flight-recorded AND logged, so the
        # async pump never turns the old synchronous loud-failure path
        # into a silent one
        self._c_errors.inc()
        telemetry.flight_recorder().record(
            "fanout_publish_error", fleet=fleet, error=repr(e)
        )
        logger.error(
            "param fan-out to fleet %d predictor FAILED (its actors are "
            "sampling a stale policy until a publish succeeds): %r",
            fleet, e,
        )

    @property
    def num_actions(self) -> int:
        return self.predictors[0].num_actions

    def update_params(self, params, policy: str = "default") -> None:
        for pump in self._pumps:
            pump.publish(policy, params)
        self._c_publishes.inc()

    def flush(self, timeout: float = 5.0) -> bool:
        """Wait until every fleet applied the latest publish (False if a
        predictor stayed wedged past ``timeout`` — the caller keeps its
        thread either way; that is the whole point of the pumps)."""
        ok = True
        for pump in self._pumps:
            ok = pump.flush(timeout) and ok
        return ok

    # StartProcOrThread protocol: the facade owns pump THREADS now, so it
    # must ride the trainer lifecycle (cli puts it first in startables:
    # the pumps stop before any predictor they publish into does)
    def start(self) -> None:
        """No-op: the pumps spin up in ``__init__`` so pre-train
        publishes (checkpoint restore) already fan out."""

    def stop(self) -> None:
        for pump in self._pumps:
            pump.stop()

    def join(self, timeout: Optional[float] = None) -> None:
        for pump in self._pumps:
            pump.join(timeout)

    def close(self) -> None:
        for pump in self._pumps:
            pump.stop()

    def predict_batch(self, states):
        return self.predictors[0].predict_batch(states)


@dataclasses.dataclass
class FleetPlane:
    """One fleet's assembled plane (what build_fleet_planes returns)."""

    fleet: int
    pipe_c2s: str
    pipe_s2c: str
    predictor: Any
    master: Any
    supervisor: Any = None
    autoscaler: Any = None
    # NOTE deliberately no per-plane startables() convenience: start order
    # is a CROSS-plane contract (every fleet's predictor+master, then the
    # merge feed, then supervisors/autoscalers — spawning any fleet's
    # servers before every master's receive loop is live would park them
    # in their first recv), so the caller assembling all planes owns it
    # (cli.py)


def build_fleet_planes(
    n_fleets: int,
    pipe_c2s: str,
    pipe_s2c: str,
    make_predictor: Callable[[int, str], Any],
    make_master: Callable[[int, str, str, Any, str], Any],
    make_supervision: Optional[
        Callable[[int, str, str, Any], Tuple[Any, Any]]
    ] = None,
) -> List[FleetPlane]:
    """Assemble K per-fleet actor planes behind one learner.

    Factories (all fleet-indexed, handed the derived addresses and the
    fleet's telemetry role):

    - ``make_predictor(fleet, tele_role)`` — the fleet's BatchedPredictor,
      warmed by the caller;
    - ``make_master(fleet, c2s, s2c, predictor, tele_role)`` — the fleet's
      SimulatorMaster subclass (owns its train queue);
    - ``make_supervision(fleet, c2s, s2c, master)`` — optional
      ``(FleetSupervisor, Autoscaler-or-None)`` pair for locally-hosted
      fleets (external fleets pass None and supervise on their own hosts).

    Single-fleet (``n_fleets == 1``) assemblies keep the legacy telemetry
    roles (``master``/``predictor``) so every existing dashboard, signal
    scrape and test reads unchanged; only a real multi-fleet run grows the
    ``.f<k>`` label space.

    This function is the sanctioned multi-fleet spawn point: ba3clint A8
    flags direct calls outside ``orchestrate/`` the same way it flags
    direct env-server construction — cli.py and scripts/plane_bench.py carry the
    sanctioned suppressions (factories handed to supervisors, and the raw
    measurand plane).
    """
    if n_fleets < 1:
        raise ValueError(f"n_fleets must be >= 1, got {n_fleets}")
    pipes = [fleet_pipes(pipe_c2s, pipe_s2c, k) for k in range(n_fleets)]
    flat = [a for pair in pipes for a in pair]
    if len(set(flat)) != len(flat):
        raise ValueError(
            f"derived fleet pipe addresses collide across {n_fleets} fleets "
            f"({flat}) — space the base tcp ports at least {2 * n_fleets} "
            "apart between c2s and s2c, or use distinct hosts/paths"
        )
    planes: List[FleetPlane] = []
    for k in range(n_fleets):
        c2s_k, s2c_k = pipes[k]
        tag = k if n_fleets > 1 else None  # single fleet keeps legacy roles
        predictor = make_predictor(k, telemetry.fleet_role("predictor", tag))
        master = make_master(
            k, c2s_k, s2c_k, predictor, telemetry.fleet_role("master", tag)
        )
        supervisor = autoscaler = None
        if make_supervision is not None:
            supervisor, autoscaler = make_supervision(k, c2s_k, s2c_k, master)
        planes.append(
            FleetPlane(
                fleet=k, pipe_c2s=c2s_k, pipe_s2c=s2c_k,
                predictor=predictor, master=master,
                supervisor=supervisor, autoscaler=autoscaler,
            )
        )
    return planes
