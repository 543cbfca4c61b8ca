"""One generic reconcile loop over the whole topology (docs/topology.md).

Every controller this repo grew — FleetSupervisor, Autoscaler,
LearnerSupervisor, PodSupervisor, ReplicaSet/ReplicaAutoscaler/
PromotionController — is the same loop wearing a different idiom: watch
the live state, compare against the desired state, act through a
factory. This module is that loop ONCE: resources implement the
:class:`Reconcilable` protocol (observe → diff → act → retire) as thin
adapters over the EXISTING machinery (FleetSupervisor slots,
PodSupervisor hosts, ReplicaSet incarnations, the LearnerSupervisor
resume gate), and one :class:`Reconciler` thread ticks them all:

- **observe** returns a plain-dict snapshot of the live state (process
  table, the masters'/router's own health accounts, telemetry);
- **diff** is a PURE function of that snapshot — desired vs live → the
  exact action list (the deterministic unit suite in
  tests/test_reconcile.py pins it);
- **act** executes one action through the existing factories, under a
  per-resource exponential backoff (a failing respawn retries next tick,
  later and later) and a topology-wide restart-budget circuit breaker
  (a crash loop anywhere degrades to a visible incident, never a fork
  storm);
- every decision is flight-recorded WITH its input snapshot, so the
  postmortem shows what the loop saw when it acted.

Telemetry lands under the ``reconciler`` role (docs/observability.md):
``reconcile_actions_total``, ``reconcile_drift_gauge``, per-resource
heal counters, circuit state.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import threading
import time
import weakref
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.orchestrate.topology import ReconcilePolicy
from distributed_ba3c_tpu.utils import logger
from distributed_ba3c_tpu.utils.concurrency import StoppableThread

#: resource kinds with a dedicated heal counter (literal names so the
#: ba3cwire W5 catalog check sees every series; an unknown kind falls
#: back to the generic action counter only)
HEAL_KINDS = ("fleet", "pod", "learner", "serving")

#: verbs that count against the restart budget — healing state changes,
#: as opposed to policy evaluations ("tick") which are free
HEAL_VERBS = ("spawn", "respawn", "kill", "replace", "re-arm", "scale")


@dataclasses.dataclass(frozen=True)
class Action:
    """One reconcile decision: what to do to which resource, and why."""

    verb: str
    resource: str
    reason: str = ""
    detail: Tuple[Tuple[str, Any], ...] = ()

    @classmethod
    def make(cls, verb: str, resource: str, reason: str = "", **detail):
        return cls(
            verb=verb, resource=resource, reason=reason,
            detail=tuple(sorted(detail.items())),
        )

    def detail_dict(self) -> Dict[str, Any]:
        return dict(self.detail)


class Reconcilable:
    """The one controller protocol (duck-typed; this base documents it).

    ``kind`` buckets the resource's heal counter (``fleet``/``pod``/
    ``learner``/``serving``/``policy``); ``name`` is its identity in
    actions and flight events. ``observe()`` must not mutate; ``diff``
    must be pure in the observation; ``act`` performs exactly one
    action's worth of work through the existing factories; ``retire``
    releases everything (idempotent).
    """

    kind: str = ""
    name: str = ""

    def prepare(self) -> None:
        """Bring up the initial desired state (called from
        Reconciler.start, before the loop runs)."""

    def observe(self) -> Dict[str, Any]:
        raise NotImplementedError

    def diff(self, observed: Dict[str, Any]) -> List[Action]:
        raise NotImplementedError

    def act(self, action: Action) -> None:
        raise NotImplementedError

    def retire(self) -> None:
        """Release the resource (teardown; idempotent)."""


# --------------------------------------------------------------------------
# the pure diff functions (the deterministic unit suite's surface)
# --------------------------------------------------------------------------

def diff_fleet(name: str, obs: Dict[str, Any]) -> List[Action]:
    """Desired vs live for a supervised fleet (env servers or pod hosts).

    Order is the supervisor's own: wedged slots die first (they hold wire
    identities), then due vacancies respawn; backoff-parked vacancies are
    DRIFT but not actions (their retry time has not come). A supervisor
    whose own circuit is open parks everything except wedge kills.
    """
    out: List[Action] = []
    for ident in obs.get("wedged", ()):
        out.append(Action.make(
            "kill", name, reason="wedged (alive but pruned)", ident=ident,
        ))
    if obs.get("circuit_open"):
        return out
    for idx in obs.get("vacant_due", ()):
        verb = "spawn" if not obs.get("ever_started", True) else "respawn"
        out.append(Action.make(
            verb, name, reason="slot vacant and due", slot=idx,
        ))
    delta = int(obs.get("scale_delta", 0))
    if delta:
        out.append(Action.make(
            "scale", name,
            reason=str(obs.get("scale_reason", "autoscale")),
            delta=delta,
        ))
    return out


def diff_learner(name: str, obs: Dict[str, Any]) -> List[Action]:
    """The learner's failover state machine, as a diff.

    done/given-up topologies want nothing; a stalled attempt is killed
    (the resume path takes over next tick); a dead-or-never-started
    learner re-arms through the resume gate — ``--load`` exactly when a
    finalized checkpoint exists.
    """
    if obs.get("done") or obs.get("given_up"):
        return []
    if obs.get("running"):
        if obs.get("stalled"):
            return [Action.make(
                "kill", name, reason="stall watchdog",
                attempt=obs.get("attempt"),
            )]
        return []
    return [Action.make(
        "re-arm", name,
        reason=(
            "resume from finalized checkpoint"
            if obs.get("finalized_step") is not None
            else "start from scratch (no finalized checkpoint)"
        ),
        attempt=obs.get("attempt"),
        resume_step=obs.get("finalized_step"),
    )]


def diff_serving(name: str, obs: Dict[str, Any]) -> List[Action]:
    """Dead replicas are replaced 1:1 (heal-to-count rides the same
    act), and a set short of its floor grows back."""
    out: List[Action] = []
    for rid in obs.get("dead", ()):
        out.append(Action.make(
            "replace", name, reason="router declared replica dead",
            replica=rid,
        ))
    shortfall = int(obs.get("min_replicas", 1)) - int(obs.get("target", 0))
    if shortfall > 0 and not obs.get("dead"):
        out.append(Action.make(
            "spawn", name, reason="replica set below floor", n=shortfall,
        ))
    return out


# --------------------------------------------------------------------------
# resource adapters over the existing controllers
# --------------------------------------------------------------------------

class FleetResource(Reconcilable):
    """FleetSupervisor (or PodSupervisor — same slot machinery, whole
    host groups) as a Reconcilable. The supervisor thread is NOT started;
    the reconciler owns the tick. One underlying ``tick()`` call heals
    every action in a round (the supervisor's slot pass is atomic by
    design), so acts after the first in a round are satisfied no-ops.
    """

    def __init__(self, name: str, supervisor, kind: str = "fleet",
                 scale_intent: Optional[Callable[[], Tuple[int, str]]] = None):
        self.kind = kind
        self.name = name
        self.supervisor = supervisor
        # optional () -> (delta, reason) hook for an external scale
        # driver (tests, the bench); the production autoscalers ride as
        # PolicyResources and call scale_by through their own tick
        self.scale_intent = scale_intent
        self._ticked_in_round = False

    def prepare(self) -> None:
        self.supervisor.spawn_initial()

    def observe(self) -> Dict[str, Any]:
        self._ticked_in_round = False
        obs = self.supervisor.observe()
        if self.scale_intent is not None:
            delta, reason = self.scale_intent()
            if delta:
                obs["scale_delta"] = delta
                obs["scale_reason"] = reason
        return obs

    def diff(self, observed: Dict[str, Any]) -> List[Action]:
        return diff_fleet(self.name, observed)

    def act(self, action: Action) -> None:
        if action.verb == "scale":
            self.supervisor.scale_by(
                int(action.detail_dict()["delta"]), reason=action.reason
            )
            return
        if not self._ticked_in_round:
            self._ticked_in_round = True
            self.supervisor.tick()

    def retire(self) -> None:
        self.supervisor.close()


class LearnerResource(Reconcilable):
    """LearnerSupervisor's resume gate, reconciler-ticked: the attempt
    runs as a non-blocking child; death re-arms through the finalized-
    checkpoint gate with the SAME accounting as the blocking loop."""

    kind = "learner"

    def __init__(self, name: str, supervisor):
        self.name = name
        self.supervisor = supervisor
        self._done = False
        self._given_up = False
        self._final_rc: Optional[int] = None

    @property
    def final_rc(self) -> Optional[int]:
        """0 once the learner finished cleanly; the fatal rc after a
        give-up; None while supervision is still live."""
        return self._final_rc

    def observe(self) -> Dict[str, Any]:
        sup = self.supervisor
        from distributed_ba3c_tpu.orchestrate.learner import finalized_step

        return {
            "kind": "learner",
            "running": sup.attempt_running(),
            "stalled": sup.attempt_stalled(),
            "attempt": sup.attempt,
            "finalized_step": finalized_step(sup.ckpt_dir),
            "done": self._done,
            "given_up": self._given_up,
        }

    def diff(self, observed: Dict[str, Any]) -> List[Action]:
        return diff_learner(self.name, observed)

    def act(self, action: Action) -> None:
        sup = self.supervisor
        if action.verb == "kill":
            sup.kill_attempt(reason="stall")
            return
        # re-arm: account the previous attempt's death (if any), then
        # relaunch through the resume gate — unless the budget is spent
        rc = sup.reap_attempt()
        if rc is not None:
            verdict = sup.note_exit(rc)
            if verdict == "done":
                self._done, self._final_rc = True, 0
                return
            if verdict == "giveup":
                self._given_up, self._final_rc = True, rc
                return
        sup.start_attempt()

    def retire(self) -> None:
        self.supervisor.terminate_attempt()


class ServingResource(Reconcilable):
    """ReplicaSet incarnations as a Reconcilable: the set's own corpse-
    sweeper thread is NOT started (``ReplicaSet.start(n,
    reconcile_thread=False)``); the router's health verdicts drive the
    diff and ``ReplicaSet.reconcile()`` is the act."""

    kind = "serving"

    def __init__(self, name: str, replica_set):
        self.name = name
        self.replica_set = replica_set
        self._healed_in_round = False

    def observe(self) -> Dict[str, Any]:
        self._healed_in_round = False
        rs = self.replica_set
        states = rs.router.replica_states()
        live = rs.replica_ids()
        return {
            "kind": "serving",
            "target": len(live),
            "min_replicas": rs.min_replicas,
            "max_replicas": rs.max_replicas,
            "dead": tuple(r for r in live if states.get(r) == "dead"),
            "states": dict(states),
        }

    def diff(self, observed: Dict[str, Any]) -> List[Action]:
        return diff_serving(self.name, observed)

    def act(self, action: Action) -> None:
        if self._healed_in_round:
            return
        self._healed_in_round = True
        if action.verb == "spawn":
            self.replica_set.scale_to(
                self.replica_set.min_replicas, reason=action.reason
            )
        else:
            self.replica_set.reconcile()

    def retire(self) -> None:
        # the router owns the set's close in cli.py (router.replica_set);
        # a bench-owned set retires here
        pass


class PolicyResource(Reconcilable):
    """A periodic control loop (ReplicaAutoscaler, PromotionController —
    anything with ``tick()``) ridden by the reconciler at its own
    interval. Policy evaluations are counted, not flight-spammed: the
    policies flight-record their own decisions."""

    kind = "policy"

    def __init__(self, name: str, controller, interval_s: float = 2.0):
        self.name = name
        self.controller = controller
        self.interval_s = max(0.0, float(interval_s))
        # -inf, not 0.0: time.monotonic() counts from boot, so on a freshly
        # started machine "0.0 is long ago" is false for the first interval
        self._last_tick = float("-inf")

    def observe(self) -> Dict[str, Any]:
        return {"kind": "policy", "due": (
            time.monotonic() - self._last_tick >= self.interval_s
        )}

    def diff(self, observed: Dict[str, Any]) -> List[Action]:
        if observed.get("due"):
            return [Action.make("tick", self.name, reason="interval elapsed")]
        return []

    def act(self, action: Action) -> None:
        self._last_tick = time.monotonic()
        self.controller.tick()

    def retire(self) -> None:
        stop = getattr(self.controller, "stop", None)
        if stop is not None:
            try:
                stop()
            except Exception:
                pass


# --------------------------------------------------------------------------
# the loop
# --------------------------------------------------------------------------

class _ResourceState:
    __slots__ = ("failures", "next_act_t")

    def __init__(self):
        self.failures = 0
        self.next_act_t = 0.0


class Reconciler(StoppableThread):
    """One loop, every resource: observe → diff → act, per-resource
    exponential backoff, topology-wide circuit breaker, every decision
    flight-recorded with its input snapshot.

    Satisfies the StartProcOrThread protocol (start/stop/join/close), so
    cli.py appends ONE startable where five controllers used to ride.
    ``tick_once()`` is public: tests and the bench drive the loop
    deterministically without the thread.
    """

    def __init__(
        self,
        policy: Optional[ReconcilePolicy] = None,
        resources: Iterable[Reconcilable] = (),
        tele_role: str = "reconciler",
    ):
        super().__init__(daemon=True, name="Reconciler")
        self.policy = policy or ReconcilePolicy()
        self._resources: List[Reconcilable] = []
        self._state: Dict[str, _ResourceState] = {}
        self._lock = threading.Lock()
        self._heal_times: collections.deque = collections.deque()
        self._circuit_open = self.policy.restart_budget == 0
        self._flight = telemetry.flight_recorder()
        tele = telemetry.registry(tele_role)
        self._c_ticks = tele.counter("reconcile_ticks_total")
        self._c_actions = tele.counter("reconcile_actions_total")
        self._c_policy = tele.counter("reconcile_policy_ticks_total")
        self._c_errors = tele.counter("reconcile_errors_total")
        self._c_skipped = tele.counter("reconcile_skipped_total")
        self._c_trips = tele.counter("reconcile_circuit_trips_total")
        self._c_heal = {
            "fleet": tele.counter("reconcile_heal_fleet_total"),
            "pod": tele.counter("reconcile_heal_pod_total"),
            "learner": tele.counter("reconcile_heal_learner_total"),
            "serving": tele.counter("reconcile_heal_serving_total"),
        }
        self._g_drift = tele.gauge("reconcile_drift_gauge")
        ref = weakref.ref(self)
        tele.gauge(
            "reconcile_circuit_open",
            fn=lambda: int(s._circuit_open) if (s := ref()) else 0,
        )
        for r in resources:
            self.add(r)

    # -- assembly ----------------------------------------------------------
    def add(self, resource: Reconcilable) -> Reconcilable:
        if not resource.name:
            raise ValueError("a Reconcilable needs a name")
        with self._lock:
            if any(r.name == resource.name for r in self._resources):
                raise ValueError(f"duplicate resource name {resource.name!r}")
            self._resources.append(resource)
            self._state[resource.name] = _ResourceState()
        return resource

    def resources(self) -> List[Reconcilable]:
        with self._lock:
            return list(self._resources)

    @property
    def circuit_open(self) -> bool:
        return self._circuit_open

    # -- lifecycle (StartProcOrThread protocol) ----------------------------
    def start(self) -> None:
        for r in self.resources():
            r.prepare()
        super().start()
        logger.info(
            "reconciler up: %d resources (%s), budget %d/%gs",
            len(self._resources),
            ", ".join(f"{r.kind}:{r.name}" for r in self.resources()),
            self.policy.restart_budget, self.policy.budget_window_s,
        )

    def run(self) -> None:
        while not self.stopped():
            try:
                self.tick_once()
            except Exception:
                # the reconcile loop is the component that must not die
                # of one bad tick — log and keep reconciling
                logger.exception("reconcile tick failed")
            self._stop_evt.wait(self.policy.poll_interval_s)

    def join(self, timeout: Optional[float] = None) -> None:
        if self.is_alive():
            super().join(timeout)

    def close(self) -> None:
        self.stop()
        self.join(timeout=5)
        # retire in reverse add order: serving/policies before the fleets
        # their traffic rides on is the caller's ordering to choose; the
        # guarantee here is every retire runs even when one raises
        for r in reversed(self.resources()):
            try:
                r.retire()
            except Exception:
                logger.exception("retire of %s failed", r.name)

    # -- the tick ----------------------------------------------------------
    def tick_once(self) -> List[Action]:
        """One full observe→diff→act pass over every resource; returns
        the actions EXECUTED (skips and backoff parks excluded)."""
        now = time.monotonic()
        self._c_ticks.inc()
        executed: List[Action] = []
        drift = 0
        for res in self.resources():
            st = self._state[res.name]
            try:
                obs = res.observe()
                actions = res.diff(obs)
            except Exception:
                self._c_errors.inc()
                logger.exception("observe/diff of %s failed", res.name)
                continue
            heal_actions = [a for a in actions if a.verb != "tick"]
            drift += len(heal_actions)
            if heal_actions and now < st.next_act_t:
                # this resource's last act failed: it is parked under
                # exponential backoff, its drift stays on the gauge
                self._c_skipped.inc()
                continue
            for action in actions:
                healing = action.verb != "tick"
                if healing and self._circuit_open:
                    self._c_skipped.inc()
                    continue
                try:
                    res.act(action)
                except Exception as e:
                    st.failures += 1
                    st.next_act_t = now + self.policy.backoff_s(st.failures)
                    self._c_errors.inc()
                    self._flight.record(
                        "reconcile_act_error",
                        resource=res.name, verb=action.verb,
                        error=repr(e)[:200], failures=st.failures,
                        retry_in_s=round(st.next_act_t - now, 2),
                    )
                    logger.exception(
                        "act %s on %s failed (failure #%d, retry in %.1fs)",
                        action.verb, res.name, st.failures,
                        st.next_act_t - now,
                    )
                    break  # park the resource; later actions wait too
                else:
                    if healing:
                        st.failures = 0
                        st.next_act_t = 0.0
                        self._c_actions.inc()
                        if res.kind in self._c_heal:
                            self._c_heal[res.kind].inc()
                        if action.verb in HEAL_VERBS:
                            self._heal_times.append(time.monotonic())
                        # the decision AND what the loop saw when it made
                        # it — the postmortem is the artifact
                        self._flight.record(
                            "reconcile_action",
                            resource=res.name, resource_kind=res.kind,
                            verb=action.verb, reason=action.reason,
                            detail=action.detail_dict(),
                            snapshot=_json_safe(obs),
                        )
                        executed.append(action)
                    else:
                        self._c_policy.inc()
        self._update_circuit(time.monotonic())
        self._g_drift.set(drift)
        return executed

    def _update_circuit(self, now: float) -> None:
        """FleetSpec's breaker shape, topology-wide: open past the
        budget, half-close when the window drains to half of it."""
        budget = self.policy.restart_budget
        window = self.policy.budget_window_s
        while self._heal_times and now - self._heal_times[0] > window:
            self._heal_times.popleft()
        if budget == 0:
            return
        if not self._circuit_open and len(self._heal_times) > budget:
            self._circuit_open = True
            self._c_trips.inc()
            self._flight.record(
                "reconcile_circuit_open",
                heals_in_window=len(self._heal_times), budget=budget,
            )
            logger.error(
                "reconcile circuit OPEN: %d heal actions in %.0fs "
                "(budget %d) — healing paused until the window drains",
                len(self._heal_times), window, budget,
            )
        elif self._circuit_open and len(self._heal_times) <= budget // 2:
            self._circuit_open = False
            self._flight.record(
                "reconcile_circuit_close",
                heals_in_window=len(self._heal_times),
            )
            logger.info("reconcile circuit closed (half-open drain)")


def _json_safe(obj: Any, depth: int = 4) -> Any:
    """Snapshots ride the flight ring and the bench artifact: clamp them
    to JSON-able plain data so one exotic observation cannot poison the
    postmortem dump."""
    if depth <= 0:
        return repr(obj)[:80]
    if isinstance(obj, dict):
        return {
            str(k)[:80]: _json_safe(v, depth - 1)
            for k, v in list(obj.items())[:32]
        }
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v, depth - 1) for v in list(obj)[:32]]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        return repr(obj)[:80]
