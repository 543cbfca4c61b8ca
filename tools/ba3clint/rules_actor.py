"""A-series rules: the actor plane's concurrency conventions, machine-checked.

``utils/concurrency.py`` asserts "message passing only, no shared mutable
state" in a docstring; these rules are that docstring as code. Rationale and
worked examples live in docs/static_analysis.md.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Set

from tools.ba3clint.engine import (
    FileContext,
    Finding,
    Rule,
    ancestors,
    chain_root,
    dotted_name,
    enclosing_functions,
    enclosing_statement,
    parent,
)

_THREAD_CTORS = {"threading.Thread"}
_PROC_CTORS = {"multiprocessing.Process", "multiprocessing.context.Process"}


class BareThreadRule(Rule):
    """A1: bare ``threading.Thread``/``mp.Process`` instantiation.

    A bare thread has no stop flag: shutdown can only kill it by exiting the
    interpreter, and a leaked thread wedges later in-process jit dispatch
    (the round-1 pytest deadlock). Use ``StoppableThread``/``LoopThread``
    from ``utils.concurrency`` (threads) or a process that is registered
    with ``ensure_proc_terminate`` — or suppress with the justification for
    why this thread's lifetime is otherwise bounded.
    """

    id = "A1"
    name = "bare-thread"
    summary = "bare threading.Thread/mp.Process where a stoppable wrapper is required"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            if resolved in _THREAD_CTORS:
                yield ctx.finding(
                    self, node,
                    "bare threading.Thread has no stop flag — use "
                    "StoppableThread/LoopThread (utils.concurrency) so "
                    "shutdown can be observed",
                )
            elif resolved in _PROC_CTORS:
                yield ctx.finding(
                    self, node,
                    "bare multiprocessing.Process — use a managed process "
                    "(ensure_proc_terminate + start_proc_mask_signal)",
                )


_QUEUEISH_EXACT = {"q", "_q", "_out", "out_q", "outq", "in_q", "inq"}


def _queueish(recv: ast.AST) -> bool:
    if isinstance(recv, ast.Attribute):
        last = recv.attr
    elif isinstance(recv, ast.Name):
        last = recv.id
    else:
        return False
    low = last.lower()
    return "queue" in low or low in _QUEUEISH_EXACT


def _has_kw(call: ast.Call, name: str) -> bool:
    return any(kw.arg == name for kw in call.keywords)


def _nonblocking(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg == "block" and isinstance(kw.value, ast.Constant):
            if kw.value.value is False:
                return True
    return False


class BlockingQueueOpRule(Rule):
    """A2: blocking ``get()``/``put()`` without a timeout on a queue.

    A get/put with no timeout blocks forever if the peer thread died — the
    stop flag is never re-checked and shutdown wedges. Every queue op in the
    actor plane must either carry a ``timeout=`` (and loop on the stop flag:
    see ``queue_get_stoppable``/``queue_put_stoppable``) or be the
    ``_nowait`` variant.
    """

    id = "A2"
    name = "blocking-queue-op"
    summary = "Queue.get()/put() with no timeout wedges shutdown"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute):
                continue
            if fn.attr == "get":
                # dict.get(key) takes positional args; Queue.get() does not
                if node.args or _has_kw(node, "timeout") or _nonblocking(node):
                    continue
                if not _queueish(fn.value):
                    continue
                yield ctx.finding(
                    self, node,
                    "blocking Queue.get() with no timeout — pass timeout= "
                    "and re-check the stop flag (queue_get_stoppable)",
                )
            elif fn.attr == "put":
                if not node.args or _has_kw(node, "timeout") or _nonblocking(node):
                    continue
                if not _queueish(fn.value):
                    continue
                yield ctx.finding(
                    self, node,
                    "blocking Queue.put() with no timeout — pass timeout= "
                    "and re-check the stop flag (queue_put_stoppable)",
                )


_MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "setdefault", "popitem", "add", "discard",
}


def _mentions_clients_subscript(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript):
            base = dotted_name(sub.value)
            if base and (base == "clients" or base.endswith(".clients")):
                return True
    return False


class CrossThreadClientMutationRule(Rule):
    """A3: shared client-table state mutated from a closure.

    Closures handed to the predictor (``put_task`` callbacks) run on a
    predictor worker thread. Mutating per-client state (``client.memory``,
    ``client.score``, the ``clients`` table itself) from there is only safe
    when the wire protocol serializes it (the simulator is blocked awaiting
    its action). That invariant lives outside the code — so every such
    mutation must either go through a lock/queue or carry a suppression
    whose justification states the serialization argument. The runtime
    sanitizer (utils/sanitizer.py, BA3C_SANITIZE=1) checks the table half
    of the claim in tests.
    """

    id = "A3"
    name = "cross-thread-client-mutation"
    summary = "client-table state mutated from a closure running on another thread"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not enclosing_functions(node):
                continue  # only closures (nested defs) run on foreign threads
            yield from self._check_closure(ctx, node)

    def _check_closure(self, ctx: FileContext, fn: ast.AST) -> Iterator[Finding]:
        tracked: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _mentions_clients_subscript(
                node.value
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        tracked.add(t.id)

        def is_shared(expr: ast.AST) -> bool:
            root = chain_root(expr)
            if isinstance(root, ast.Name) and root.id in tracked:
                return True
            return _mentions_clients_subscript(expr)

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                f = node.func
                if (
                    isinstance(f, ast.Attribute)
                    and f.attr in _MUTATORS
                    and is_shared(f.value)
                ):
                    yield ctx.finding(
                        self, node,
                        f".{f.attr}() on shared client state from a closure "
                        "(runs on a predictor/worker thread) — needs a "
                        "lock/queue handoff, or a suppression stating the "
                        "protocol-serialization argument",
                    )
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)) and is_shared(t):
                        yield ctx.finding(
                            self, node,
                            "write to shared client state from a closure "
                            "(runs on a predictor/worker thread) — needs a "
                            "lock/queue handoff, or a suppression stating "
                            "the protocol-serialization argument",
                        )
                        break


_SUSPECT_TARGET_FRAGMENTS = (
    "last", "t0", "deadline", "start", "seen", "now", "begin", "expire",
    "elapsed", "heartbeat",
)


class WallClockArithRule(Rule):
    """A4: ``time.time()`` used for interval/timeout arithmetic.

    The wall clock jumps (NTP slew, suspend/resume, leap smearing); a
    heartbeat or timeout computed from ``time.time()`` can mass-expire
    every actor on a clock step (`actors/simulator.py` did exactly this for
    ``last_seen``). Durations and deadlines must use ``time.monotonic()``;
    ``time.time()`` is only for timestamps that leave the process (logs,
    TensorBoard wall_time).
    """

    id = "A4"
    name = "wall-clock-arith"
    summary = "time.time() used for interval/timeout arithmetic instead of time.monotonic()"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if ctx.info.resolve(node.func) != "time.time":
                continue
            if self._in_arith(node) or self._assigned_to_suspect(node):
                yield ctx.finding(
                    self, node,
                    "time.time() in interval/timeout arithmetic — the wall "
                    "clock jumps; use time.monotonic()",
                )

    @staticmethod
    def _in_arith(node: ast.AST) -> bool:
        for cur in ancestors(node):
            if isinstance(cur, (ast.BinOp, ast.Compare)):
                return True
            # the value was swallowed by a call or container before reaching
            # any arithmetic (e.g. json.dumps({"ts": time.time()}) + "\n" is
            # string concat on the *serialized* value, not clock arithmetic)
            if isinstance(
                cur, (ast.Call, ast.Dict, ast.List, ast.Set, ast.Tuple, ast.stmt)
            ):
                return False
        return False

    @staticmethod
    def _assigned_to_suspect(node: ast.AST) -> bool:
        stmt = enclosing_statement(node)
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        for t in targets:
            name = t.attr if isinstance(t, ast.Attribute) else (
                t.id if isinstance(t, ast.Name) else None
            )
            if name and any(
                frag in name.lower() for frag in _SUSPECT_TARGET_FRAGMENTS
            ):
                return True
        return False


def _is_dunder(name: str) -> bool:
    return name.startswith("__") and name.endswith("__")


class PrivateImportRule(Rule):
    """A5: ``from <module> import _private`` — importing underscore names.

    A leading underscore is the module's statement that the name may be
    renamed, re-scoped, or deleted without notice; an external import turns
    that private detail into silent API surface (a script once depended on
    ``devicelock._stderr_print`` exactly this way — ADVICE r5).
    Promote the name to a public one (keep a private alias in the owning
    module if its history matters), or suppress with the justification for
    why the coupling is intended.
    """

    id = "A5"
    name = "private-import"
    summary = "from-import of an underscore-private name couples to another module's internals"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if node.module == "__future__":
                continue
            for alias in node.names:
                nm = alias.name
                if nm.startswith("_") and not _is_dunder(nm):
                    mod = ("." * node.level) + (node.module or "")
                    yield ctx.finding(
                        self, node,
                        f"importing private name {nm!r} from {mod!r} — "
                        "underscore names are the owning module's internals; "
                        "promote it to a public name (keep a private alias) "
                        "or suppress with the coupling justification",
                    )


_WIRE_OPS = {
    "send", "recv", "send_multipart", "recv_multipart", "send_pyobj",
    "recv_pyobj", "send_string", "recv_string", "send_json", "recv_json",
    "send_serialized", "recv_serialized",
}
_SOCKISH_FRAGMENTS = ("sock", "dealer", "router", "push", "pull", "zmq")


def _socket_ish(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name and any(f in name.lower() for f in _SOCKISH_FRAGMENTS):
            return True
    return False


def _target_names(target: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(target) if isinstance(n, ast.Name)}


def _env_indexed_iter(it: ast.AST) -> bool:
    for sub in ast.walk(it):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name and "env" in name.lower():
            return True
    return False


class PerEnvWireLoopRule(Rule):
    """A6: per-element socket send/recv inside a loop over env indices.

    The per-env wire — B sends and B drains per step where ``env.step``
    already produced the whole [B, ...] block — is what pinned the plane at
    2,128 env-steps/s/host (PERF.md round 4); the block wire replaced it
    with 2 socket ops per server per step (docs/actor_plane.md). A wire op
    executed once per env index regresses exactly that, so it must either
    become one batched multipart op outside the loop or carry a suppression
    naming why per-element is intended (the `--wire per-env` compat foil in
    ``envs/native.py`` is the only sanctioned case).
    """

    id = "A6"
    name = "per-env-wire-loop"
    summary = "per-element socket send/recv in a loop over env indices regresses the block wire"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        seen: Set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, ast.For):
                continue
            targets = _target_names(loop.target)
            env_iter = _env_indexed_iter(loop.iter)
            for node in ast.walk(loop):
                if id(node) in seen or not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if not isinstance(fn, ast.Attribute) or fn.attr not in _WIRE_OPS:
                    continue
                if not _socket_ish(fn.value):
                    continue
                if (
                    env_iter
                    or self._loop_var_indexes(node, targets)
                    or self._receiver_is_loop_var(fn.value, targets)
                ):
                    seen.add(id(node))
                    yield ctx.finding(
                        self, node,
                        f"per-element .{fn.attr}() inside a loop over env "
                        "indices — batch the block into ONE multipart "
                        "message per step (see docs/actor_plane.md), or "
                        "suppress with the reason per-element is intended",
                    )

    @staticmethod
    def _loop_var_indexes(call: ast.Call, targets: Set[str]) -> bool:
        # the loop variable used as a subscript INDEX anywhere in the call
        # (`dealers[i].recv()`, `push.send(stacks[i])`) = a per-env element op
        for sub in ast.walk(call):
            if isinstance(sub, ast.Subscript):
                for n in ast.walk(sub.slice):
                    if isinstance(n, ast.Name) and n.id in targets:
                        return True
        return False

    @staticmethod
    def _receiver_is_loop_var(recv: ast.AST, targets: Set[str]) -> bool:
        # iterating the socket collection itself: `for s in dealers: s.send(..)`
        root = chain_root(recv)
        return isinstance(root, ast.Name) and root.id in targets


#: identifier TOKENS (underscore-split) that mark a statement as metric
#: accounting. Whole tokens, not substrings: "rate" must catch `msg_rate`
#: without firing on `learning_rate`-adjacent timestamps via `generate`/
#: `iterate`/`separate` — except learning_rate itself, which token
#: matching would also hit; it is a hyperparameter, not a metric, so it
#: is exempted explicitly below.
_METRIC_NAME_TOKENS = frozenset(
    ("fps", "rate", "throughput", "latency", "persec")
)
_NON_METRIC_NAMES = frozenset(("learning_rate", "lr_rate"))
#: literal-string fragments that mark a print as metric reporting
_METRIC_STRING_FRAGMENTS = (
    "fps", "steps/s", "steps/sec", "/sec", "per sec", "throughput",
    "latency", "qsize",
)


def _string_literals(call: ast.Call) -> Iterator[str]:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                yield sub.value
            elif isinstance(sub, ast.JoinedStr):
                for v in sub.values:
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        yield v.value


class AdhocMetricRule(Rule):
    """A7: ``time.time()``/``print``-based metric accounting outside
    ``telemetry/``.

    The telemetry plane (distributed_ba3c_tpu/telemetry/,
    docs/observability.md) is THE account of rates, latencies and queue
    depths: registry counters feed the scrape endpoint, the stat.json/TB
    bridge, and the fleet piggyback at once. A hand-rolled
    ``fps = n / (time.time() - t0)`` + ``print(...)`` is invisible to all
    three — and wall-clock-based on top (see A4). Route the number through
    ``telemetry.registry(role)`` (Counter/Gauge/Histogram) and let the
    exporters render it; ``print`` stays fine for non-metric output, and
    the rule does not apply inside ``telemetry/`` itself (something has to
    implement the plane).
    """

    id = "A7"
    name = "adhoc-metric"
    summary = "ad-hoc time.time()/print metric accounting bypasses the telemetry registry"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if "telemetry" in ctx.path.replace(os.sep, "/").split("/"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and node.func.id == "print":
                for s in _string_literals(node):
                    low = s.lower()
                    if any(f in low for f in _METRIC_STRING_FRAGMENTS):
                        yield ctx.finding(
                            self, node,
                            "print-based metric reporting — route it "
                            "through telemetry.registry(...) so the scrape "
                            "endpoint / stat.json / fleet series see it",
                        )
                        break
            elif ctx.info.resolve(node.func) == "time.time":
                stmt = enclosing_statement(node)
                if stmt is not None and self._stmt_mentions_metric(stmt):
                    yield ctx.finding(
                        self, node,
                        "time.time()-based metric accounting — use a "
                        "telemetry registry Counter/Histogram (monotonic "
                        "inside) instead of hand-rolled rate math",
                    )

    @staticmethod
    def _stmt_mentions_metric(stmt: ast.stmt) -> bool:
        for sub in ast.walk(stmt):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if not name:
                continue
            low = name.lower()
            if low in _NON_METRIC_NAMES:
                continue
            tokens = low.split("_")
            if not _METRIC_NAME_TOKENS.isdisjoint(tokens):
                return True
            # "per_sec"/"persec" may straddle a token boundary
            if "persec" in low.replace("_", ""):
                return True
        return False


#: fleet-role process classes: anything whose lifecycle the supervisor owns
_FLEET_PROC_SUFFIXES = (".CppEnvServerProcess", ".SimulatorProcess")
_FLEET_PROC_BARE = {"CppEnvServerProcess", "SimulatorProcess"}

#: the multi-fleet assembly entry point (actors/fleet.py): ONE call stands
#: up K masters/predictors and hands K factories to K FleetSupervisors —
#: K fleets' worth of spawns behind one name, so a stray call outside
#: orchestrate/ bypasses K fleets' worth of lifecycle accounting
_FLEET_ASSEMBLY_SUFFIXES = (".build_fleet_planes",)
_FLEET_ASSEMBLY_BARE = {"build_fleet_planes"}

#: fleet-role entry points a subprocess spawn may name
_FLEET_ENTRY_FRAGMENTS = ("train.py", "launch_env_fleet")

_SUBPROCESS_SPAWNERS = {
    "subprocess.Popen", "subprocess.run", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output",
}

_RAW_FORKS = {"os.fork", "os.forkpty", "os.posix_spawn", "os.posix_spawnp"}


class UnsupervisedFleetSpawnRule(Rule):
    """A8: fleet-role process spawned outside ``orchestrate/``.

    The orchestration subsystem (distributed_ba3c_tpu/orchestrate/,
    docs/orchestration.md) owns the fleet lifecycle: respawn with backoff,
    the restart-budget circuit breaker, stale shm-ring reclaim, scale
    accounting as ``tele/orchestrator/*``. A ``CppEnvServerProcess``/
    ``SimulatorProcess`` constructed-and-started directly — or a
    ``subprocess.Popen`` of ``train.py``/``launch_env_fleet`` — bypasses
    all of it: the process that dies stays dead and nothing is accounted.
    The multi-fleet assembly ``build_fleet_planes`` (actors/fleet.py) is
    flagged the same way: one call stands up K fleets of spawns, so a
    stray call multiplies the bypass K-fold.
    Route fleet roles through ``FleetSupervisor``/``LearnerSupervisor``,
    or suppress with the justification for why this spawn's lifecycle is
    otherwise owned (a factory HANDED to the supervisor parameterizes the
    slot rather than spawning it — that is the sanctioned suppression,
    and the one cli.py's build_fleet_planes call site carries).
    ``os.fork`` and friends are flagged unconditionally: the repo is
    spawn-context-only (a fork from the threaded trainer can deadlock the
    child — envs/simulator.py).
    """

    id = "A8"
    name = "unsupervised-fleet-spawn"
    summary = "fleet-role process spawned outside orchestrate/ bypasses the supervisor"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if "orchestrate" in ctx.path.replace(os.sep, "/").split("/"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            if resolved is None:
                continue
            if (
                resolved in _FLEET_PROC_BARE
                or resolved.endswith(_FLEET_PROC_SUFFIXES)
            ):
                yield ctx.finding(
                    self, node,
                    f"direct {resolved.rsplit('.', 1)[-1]} construction — "
                    "fleet-role processes belong to a FleetSupervisor "
                    "(respawn/backoff/scale accounting; "
                    "docs/orchestration.md)",
                )
            elif (
                resolved in _FLEET_ASSEMBLY_BARE
                or resolved.endswith(_FLEET_ASSEMBLY_SUFFIXES)
            ):
                yield ctx.finding(
                    self, node,
                    "multi-fleet assembly (build_fleet_planes) outside "
                    "orchestrate/ — K fleets of spawns need their "
                    "factories supervisor-owned; the sanctioned call "
                    "sites (cli.py's factory-only assembly) carry an "
                    "explicit suppression (docs/actor_plane.md)",
                )
            elif resolved in _RAW_FORKS:
                yield ctx.finding(
                    self, node,
                    f"{resolved}() — the repo is spawn-context-only, and "
                    "fleet roles belong to the orchestrate/ supervisors",
                )
            elif resolved in _SUBPROCESS_SPAWNERS and any(
                frag in s
                for s in _string_literals(node)
                for frag in _FLEET_ENTRY_FRAGMENTS
            ):
                yield ctx.finding(
                    self, node,
                    "subprocess spawn of a fleet-role entry point — a "
                    "supervised learner belongs to LearnerSupervisor "
                    "(checkpoint failover + accounting), a fleet to "
                    "FleetSupervisor (docs/orchestration.md)",
                )


#: queue constructors whose default is UNBOUNDED — in the serving plane an
#: unbounded queue converts overload into unbounded latency instead of the
#: fast typed rejection the SLO contract promises (docs/serving.md)
_QUEUE_CTOR_SUFFIXES = (
    "queue.Queue", "queue.LifoQueue", "queue.PriorityQueue",
    "multiprocessing.Queue", "concurrency.FastQueue",
)
_QUEUE_CTOR_BARE = {"Queue", "LifoQueue", "PriorityQueue", "FastQueue"}

_BLOCKING_SLEEPS = {"time.sleep"}
_CONSOLE_FILE_IO = {"open", "print", "builtins.open", "builtins.print"}


class ServingHotPathBlockRule(Rule):
    """A9: blocking I/O or an unbounded queue inside the serving plane
    (``predict/``).

    The predictor's scheduler/callback path is the latency budget of every
    request the serving tier answers (docs/serving.md): a ``time.sleep``,
    file/console I/O, or a socket op on that path stalls EVERY in-flight
    request behind it, and an unbounded ``queue.Queue`` turns overload
    into unbounded queue latency instead of the fast typed rejection the
    SLO contract promises. Queues in ``predict/`` must be constructed with
    a positive bound (a computed bound like ``maxsize=queue_depth`` is
    accepted); waiting must go through bounded-timeout queue ops
    (``queue_get_stoppable``), never sleeps. The rule applies only to
    files under a ``predict/`` directory — everywhere else A2/A7 own the
    neighboring hazards.
    """

    id = "A9"
    name = "serving-hot-path-block"
    summary = "blocking I/O or unbounded queue inside the predict/ serving plane"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if "predict" not in ctx.path.replace(os.sep, "/").split("/"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            if resolved and (
                resolved in _QUEUE_CTOR_BARE
                or resolved.endswith(_QUEUE_CTOR_SUFFIXES)
            ):
                if not self._bounded(node):
                    yield ctx.finding(
                        self, node,
                        "unbounded queue in the serving plane — overload "
                        "must become fast typed rejection, not unbounded "
                        "latency: construct with a positive maxsize "
                        "(docs/serving.md admission contract)",
                    )
            elif resolved in _BLOCKING_SLEEPS:
                yield ctx.finding(
                    self, node,
                    "time.sleep on the serving path stalls every in-flight "
                    "request behind it — wait via bounded-timeout queue ops "
                    "(queue_get_stoppable) instead",
                )
            elif resolved in _CONSOLE_FILE_IO:
                yield ctx.finding(
                    self, node,
                    f"{resolved}() is blocking file/console I/O on the "
                    "serving path — route diagnostics through telemetry/"
                    "logger outside predict/",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _WIRE_OPS
                and _socket_ish(node.func.value)
            ):
                yield ctx.finding(
                    self, node,
                    f"socket .{node.func.attr}() inside the serving plane — "
                    "wire I/O belongs to the masters (actors/), the "
                    "predictor only schedules device calls",
                )

    @staticmethod
    def _bounded(call: ast.Call) -> bool:
        bound = call.args[0] if call.args else None
        for kw in call.keywords:
            if kw.arg == "maxsize":
                bound = kw.value
        if bound is None:
            return False
        if isinstance(bound, ast.Constant):
            return isinstance(bound.value, int) and bound.value > 0
        # a computed bound (maxsize=queue_depth) is accepted: the rule
        # polices the unbounded DEFAULT, not the sizing policy
        return True


#: predictor policy-table internals whose direct access bypasses the
#: versioned publish path
_PARAMS_ATTRS = {"_params", "_policies"}
_PREDICTORISH_FRAGMENTS = ("pred", "serving")


def _predictorish(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name and any(f in name.lower() for f in _PREDICTORISH_FRAGMENTS):
            return True
    return False


class UnversionedParamsReadRule(Rule):
    """A10: direct ``update_params``/params-table access on a predictor
    outside the versioned params plane (``pod/``, ``predict/``).

    The pod's staleness accounting (docs/pod.md) rests on ONE invariant:
    every parameter publish into a serving predictor goes through a
    versioned path — the learner's counted publish or the actor-host
    :class:`StaleParamsCache` — so each experience block's version stamp
    actually names the policy that produced it. A stray
    ``predictor.update_params(...)`` (or a poke at the ``_params``/
    ``_policies`` policy table) silently serves weights NO version names:
    the learner's measured ``params_lag`` becomes a lie and the
    ``--max_staleness`` bound guards the wrong quantity. The sanctioned
    call sites — the Trainer's synchronous single-host publish (its
    version IS the train step) and the FanoutPredictors fan-out facade —
    carry suppressions stating exactly that; everything else routes
    through the cache (pod/cache.py ``on_update``). ``predict/`` itself
    is exempt (the predictor owns its table), as is ``pod/`` (the plane
    being protected).
    """

    id = "A10"
    name = "unversioned-params-read"
    summary = "predictor params published/read outside the versioned pod params plane"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        parts = ctx.path.replace(os.sep, "/").split("/")
        if "pod" in parts or "predict" in parts:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                if (
                    isinstance(fn, ast.Attribute)
                    and fn.attr == "update_params"
                    # predictor-ish receivers only (same filter as the
                    # attribute branch): an unrelated object with an
                    # update_params method must not force a bogus
                    # suppression that dilutes the audit trail
                    and _predictorish(fn.value)
                ):
                    yield ctx.finding(
                        self, node,
                        ".update_params() outside the versioned params "
                        "plane — publish through the pod cache "
                        "(pod/cache.py on_update) or a sanctioned "
                        "learner-publish site with a suppression naming "
                        "its version source (docs/pod.md)",
                    )
            elif isinstance(node, ast.Attribute):
                if (
                    node.attr in _PARAMS_ATTRS
                    and _predictorish(node.value)
                ):
                    yield ctx.finding(
                        self, node,
                        f"direct .{node.attr} access on a predictor — the "
                        "policy table is the predictor's own; readers go "
                        "through predict_batch/update_params so the "
                        "version accounting holds (docs/pod.md)",
                    )


#: span-constructor call names (telemetry/tracing.py): the class used as
#: `with tracing.span(...)` or explicitly `.finish()`ed
_SPAN_CTOR_SUFFIXES = ("tracing.span",)

#: metric-name tokens that mark a monotonic subtraction as latency math
#: (A7's token set plus the trace plane's own vocabulary)
_LATENCY_TOKENS = _METRIC_NAME_TOKENS | {"hop", "e2e"}

#: consuming attributes that make a monotonic pair SANCTIONED in place:
#: the value flows straight into the telemetry plane
_TELEMETRY_SINKS = {"observe", "record", "hop", "finish_span", "set"}


class OrphanSpanRule(Rule):
    """A11: a span started outside a context manager / without finish(),
    or ad-hoc ``time.monotonic()`` pair latency math outside ``telemetry/``.

    The trace plane (telemetry/tracing.py, docs/observability.md) only
    attributes wall-clock that actually reaches the span buffer: a
    ``tracing.span(...)`` constructed bare — not as a ``with`` item, not
    ``finish()``ed on every exit path — buffers NOTHING (its duration
    silently never lands, and the per-hop ``hop_<name>_s`` histogram the
    exporters serve stays empty), which is strictly worse than no
    instrumentation because the call site LOOKS covered. And a
    hand-rolled ``latency = time.monotonic() - t0`` that feeds a print or
    a local is A7's ad-hoc-metric hazard with the monotonic clock — right
    clock, wrong sink: route it through a Histogram ``observe`` or a span
    hop so every exporter sees it. Monotonic pairs flowing directly into
    ``.observe(...)``/``.hop(...)``/``record(...)`` in the same statement
    are the sanctioned shape; ``telemetry/`` itself is exempt (something
    has to implement the plane).
    """

    id = "A11"
    name = "orphan-span"
    summary = "span without context-manager/finish(), or ad-hoc monotonic-pair latency math"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        in_telemetry = (
            "telemetry" in ctx.path.replace(os.sep, "/").split("/")
        )
        if not in_telemetry:
            yield from self._check_monotonic_pairs(ctx)
        yield from self._check_orphan_spans(ctx)

    # -- half 1: tracing.span(...) lifecycle -------------------------------
    def _check_orphan_spans(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            if not resolved or not (
                resolved.endswith(_SPAN_CTOR_SUFFIXES) or resolved == "span"
            ):
                continue
            if self._is_with_item(node):
                continue
            stmt = enclosing_statement(node)
            var = self._assigned_name(stmt, node)
            if var is not None and self._finished_in_scope(node, var):
                continue
            yield ctx.finding(
                self, node,
                "span constructed outside a `with` and never .finish()ed "
                "on this path — its duration never reaches the span "
                "buffer or the hop_<name>_s histogram; use `with "
                "tracing.span(...) as s:` or finish() on every exit "
                "(telemetry/tracing.py)",
            )

    @staticmethod
    def _is_with_item(call: ast.Call) -> bool:
        p = parent(call)
        return isinstance(p, ast.withitem) and p.context_expr is call

    @staticmethod
    def _assigned_name(stmt, call) -> "str | None":
        if isinstance(stmt, ast.Assign) and stmt.value is call:
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    return t.id
        return None

    @staticmethod
    def _finished_in_scope(node: ast.AST, var: str) -> bool:
        scope: ast.AST = node
        for cur in ancestors(node):
            scope = cur
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
        for sub in ast.walk(scope):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "finish"
                and isinstance(sub.func.value, ast.Name)
                and sub.func.value.id == var
            ):
                return True
        return False

    # -- half 2: monotonic pair latency math -------------------------------
    def _check_monotonic_pairs(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if ctx.info.resolve(node.func) != "time.monotonic":
                continue
            sub = self._enclosing_subtraction(node)
            if sub is None:
                continue
            if self._feeds_telemetry_sink(sub):
                continue
            stmt = enclosing_statement(node)
            if stmt is None or not self._stmt_mentions_latency(stmt):
                continue
            yield ctx.finding(
                self, node,
                "time.monotonic() pair latency math outside telemetry/ — "
                "feed the duration to a Histogram .observe() or a span "
                "hop in the same statement so the scrape endpoint / "
                "stat.json / trace plane all see it (A7's intent, "
                "monotonic edition)",
            )

    @staticmethod
    def _enclosing_subtraction(node: ast.AST) -> Optional[ast.BinOp]:
        for cur in ancestors(node):
            if isinstance(cur, ast.BinOp) and isinstance(cur.op, ast.Sub):
                return cur
            if isinstance(cur, ast.stmt):
                return None
        return None

    @staticmethod
    def _feeds_telemetry_sink(sub: ast.BinOp) -> bool:
        # the subtraction is an ARGUMENT of an .observe()/.hop()/record()
        # call in the same expression — the sanctioned in-place shape
        for cur in ancestors(sub):
            if isinstance(cur, ast.Call):
                fn = cur.func
                name = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None
                )
                if name in _TELEMETRY_SINKS:
                    return True
            if isinstance(cur, ast.stmt):
                return False
        return False

    @staticmethod
    def _stmt_mentions_latency(stmt: ast.stmt) -> bool:
        for sub in ast.walk(stmt):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if not name:
                continue
            low = name.lower()
            if low in _NON_METRIC_NAMES:
                continue
            if not _LATENCY_TOKENS.isdisjoint(low.split("_")):
                return True
            if "persec" in low.replace("_", ""):
                return True
        return False


#: flag fragments that mark a send/recv as explicitly non-blocking
_NOBLOCK_FRAGMENTS = ("NOBLOCK", "DONTWAIT")

#: setsockopt names that give a socket's blocking ops a bounded timeout
_TIMEOUT_SOCKOPTS = ("RCVTIMEO", "SNDTIMEO")


def _has_noblock_flag(ctx: FileContext, call: ast.Call) -> bool:
    exprs = list(call.args) + [kw.value for kw in call.keywords]
    for e in exprs:
        for sub in ast.walk(e):
            nm = dotted_name(sub)
            if nm and any(f in nm for f in _NOBLOCK_FRAGMENTS):
                return True
    return False


def _scope_has_bounded_poll(node: ast.AST) -> bool:
    """The enclosing function contains a ``.poll(<timeout>)`` call — the
    Poller-guarded loop shape, where the recv only fires on POLLIN and
    the wait itself is bounded by the poll timeout."""
    fns = enclosing_functions(node)
    scope = fns[0] if fns else None
    if scope is None:
        return False
    for sub in ast.walk(scope):
        if not isinstance(sub, ast.Call):
            continue
        f = sub.func
        if isinstance(f, ast.Attribute) and f.attr == "poll":
            if sub.args or any(kw.arg == "timeout" for kw in sub.keywords):
                return True
    return False


def _file_sets_socket_timeout(ctx: FileContext) -> bool:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "setsockopt"):
            continue
        for arg in node.args:
            nm = dotted_name(arg)
            if nm and any(nm.endswith(o) for o in _TIMEOUT_SOCKOPTS):
                return True
    return False


class UnboundedSocketWaitRule(Rule):
    """A12: blocking ZMQ recv/send with no Poller timeout and no
    RCVTIMEO/SNDTIMEO.

    A bare ``sock.recv()`` parks its thread until the peer speaks — and a
    partitioned peer never does. Every wedge netchaos reproduces reduces
    to exactly this shape: the wait has no bound, so neither the stop
    flag nor the link-state machine is ever consulted again and the
    thread is lost to the partition (docs/netchaos.md). A wire op must
    either (a) run inside a Poller-guarded loop whose ``poll(timeout)``
    bounds the wait, (b) pass ``zmq.NOBLOCK``/``DONTWAIT``, or (c) run on
    a socket the file configures with ``RCVTIMEO``/``SNDTIMEO``. The
    sanctioned exceptions are the lockstep env-server client loops —
    parking in recv awaiting the action reply IS their protocol, and the
    supervisor owns their lifetime — which carry suppressions saying so.
    """

    id = "A12"
    name = "unbounded-socket-wait"
    summary = "blocking ZMQ recv/send with no Poller timeout or RCVTIMEO/SNDTIMEO"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        file_timeout = _file_sets_socket_timeout(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute) or fn.attr not in _WIRE_OPS:
                continue
            if not _socket_ish(fn.value):
                continue
            if _has_noblock_flag(ctx, node):
                continue
            if _scope_has_bounded_poll(node):
                continue
            if file_timeout:
                continue
            yield ctx.finding(
                self, node,
                f"blocking .{fn.attr}() with no bound — a partitioned peer "
                "parks this thread forever: guard it with a Poller "
                "poll(timeout) loop, pass zmq.NOBLOCK, or set "
                "RCVTIMEO/SNDTIMEO (docs/netchaos.md); lockstep env-server "
                "clients suppress with the protocol justification",
            )


#: function-name fragments that mark a def as the train-ingest path: the
#: collates, the masters' flush/emit sites, the pod's block staging, and
#: the lazy views' materializations — where obs bytes move between the
#: wire/ring and the learner's staging
_INGEST_FN_FRAGMENTS = (
    "collate", "flush", "emit", "ingest", "to_block", "__array__",
    "stage_group",
)

#: the copy constructors the staging discipline replaces
_COPY_CALLS = {"numpy.stack", "numpy.ascontiguousarray", "numpy.concatenate"}

#: the ONE module allowed to copy obs bytes on the ingest path
_STAGING_MODULE = "data/staging.py"


class IngestExtraCopyRule(Rule):
    """A13: ``np.stack``/``np.ascontiguousarray``/``.copy()`` on the
    train-ingest path outside ``data/staging.py``.

    The ingest copy budget (docs/ingest.md) is ONE host pass per block:
    shm-ring/wire bytes → the staging write; ``plane_bench --ingest``
    gates ``ingest_copies_total / ingest_blocks_total == 1`` on it. A
    fresh stack/contiguous-copy/`.copy()` inside a collate, flush/emit,
    or block-staging function re-grows exactly the materialize→stack→
    transpose chain the staging subsystem retired — every byte it copies
    is a second pass the budget no longer accounts for. Route the bytes
    through the in-place collates (``collate_*_into``) / the stagers, or
    suppress with the justification for why this site is sanctioned (the
    per-env compat foil's stack, the legacy collate fallbacks, and the
    lazy views' ``__array__`` compat materializations carry exactly such
    suppressions). The rule scopes to functions whose names mark the
    ingest path — copies elsewhere are someone else's budget.
    """

    id = "A13"
    name = "ingest-extra-copy"
    summary = "obs-byte copy (stack/ascontiguousarray/.copy) on the train-ingest path outside data/staging.py"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace(os.sep, "/")
        if path.endswith(_STAGING_MODULE):
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            low = fn.name.lower()
            if not any(f in low for f in _INGEST_FN_FRAGMENTS):
                continue
            yield from self._check_fn(ctx, fn)

    def _check_fn(self, ctx: FileContext, fn: ast.AST) -> Iterator[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            # nested defs get their own scope decision (a non-ingest
            # closure inside a flush fn is still the flush path; keep it)
            resolved = ctx.info.resolve(node.func)
            if resolved in _COPY_CALLS:
                short = resolved.rsplit(".", 1)[-1]
                yield ctx.finding(
                    self, node,
                    f"np.{short} on the train-ingest path — the copy "
                    "budget is ONE staging write per block "
                    "(data/staging.py collate_*_into / BlockStager); a "
                    "sanctioned compat copy needs a suppression saying "
                    "why (docs/ingest.md)",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "copy"
                and not node.args
                and not node.keywords
                and isinstance(node.func.value, (ast.Call, ast.Subscript))
            ):
                # array-expression .copy() (np.swapaxes(...).copy(),
                # arr[...].copy()) — dict/list .copy() on plain names
                # stays out of scope
                yield ctx.finding(
                    self, node,
                    ".copy() of an array expression on the train-ingest "
                    "path — write into the staging slot instead "
                    "(collate_*_into), or suppress with the sanction",
                )


#: the serving plane's front-door types; constructing one directly outside
#: predict/ (or dispatching at one so constructed) bypasses the router's
#: health/overflow/canary machinery and the sanctioned predictor factories
_PREDICTOR_CTOR_BARE = {"BatchedPredictor"}
_PREDICTOR_CTOR_SUFFIXES = (".BatchedPredictor",)
_PREDICTOR_DISPATCH_ATTRS = {"put_task", "put_block_task"}


class UnroutedPredictorDispatchRule(Rule):
    """A14: ``BatchedPredictor`` constructed — or dispatched at, when
    locally constructed — outside ``predict/`` and the sanctioned
    factories.

    The serving tier is ROUTED (predict/router.py, docs/serving.md): R
    replicas behind health-checked least-loaded dispatch with
    deadline-aware overflow, replica autoscaling and the canary
    promotion loop. A ``BatchedPredictor`` constructed ad hoc outside
    ``predict/`` is a serving plane nothing routes, nothing health-checks
    and nothing autoscales — its traffic bypasses the overflow path (so
    its overload sheds instead of failing over) and its policy table
    drifts from the router's (a promotion never reaches it). Construction
    belongs to the sanctioned factories — cli.py's ``make_predictor``
    (handed to the fleet assembly), the pod host's versioned-cache-fed
    predictor, orchestrate/serving.py's ``ReplicaSet`` factory — each of
    which carries the suppression naming why its lifecycle is owned
    (bench/test null planes are the raw measurand and suppress the same
    way). Dispatch (``put_task``/``put_block_task``) is flagged only on
    receivers ASSIGNED from a flagged construction in the same file:
    masters dispatching whatever predictor-or-router they were handed
    stay clean by construction — injection IS the sanctioned shape.
    """

    id = "A14"
    name = "unrouted-predictor-dispatch"
    summary = "BatchedPredictor constructed/dispatched outside predict/ bypasses the routed serving plane"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if "predict" in ctx.path.replace(os.sep, "/").split("/"):
            return
        local_names: Set[str] = set()
        ctor_nodes = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            if resolved and (
                resolved in _PREDICTOR_CTOR_BARE
                or resolved.endswith(_PREDICTOR_CTOR_SUFFIXES)
            ):
                ctor_nodes.append(node)
                p = parent(node)
                if isinstance(p, ast.Assign):
                    for t in p.targets:
                        local_names |= _target_names(t)
                elif isinstance(p, ast.AnnAssign):
                    local_names |= _target_names(p.target)
        for node in ctor_nodes:
            yield ctx.finding(
                self, node,
                "direct BatchedPredictor construction outside predict/ — "
                "an unrouted serving plane (no health checks, no "
                "overflow, no canary reach); route through the sanctioned "
                "factories (cli.py make_predictor / ReplicaSet) or "
                "suppress naming who owns this plane's lifecycle "
                "(docs/serving.md)",
            )
        if not local_names:
            return
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _PREDICTOR_DISPATCH_ATTRS
            ):
                names = {
                    n.id
                    for n in ast.walk(node.func.value)
                    if isinstance(n, ast.Name)
                }
                if names & local_names:
                    yield ctx.finding(
                        self, node,
                        f".{node.func.attr}() at a locally-constructed "
                        "BatchedPredictor — serving traffic belongs on "
                        "the router (or an injected predictor handle); "
                        "this dispatch bypasses overflow/health/canary "
                        "routing (docs/serving.md)",
                    )


#: liveness probes a hand-rolled supervision loop polls
_LIVENESS_POLL_ATTRS = {"is_alive", "poll"}
#: respawn moves the same loop makes — .start() on a thread/process
#: handle, or a fresh subprocess
_RESPAWN_ATTRS = {"start"}


class AdhocLifecycleLoopRule(Rule):
    """A15: hand-rolled spawn/health-poll supervision loop outside
    ``orchestrate/``.

    The reconciler (orchestrate/reconcile.py, docs/topology.md) is the
    ONE loop that observes liveness and respawns: per-resource
    exponential backoff, the topology-wide restart-budget circuit
    breaker, ``tele/reconciler/*`` accounting and a flight-recorded
    decision trail for every heal. A ``while``/``for`` loop elsewhere
    whose body both polls liveness (``.is_alive()``/``.poll()``) and
    spawns (``.start()``/``subprocess.Popen``) is a shadow supervisor:
    its respawns are unbudgeted (a crash loop spins at poll speed with
    no breaker), uncounted (the drift gauge and heal counters never see
    them) and unexplainable post-hoc (no decision trail). Implement the
    lifecycle as a :class:`Reconcilable` resource driven by the
    Reconciler instead, or suppress with the justification for why this
    loop's respawns are otherwise budgeted and accounted (an acceptance
    bench that IS the measurand of supervision, a test double).
    Loops that only poll (a wait-for-exit) or only spawn (a launch
    fan-out) stay clean — the hazard is the closed observe+respawn
    cycle.
    """

    id = "A15"
    name = "adhoc-lifecycle-loop"
    summary = "spawn/health-poll supervision loop outside orchestrate/ shadows the reconciler"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if "orchestrate" in ctx.path.replace(os.sep, "/").split("/"):
            return
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.While, ast.For)):
                continue
            polls = spawns = False
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute):
                    if node.func.attr in _LIVENESS_POLL_ATTRS:
                        polls = True
                    elif node.func.attr in _RESPAWN_ATTRS:
                        spawns = True
                resolved = ctx.info.resolve(node.func)
                if resolved and (
                    resolved in _SUBPROCESS_SPAWNERS
                    or resolved.endswith(".Popen")
                ):
                    spawns = True
            if polls and spawns:
                yield ctx.finding(
                    self, loop,
                    "loop both polls liveness and spawns — a shadow "
                    "supervisor with no backoff, no restart budget, no "
                    "heal accounting; make it a Reconcilable resource "
                    "driven by the orchestrate/ Reconciler, or suppress "
                    "naming who budgets these respawns "
                    "(docs/topology.md)",
                )


#: the two dtypes the quantized-rollout ladder owns end to end — a cast to
#: either outside the sanctioned sites IS a new serving numerics rung
_QUANT_CAST_DTYPES = {"bfloat16", "int8"}
#: path segments that carry the params-publish/actor-forward path
_QUANT_CAST_SEGMENTS = {"predict", "fused", "pod"}


def _quant_cast_dtype(node: Optional[ast.AST]) -> Optional[str]:
    """The bf16/int8 dtype a cast target names, else None — matches both
    the ``jnp.bfloat16`` attribute form and the ``"int8"`` string form."""
    if isinstance(node, ast.Attribute) and node.attr in _QUANT_CAST_DTYPES:
        return node.attr
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, str)
        and node.value in _QUANT_CAST_DTYPES
    ):
        return node.value
    return None


class UnauditedDtypeCastRule(Rule):
    """A16: ad-hoc bf16/int8 cast on the params-publish/actor-forward
    path outside ``quantize/``.

    The rollout-precision ladder is AUDIT-PINNED: every dtype the serving
    and actor forwards run at has a registered entry point
    (predict.server_bf16 / fused.actor_bf16 / predict.server_int8 /
    fused.actor_int8) whose compiled program the manifest's T1/T5 rows
    structurally pin. An ``astype(jnp.bfloat16)`` /
    ``lax.convert_element_type(..., jnp.int8)`` added ad hoc in
    ``predict/``, ``fused/`` or ``pod/`` is a serving-numerics change no
    audit sees: the program it produces has no entry, no byte census, no
    parity band — a precision regression (or an accidental double-cast)
    ships silently. Quantizing casts belong to ``quantize/`` (the int8
    rung's one home: per-channel scales, calibrated activation ranges,
    the audited epilogue) or to THE audited publish-cast site, which
    carries the suppression naming its entry. Everything else on this
    path hands dtype selection to ``rollout_dtype`` and the sanctioned
    cast hooks. f32 casts stay clean — the ladder's base rung is not a
    quantization.
    """

    id = "A16"
    name = "unaudited-dtype-cast"
    summary = "ad-hoc bf16/int8 cast on the publish/actor-forward path outside quantize/ dodges the audit"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        segs = set(ctx.path.replace(os.sep, "/").split("/"))
        if not segs & _QUANT_CAST_SEGMENTS or "quantize" in segs:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            dt = None
            via = None
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr == "astype"
                and node.args
            ):
                dt = _quant_cast_dtype(node.args[0])
                via = "astype"
            else:
                resolved = ctx.info.resolve(fn)
                if resolved and resolved.endswith("convert_element_type"):
                    target = node.args[1] if len(node.args) > 1 else next(
                        (
                            kw.value for kw in node.keywords
                            if kw.arg == "new_dtype"
                        ),
                        None,
                    )
                    dt = _quant_cast_dtype(target)
                    via = "convert_element_type"
            if dt:
                yield ctx.finding(
                    self, node,
                    f"ad-hoc {via} to {dt} on the publish/actor-forward "
                    "path — a serving-numerics change no audit entry "
                    "pins; route it through rollout_dtype + quantize/ "
                    "(or suppress naming the audited entry this cast "
                    "feeds — docs/static_analysis.md)",
                )


ACTOR_RULES = [
    BareThreadRule(),
    BlockingQueueOpRule(),
    CrossThreadClientMutationRule(),
    WallClockArithRule(),
    PrivateImportRule(),
    PerEnvWireLoopRule(),
    AdhocMetricRule(),
    UnsupervisedFleetSpawnRule(),
    ServingHotPathBlockRule(),
    UnversionedParamsReadRule(),
    OrphanSpanRule(),
    UnboundedSocketWaitRule(),
    IngestExtraCopyRule(),
    UnroutedPredictorDispatchRule(),
    AdhocLifecycleLoopRule(),
    UnauditedDtypeCastRule(),
]
