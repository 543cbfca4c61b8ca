"""J-series rules: JAX hot-path hazards (host syncs, retraces, key reuse).

IMPALA-style stacks lose their throughput to silent host syncs and
recompiles long before they lose it to math; these rules flag the patterns
that have bitten this repo (every device->host fetch waits for the device
and pays a fixed round trip regardless of payload). Rationale and worked
examples in docs/static_analysis.md.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.ba3clint.engine import (
    FileContext,
    Finding,
    Rule,
    dotted_name,
    enclosing_functions,
    enclosing_loop,
    enclosing_statement,
)

_SYNC_FNS = {"jax.device_get", "jax.block_until_ready"}
_HOST_CAST_FNS = {"numpy.asarray", "numpy.array", "np.asarray", "np.array"}


def _in_jitted_scope(ctx: FileContext, node: ast.AST) -> bool:
    return any(
        fn.name in ctx.info.jitted_fn_defs for fn in enclosing_functions(node)
    )


def _contains_jitted_call(ctx: FileContext, node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            nm = dotted_name(sub.func)
            if nm and nm in ctx.info.jitted:
                return True
    return False


class HostSyncHotPathRule(Rule):
    """J1: host synchronization inside a per-step loop or a jitted function.

    ``jax.device_get``/``.block_until_ready()`` force the host to wait for
    the device; inside a step loop they serialize dispatch and execution
    (the async-dispatch overlap the trainer depends on disappears). Inside a
    function that gets jitted they either fail at trace time or silently
    bake a constant. ``np.asarray``/``float()`` on the result of a jitted
    call is the same sync wearing a numpy hat.
    """

    id = "J1"
    name = "host-sync-hot-path"
    summary = "device_get/block_until_ready/np.asarray-on-jitted inside a loop or jitted fn"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.info.resolve(node.func)
            is_sync = resolved in _SYNC_FNS or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            )
            if is_sync:
                if enclosing_loop(node) is not None:
                    yield ctx.finding(
                        self, node,
                        "host sync inside a loop body serializes dispatch — "
                        "hoist it out of the hot loop (fetch once per "
                        "epoch/window)",
                    )
                elif _in_jitted_scope(ctx, node):
                    yield ctx.finding(
                        self, node,
                        "host sync inside a function that gets jitted — "
                        "it fails at trace time or bakes a constant",
                    )
                continue
            is_cast = resolved in _HOST_CAST_FNS or (
                isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int")
            )
            if not is_cast or not node.args:
                continue
            if _in_jitted_scope(ctx, node) and resolved in _HOST_CAST_FNS:
                yield ctx.finding(
                    self, node,
                    "np.asarray/np.array inside a function that gets jitted "
                    "— use jnp, or move the host conversion outside the "
                    "traced scope",
                )
            elif enclosing_loop(node) is not None and _contains_jitted_call(
                ctx, node.args[0]
            ):
                yield ctx.finding(
                    self, node,
                    "host cast of a jitted call's result inside a loop — "
                    "this blocks on the device every iteration",
                )


class JitInLoopRule(Rule):
    """J2: ``jax.jit`` constructed inside a loop body.

    Each ``jax.jit(f)`` call creates a fresh compilation cache; inside a
    loop that means retracing (and often recompiling) every iteration.
    Construct the jitted callable once, outside the loop.
    """

    id = "J2"
    name = "jit-in-loop"
    summary = "jax.jit(...) constructed inside a loop body retraces every iteration"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if ctx.info.resolve(node.func) not in (
                "jax.jit", "jax.pjit", "jit", "pjit"
            ):
                continue
            if enclosing_loop(node) is not None:
                yield ctx.finding(
                    self, node,
                    "jax.jit constructed inside a loop — each call makes a "
                    "fresh cache and retraces; hoist the jit out of the loop",
                )


class NonStaticJitArgRule(Rule):
    """J3: dict/list/set/str literal passed to a jitted callable.

    Container literals passed positionally to a jitted function are traced
    as pytrees — fine for arrays, but a literal of Python scalars/strings
    retraces on every distinct value, and an intended-static string arg
    raises unless marked ``static_argnums``. Passing the literal inline is
    the tell that the call site thinks it is passing configuration.
    """

    id = "J3"
    name = "nonstatic-jit-arg"
    summary = "dict/list/str literal passed to a jitted fn (retrace/static_argnums hazard)"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            nm = dotted_name(node.func)
            if not nm or nm not in ctx.info.jitted:
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, (ast.Dict, ast.List, ast.Set)) or (
                    isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                ):
                    yield ctx.finding(
                        self, arg,
                        f"literal {type(arg).__name__.lower()} passed to "
                        f"jitted `{nm}` — non-array/config args retrace per "
                        "value or need static_argnums; build arrays outside "
                        "the call",
                    )


_KEY_DERIVE_FNS = {"split", "fold_in", "clone", "key_data", "wrap_key_data"}


class PRNGKeyReuseRule(Rule):
    """J4: a PRNGKey used by more than one sampler (or in a loop) unsplit.

    Passing the same key to two sampling calls produces *identical*
    randomness — silently correlated exploration, identical dropout masks.
    Every consumption must go through ``jax.random.split``/``fold_in``.
    """

    id = "J4"
    name = "prngkey-reuse"
    summary = "PRNGKey consumed more than once (or in a loop) without split/fold_in"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_fn(ctx, fn)

    def _check_fn(self, ctx: FileContext, fn: ast.AST) -> Iterator[Finding]:
        keys: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                resolved = ctx.info.resolve(node.value.func)
                if resolved in ("jax.random.PRNGKey", "jax.random.key"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            keys.add(t.id)
        if not keys:
            return

        derived: Set[str] = set()
        uses: Dict[str, List[ast.Call]] = {k: [] for k in keys}
        looped: Dict[str, List[ast.Call]] = {k: [] for k in keys}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None
            )
            if attr is None:
                continue
            arg_names = {
                a.id for a in node.args if isinstance(a, ast.Name)
            } | {
                kw.value.id
                for kw in node.keywords
                if isinstance(kw.value, ast.Name)
            }
            hit = arg_names & keys
            if not hit:
                continue
            if attr in _KEY_DERIVE_FNS:
                derived |= hit
                continue
            resolved = ctx.info.resolve(f) or ""
            if not resolved.startswith("jax.random."):
                continue  # passing the key onward is the callee's problem
            for k in hit:
                uses[k].append(node)
                if enclosing_loop(node) is not None:
                    looped[k].append(node)

        for k in sorted(keys):
            if k in derived:
                continue
            if looped[k]:
                yield ctx.finding(
                    self, looped[k][0],
                    f"PRNGKey `{k}` consumed inside a loop without "
                    "jax.random.split — identical randomness every iteration",
                )
            elif len(uses[k]) >= 2:
                yield ctx.finding(
                    self, uses[k][1],
                    f"PRNGKey `{k}` consumed by multiple sampling calls "
                    "without jax.random.split — the draws are identical",
                )


class ReadAfterDonateRule(Rule):
    """J5: reading an argument after passing it to a donating jit.

    ``donate_argnums`` hands the buffer to XLA for reuse; a later host read
    of the donated array returns garbage or crashes in native code
    (the trainer copies params before publishing for exactly this reason).
    """

    id = "J5"
    name = "read-after-donate"
    summary = "variable read after being donated to a jitted call (donate_argnums)"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        donating = {
            name: pos for name, pos in ctx.info.jitted.items() if pos
        }
        if not donating:
            return
        seen: Set[Tuple[int, int]] = set()
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # the same call can appear in several nested blocks — report a
            # given read site once
            for block in self._blocks(fn):
                for f in self._check_block(ctx, donating, block):
                    key = (f.line, f.col)
                    if key not in seen:
                        seen.add(key)
                        yield f

    @staticmethod
    def _blocks(fn: ast.AST) -> Iterator[List[ast.stmt]]:
        yield fn.body
        for node in ast.walk(fn):
            if isinstance(node, (ast.For, ast.While, ast.If, ast.With)):
                yield node.body
                if getattr(node, "orelse", None):
                    yield node.orelse

    def _check_block(
        self,
        ctx: FileContext,
        donating: Dict[str, Tuple[int, ...]],
        block: List[ast.stmt],
    ) -> Iterator[Finding]:
        for i, stmt in enumerate(block):
            for call in ast.walk(stmt):
                if not isinstance(call, ast.Call):
                    continue
                nm = dotted_name(call.func)
                if not nm or nm not in donating:
                    continue
                # rebinds are judged at the call's OWN assignment (the call
                # may sit inside a compound statement within this block)
                rebound = self._stmt_targets(enclosing_statement(call) or stmt)
                for pos in donating[nm]:
                    if pos >= len(call.args):
                        continue
                    arg = call.args[pos]
                    if not isinstance(arg, ast.Name) or arg.id in rebound:
                        continue
                    use = self._later_read(block[i + 1:], arg.id)
                    if use is not None:
                        yield ctx.finding(
                            self, use,
                            f"`{arg.id}` was donated to jitted `{nm}` "
                            "(donate_argnums) and read afterwards — the "
                            "buffer may already be reused; jnp.copy before "
                            "the call or rebind the result",
                        )

    @staticmethod
    def _stmt_targets(stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        for t in targets:
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for el in elts:
                if isinstance(el, ast.Name):
                    out.add(el.id)
        return out

    def _later_read(
        self, rest: List[ast.stmt], name: str
    ) -> Optional[ast.AST]:
        for stmt in rest:
            for node in ast.walk(stmt):
                if (
                    isinstance(node, ast.Name)
                    and node.id == name
                    and isinstance(node.ctx, ast.Load)
                ):
                    return node
            if name in self._stmt_targets(stmt):
                return None  # rebound before any read
        return None


_J6_SYNC_ATTRS = {"block_until_ready", "item"}
_J6_CAST_NAMES = {"float", "int", "bool"}


class OverlapSyncHazardRule(Rule):
    """J6: host sync on actor-program outputs between the two dispatches.

    The overlap schedule (fused/overlap.py, docs/overlap.md) exists so the
    runtime can execute rollout k+1 concurrently with learner k. A
    ``block_until_ready``/``device_get``/``.item()``/``np.asarray``/
    ``float()`` on the ACTOR program's outputs after the actor dispatch and
    before the learner dispatch forces the rollout to complete before the
    learner is even enqueued — it re-serializes the two programs and
    silently refutes the whole split, while every test stays green.

    Heuristic, tuned to the repo idiom: inside a function that calls both
    an actor-named callable (last dotted segment contains ``actor``) and a
    learner-named one (contains ``learner``), any sync-consuming use of a
    name bound from the actor call, positioned after that actor call and
    before a later learner call, is flagged. The one sanctioned site is
    the measurement probe (``probe_overlap``), which exists to measure the
    serialization this rule forbids — its suppressions carry the
    justification.
    """

    id = "J6"
    name = "overlap-sync-hazard"
    summary = "host sync on actor-program outputs between the actor and learner dispatches"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_fn(ctx, fn)

    @staticmethod
    def _last_segment(call: ast.Call) -> str:
        nm = dotted_name(call.func)
        return nm.rsplit(".", 1)[-1].lower() if nm else ""

    def _check_fn(self, ctx: FileContext, fn: ast.AST) -> Iterator[Finding]:
        actor_calls: List[ast.Call] = []
        learner_lines: List[int] = []
        # nested defs get their own _check_fn pass — only look at calls
        # whose innermost enclosing function is THIS one
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            encl = enclosing_functions(node)
            if not encl or encl[0] is not fn:
                continue
            seg = self._last_segment(node)
            if "actor" in seg:
                actor_calls.append(node)
            elif "learner" in seg:
                learner_lines.append(node.lineno)
        if not actor_calls or not learner_lines:
            return

        # names bound from an actor call (tuple unpack included)
        actor_outputs: Dict[str, int] = {}  # name -> actor call line
        for call in actor_calls:
            stmt = enclosing_statement(call)
            if not isinstance(stmt, ast.Assign) or stmt.value is not call:
                continue
            for t in stmt.targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for el in elts:
                    if isinstance(el, ast.Name):
                        actor_outputs[el.id] = call.lineno
        if not actor_outputs:
            return
        last_learner = max(learner_lines)

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            encl = enclosing_functions(node)
            if not encl or encl[0] is not fn:
                continue
            hit = self._synced_actor_output(ctx, node, actor_outputs)
            if hit is None:
                continue
            name, actor_line = hit
            # "between the two dispatches": after the actor call that
            # bound the name, before the last learner dispatch
            if actor_line < node.lineno <= last_learner:
                yield ctx.finding(
                    self, node,
                    f"host sync on actor-program output `{name}` between "
                    "the actor and learner dispatches — this forces the "
                    "rollout to finish before the learner is enqueued, "
                    "re-serializing the overlapped programs; sync after "
                    "both dispatches (or once per window)",
                )

    @staticmethod
    def _synced_actor_output(
        ctx: FileContext, call: ast.Call, actor_outputs: Dict[str, int]
    ) -> Optional[Tuple[str, int]]:
        """(name, actor line) if ``call`` host-syncs an actor output."""

        def names_in(expr: ast.AST) -> Iterator[str]:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Name):
                    yield sub.id

        f = call.func
        resolved = ctx.info.resolve(f)
        is_sync_fn = resolved in _SYNC_FNS or resolved in _HOST_CAST_FNS or (
            isinstance(f, ast.Name) and f.id in _J6_CAST_NAMES
        )
        if is_sync_fn:
            for arg in call.args:
                for nm in names_in(arg):
                    if nm in actor_outputs:
                        return nm, actor_outputs[nm]
            return None
        if isinstance(f, ast.Attribute) and f.attr in _J6_SYNC_ATTRS:
            for nm in names_in(f.value):
                if nm in actor_outputs:
                    return nm, actor_outputs[nm]
        return None


JAX_RULES = [
    HostSyncHotPathRule(),
    JitInLoopRule(),
    NonStaticJitArgRule(),
    PRNGKeyReuseRule(),
    ReadAfterDonateRule(),
    OverlapSyncHazardRule(),
]
