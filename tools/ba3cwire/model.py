"""ba3cwire wire-surface model: protocol facts over the ba3cflow symbol table.

Everything the W-rules ask about lives here:

- **decode classification**: which calls decode wire bytes (``loads``,
  ``unpack_*``/``decode_*`` codec entries, raw ``msgpack`` calls,
  ``np.frombuffer`` fed directly from a socket ``recv``).
- **raising-decode closure**: which project functions can let a typed decode
  error (``CorruptFrameError``, msgpack/header ``ValueError``/``KeyError``)
  escape to their caller — seeded from uncontained decode calls and explicit
  ``CorruptFrameError`` raises, propagated over the call graph with witness
  chains.
- **receive loops + protection**: socket receive loops, and whether a decode
  inside one is wrapped by a try that catches decode errors and CONTINUES
  the loop (a handler that re-raises/returns/breaks still kills it).
- **length-guard analysis**: per-function floors established by
  validate-or-bail ``len(...)`` checks and guards established by enclosing
  ``if len(...) > k`` tests — the "length-versioned, positions pinned"
  header convention, made checkable.
- **metrics facts**: every literal ``counter/gauge/histogram("name")``
  creation, counter-variable bindings for monotonicity checks, and the
  parsed docs/observability.md series catalog.

Heuristics over proofs, like the siblings: unknown receivers and dynamic
series names resolve to nothing, so rules stay quiet rather than guess.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from tools.ba3clint.engine import dotted_name
from tools.ba3cflow.graph import CallGraph, resolve_call
from tools.ba3cflow.project import FunctionInfo, ModuleSyms, Project

# --------------------------------------------------------------------------
# scope walking (never cross into a nested function/class scope)
# --------------------------------------------------------------------------

_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def walk_scope(root: ast.AST) -> Iterator[ast.AST]:
    """``ast.walk`` restricted to ``root``'s own scope: nested function and
    class bodies are opaque (they execute later, under their own rules)."""
    stack: List[ast.AST] = [root]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _SCOPE_NODES):
                continue
            stack.append(child)


def walk_stmts(stmts: Sequence[ast.stmt]) -> Iterator[ast.AST]:
    for stmt in stmts:
        yield from walk_scope(stmt)


# --------------------------------------------------------------------------
# codec modules + decode classification
# --------------------------------------------------------------------------

#: the four codec planes: the only modules allowed to touch msgpack or to
#: opt out of CRC framing — everything else must route through them.
CODEC_MODULE_SUFFIXES = (
    "utils/serialize.py",
    "pod/wire.py",
    "telemetry/wire.py",
    "telemetry/tracing.py",
)


def is_codec_module(path: str) -> bool:
    norm = path.replace("\\", "/")
    return norm.endswith(CODEC_MODULE_SUFFIXES)


#: modules participating in the wire protocol: the codec planes themselves,
#: plus anything importing them (or msgpack). W1/W2 stay inside this scope
#: so array-layout packers (ops/, models/) and CLI argv parsing never
#: read as protocol surfaces.
_WIRE_IMPORT_MARKERS = (
    "utils.serialize", "pod.wire", "telemetry.wire", "telemetry.tracing",
)


def wire_scope(mod: ModuleSyms) -> bool:
    if is_codec_module(mod.path):
        return True
    for origin in mod.imports.values():
        if origin == "msgpack" or origin.startswith("msgpack."):
            return True
        if any(marker in origin for marker in _WIRE_IMPORT_MARKERS):
            return True
    return False


#: struct.unpack/unpack_from parse fixed binary layouts, not codec payloads
_UNPACK_EXCLUDE = {"unpack_from"}

#: stdlib codecs whose failure modes are NOT the wire classes W3 tracks
_FOREIGN_LOADS_HEADS = ("json.", "pickle.", "yaml.", "marshal.", "tomllib.")

_MSGPACK_DECODE_ATTRS = {"unpackb", "unpack", "loads", "load"}


def decode_label(mod: ModuleSyms, call: ast.Call) -> Optional[str]:
    """Short label when ``call`` decodes wire bytes, else None."""
    func = call.func
    if isinstance(func, ast.Name):
        if func.id == "loads":
            canon = mod.resolve(func.id)
            if canon.startswith(_FOREIGN_LOADS_HEADS):
                return None
            return "loads"
        if func.id.startswith(("unpack_", "decode_")) and \
                func.id not in _UNPACK_EXCLUDE:
            return func.id
        return None
    if isinstance(func, ast.Attribute):
        attr = func.attr
        dn = dotted_name(func)
        canon = mod.resolve(dn) if dn else None
        if attr.startswith(("unpack_", "decode_")) and \
                attr not in _UNPACK_EXCLUDE:
            if canon is not None and canon.startswith("struct."):
                return None
            return attr
        if attr in _MSGPACK_DECODE_ATTRS and canon is not None:
            if canon.split(".")[0] == "msgpack":
                return canon
            if attr == "loads" and canon.endswith("serialize.loads"):
                return "loads"
            return None
        if attr == "frombuffer" and _feeds_from_recv(call):
            return "frombuffer(recv())"
    return None


def _feeds_from_recv(call: ast.Call) -> bool:
    """True when an argument of ``call`` contains an inline ``.recv*`` —
    decoding straight off the socket with no validation in between."""
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr.startswith("recv"):
                return True
    return False


# --------------------------------------------------------------------------
# try/except shape analysis
# --------------------------------------------------------------------------

#: exception names (last dotted segment) that cover the typed decode-failure
#: classes: CorruptFrameError(ValueError), msgpack's UnpackException family,
#: header KeyError/ValueError/IndexError, struct.error, or a blanket catch.
DECODE_EXC_NAMES = {
    "Exception", "BaseException", "ValueError", "TypeError", "KeyError",
    "IndexError", "CorruptFrameError", "UnpackException", "ExtraData",
    "OutOfData", "FormatError", "StackError", "error",
}


def _exc_names(node: Optional[ast.AST]) -> List[str]:
    if node is None:
        return []
    if isinstance(node, ast.Tuple):
        out: List[str] = []
        for el in node.elts:
            out.extend(_exc_names(el))
        return out
    dn = dotted_name(node)
    return [dn.split(".")[-1]] if dn else []


def handler_catches_decode(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True  # bare except
    return any(n in DECODE_EXC_NAMES for n in _exc_names(handler.type))


def handler_kills_loop(handler: ast.ExceptHandler) -> bool:
    """A handler that raises, returns, or breaks still terminates the
    receive loop — catching the decode error is not enough."""
    return any(isinstance(n, (ast.Raise, ast.Return, ast.Break))
               for n in walk_stmts(handler.body))


def handler_reraises(handler: ast.ExceptHandler) -> bool:
    return any(isinstance(n, ast.Raise) for n in walk_stmts(handler.body))


def contained_node_ids(fn_node: ast.AST) -> Set[int]:
    """ids of nodes inside a try BODY whose handlers catch decode errors
    without re-raising — a decode error there is contained in this function
    (the caller never sees it, whatever the handler returns)."""
    out: Set[int] = set()
    for t in walk_scope(fn_node):
        if not isinstance(t, ast.Try):
            continue
        if not any(handler_catches_decode(h) and not handler_reraises(h)
                   for h in t.handlers):
            continue
        for n in walk_stmts(t.body):
            out.add(id(n))
    return out


def loop_protected_ids(loop: ast.AST) -> Set[int]:
    """ids of nodes inside a try strictly within ``loop`` whose handlers
    catch decode errors AND continue the loop (no raise/return/break)."""
    out: Set[int] = set()
    for t in walk_scope(loop):
        if not isinstance(t, ast.Try) or t is loop:
            continue
        if not any(handler_catches_decode(h) and not handler_kills_loop(h)
                   for h in t.handlers):
            continue
        for n in walk_stmts(t.body):
            out.add(id(n))
    return out


# --------------------------------------------------------------------------
# receive loops
# --------------------------------------------------------------------------


def recv_loops(fn_node: ast.AST) -> List[ast.AST]:
    """For/While loops in ``fn_node``'s scope whose body performs a socket
    ``.recv*`` — the loops a single corrupt frame must not terminate."""
    out = []
    for node in walk_scope(fn_node):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        for sub in walk_scope(node):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr.startswith("recv"):
                out.append(node)
                break
    return out


def first_recv_line(loop: ast.AST) -> Optional[int]:
    lines = [sub.lineno for sub in walk_scope(loop)
             if isinstance(sub, ast.Call) and
             isinstance(sub.func, ast.Attribute) and
             sub.func.attr.startswith("recv")]
    return min(lines) if lines else None


# --------------------------------------------------------------------------
# interprocedural wire facts
# --------------------------------------------------------------------------


class WireFacts:
    """Raising-decode closure + counter-increment closure over the project."""

    def __init__(self, project: Project, graph: CallGraph):
        self.project = project
        self.graph = graph
        self._contained: Dict[str, Set[int]] = {}
        for fn in project.functions.values():
            self._contained[fn.qualname] = contained_node_ids(fn.node)
        #: qualname -> witness chain (qualnames, innermost last) ending at
        #: the function whose decode can raise out
        self.raising: Dict[str, Tuple[str, ...]] = {}
        self._build_raising()
        #: qualnames that (transitively) increment a metrics counter
        self.incs: Set[str] = set()
        self._build_incs()

    def contained(self, fn: FunctionInfo) -> Set[int]:
        return self._contained.get(fn.qualname, set())

    def _build_raising(self) -> None:
        for fn in self.project.functions.values():
            mod = self.project.module_of(fn)
            contained = self._contained[fn.qualname]
            for n in walk_scope(fn.node):
                if id(n) in contained:
                    continue
                if isinstance(n, ast.Raise) and n.exc is not None:
                    dn = dotted_name(n.exc.func) if isinstance(n.exc, ast.Call) \
                        else dotted_name(n.exc)
                    if dn and "CorruptFrame" in dn:
                        self.raising.setdefault(fn.qualname, (fn.qualname,))
                elif isinstance(n, ast.Call):
                    label = decode_label(mod, n)
                    if label and not resolve_call(self.project, fn, n):
                        # external decode (msgpack itself, or a codec the
                        # analyzed slice doesn't include): assume it raises
                        self.raising.setdefault(fn.qualname, (fn.qualname,))
        changed = True
        while changed:
            changed = False
            for fn in self.project.functions.values():
                q = fn.qualname
                if q in self.raising:
                    continue
                contained = self._contained[q]
                for tgt, node in self.graph.callees(q):
                    chain = self.raising.get(tgt.qualname)
                    if chain is None or id(node) in contained:
                        continue
                    if q not in chain and len(chain) < 10:
                        self.raising[q] = (q,) + chain
                        changed = True
                        break

    def _build_incs(self) -> None:
        for fn in self.project.functions.values():
            for n in walk_scope(fn.node):
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr == "inc":
                    self.incs.add(fn.qualname)
                    break
        changed = True
        while changed:
            changed = False
            for q, callees in self.graph.edges.items():
                if q in self.incs:
                    continue
                if any(t.qualname in self.incs for t, _ in callees):
                    self.incs.add(q)
                    changed = True

    def raising_chain(self, fn: FunctionInfo, call: ast.Call,
                      locals_: Optional[Dict[str, str]] = None
                      ) -> Optional[Tuple[Tuple[str, ...], str]]:
        """(witness chain, label) when ``call`` can raise a decode error
        into ``fn``, else None."""
        mod = self.project.module_of(fn)
        label = decode_label(mod, call)
        targets = resolve_call(self.project, fn, call, locals_)
        if label and not targets:
            return ((), label)
        for tgt in targets:
            chain = self.raising.get(tgt.qualname)
            if chain is not None:
                return (chain, label or tgt.name)
        return None

    def counts_reject(self, fn: FunctionInfo, handler: ast.ExceptHandler,
                      locals_: Optional[Dict[str, str]] = None) -> bool:
        """True when ``handler`` increments a counter, directly or through
        a callee (the typed-reject accounting W4 requires)."""
        for n in walk_stmts(handler.body):
            if not isinstance(n, ast.Call):
                continue
            if isinstance(n.func, ast.Attribute) and n.func.attr == "inc":
                return True
            for tgt in resolve_call(self.project, fn, n, locals_, duck=True):
                if tgt.qualname in self.incs:
                    return True
        return False


# --------------------------------------------------------------------------
# length-guard analysis (W2)
# --------------------------------------------------------------------------

#: (symbol, offset): symbol None for a literal bound
Bound = Tuple[Optional[str], int]


def _len_arg(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) and \
            node.func.id == "len" and len(node.args) == 1 and not node.keywords:
        return dotted_name(node.args[0])
    return None


def _bound(expr: ast.AST) -> Optional[Bound]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int) and \
            not isinstance(expr.value, bool):
        return (None, expr.value)
    dn = dotted_name(expr)
    if dn:
        return (dn, 0)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, (ast.Add, ast.Sub)):
        dn = dotted_name(expr.left)
        if dn and isinstance(expr.right, ast.Constant) and \
                isinstance(expr.right.value, int):
            k = expr.right.value
            return (dn, k if isinstance(expr.op, ast.Add) else -k)
    return None


_SWAPPED = {ast.Lt: ast.Gt, ast.Gt: ast.Lt, ast.LtE: ast.GtE,
            ast.GtE: ast.LtE, ast.Eq: ast.Eq, ast.NotEq: ast.NotEq}


def _len_compare(node: ast.AST) -> Optional[Tuple[str, type, ast.AST]]:
    """(name, op type, bound expr) for ``len(name) OP bound`` (either
    operand order; op normalized so len() is on the left)."""
    if not isinstance(node, ast.Compare) or len(node.ops) != 1:
        return None
    left, op, right = node.left, node.ops[0], node.comparators[0]
    nm = _len_arg(left)
    if nm is not None:
        return (nm, type(op), right)
    nm = _len_arg(right)
    if nm is not None and type(op) in _SWAPPED:
        return (nm, _SWAPPED[type(op)], left)
    return None


def _bail_floors(test: ast.AST) -> Dict[str, List[Bound]]:
    """Floors established when ``test`` is true => control bails.

    ``if len(n) < 3: raise`` => past this point len(n) >= 3.
    """
    out: Dict[str, List[Bound]] = {}
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.Or):
        for v in test.values:
            for nm, bs in _bail_floors(v).items():
                out.setdefault(nm, []).extend(bs)
        return out
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        for nm, bs in _guard_floors(test.operand).items():
            out.setdefault(nm, []).extend(bs)
        return out
    cmp = _len_compare(test)
    if cmp is not None:
        nm, op, bexpr = cmp
        b = _bound(bexpr)
        if b is not None:
            sym, k = b
            if op is ast.Lt:          # bail when len < k  => len >= k
                out.setdefault(nm, []).append((sym, k))
            elif op is ast.LtE:       # bail when len <= k => len >= k+1
                out.setdefault(nm, []).append((sym, k + 1))
            elif op is ast.NotEq:     # bail when len != k => len == k
                out.setdefault(nm, []).append((sym, k))
        return out
    # `if len(n) not in (2, 3): raise` => len >= min(2, 3)
    if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
            isinstance(test.ops[0], ast.NotIn):
        nm = _len_arg(test.left)
        tup = test.comparators[0]
        if nm is not None and isinstance(tup, (ast.Tuple, ast.List, ast.Set)):
            ks = [e.value for e in tup.elts
                  if isinstance(e, ast.Constant) and isinstance(e.value, int)]
            if ks and len(ks) == len(tup.elts):
                out.setdefault(nm, []).append((None, min(ks)))
    return out


def _guard_floors(test: ast.AST) -> Dict[str, List[Bound]]:
    """Floors established when ``test`` is TRUE (guard form).

    ``len(n) > 4`` => len >= 5; ``len(n) >= 5`` => len >= 5;
    ``len(n) == 3`` => len >= 3.
    """
    out: Dict[str, List[Bound]] = {}
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        for v in test.values:
            for nm, bs in _guard_floors(v).items():
                out.setdefault(nm, []).extend(bs)
        return out
    cmp = _len_compare(test)
    if cmp is not None:
        nm, op, bexpr = cmp
        b = _bound(bexpr)
        if b is not None:
            sym, k = b
            if op is ast.Gt:
                out.setdefault(nm, []).append((sym, k + 1))
            elif op is ast.GtE:
                out.setdefault(nm, []).append((sym, k))
            elif op is ast.Eq:
                out.setdefault(nm, []).append((sym, k))
    return out


def _bails(stmts: Sequence[ast.stmt]) -> bool:
    if not stmts:
        return False
    return isinstance(stmts[-1], (ast.Raise, ast.Return, ast.Continue,
                                  ast.Break))


class HeaderAnalysis:
    """Per-function view of length-versioned positional header access."""

    def __init__(self, fn_node: ast.AST):
        self.fn_node = fn_node
        #: name -> floors from validate-or-bail checks (len(name) >= bound)
        self.validated: Dict[str, List[Bound]] = {}
        #: name -> floors from plain guard tests seen anywhere (used to
        #: infer the author's base length when nothing validates)
        self.guards_seen: Dict[str, List[Bound]] = {}
        for node in walk_scope(fn_node):
            if isinstance(node, ast.If) and _bails(node.body):
                for nm, bs in _bail_floors(node.test).items():
                    self.validated.setdefault(nm, []).extend(bs)
            if isinstance(node, ast.Assert):
                for nm, bs in _guard_floors(node.test).items():
                    self.validated.setdefault(nm, []).extend(bs)
            if isinstance(node, (ast.If, ast.IfExp)):
                for nm, bs in _guard_floors(node.test).items():
                    self.guards_seen.setdefault(nm, []).extend(bs)

    def tracked(self, name: str) -> bool:
        return name in self.validated or name in self.guards_seen

    def base_floor(self, name: str) -> Optional[int]:
        """Indexes below this are the pinned base header — always present.

        Preference order: the strongest validate-or-bail literal floor,
        else the smallest literal guard threshold (the author's implied
        base length when reads are guarded but never validated).
        """
        lits = [k for sym, k in self.validated.get(name, []) if sym is None]
        if lits:
            return max(lits)
        lits = [k for sym, k in self.guards_seen.get(name, []) if sym is None]
        if lits:
            return min(lits)
        return None

    def symbolic_floors(self, name: str) -> List[Bound]:
        return [b for b in self.validated.get(name, []) if b[0] is not None]

    def guarded(self, sub: ast.Subscript, name: str, idx: Bound) -> bool:
        """Is this subscript dominated by a length guard that covers it?"""
        cur: ast.AST = sub
        while True:
            parent = getattr(cur, "_ba3c_parent", None)
            if parent is None or isinstance(parent, _SCOPE_NODES):
                return False
            if isinstance(parent, ast.If) and _in_stmts(parent.body, cur):
                if self._test_covers(parent.test, name, idx, cur):
                    return True
            elif isinstance(parent, ast.IfExp) and parent.body is cur:
                if self._test_covers(parent.test, name, idx, cur):
                    return True
            elif isinstance(parent, ast.BoolOp) and \
                    isinstance(parent.op, ast.And):
                j = next((k for k, v in enumerate(parent.values) if v is cur),
                         None)
                if j is not None:
                    for v in parent.values[:j]:
                        if self._test_covers(v, name, idx, cur):
                            return True
            cur = parent

    def _test_covers(self, test: ast.AST, name: str, idx: Bound,
                     exclude: ast.AST) -> bool:
        sym, off = idx
        for fsym, fk in _guard_floors(test).get(name, []):
            if sym is None and fsym is None and off < fk:
                return True
            if sym is not None and fsym == sym and off < fk:
                return True
        return False

    def positional_reads(self, name_filter=None):
        """(subscript node, container dotted name, Bound index) for every
        positional integer-indexed read in this function."""
        out = []
        for node in walk_scope(self.fn_node):
            if not isinstance(node, ast.Subscript):
                continue
            nm = dotted_name(node.value)
            if nm is None or (name_filter is not None and nm != name_filter):
                continue
            if isinstance(node.slice, ast.Slice):
                continue
            b = _bound(node.slice)
            if b is None:
                continue
            sym, k = b
            if sym is None and k < 0:
                continue  # negative indexes count from the tail by design
            out.append((node, nm, b))
        return out


def _in_stmts(stmts: Sequence[ast.stmt], node: ast.AST) -> bool:
    for stmt in stmts:
        for sub in ast.walk(stmt):
            if sub is node:
                return True
    return False


# --------------------------------------------------------------------------
# codec-pair symmetry (W1)
# --------------------------------------------------------------------------


def packer_frame_count(fn_node: ast.AST) -> Optional[int]:
    """Number of frames a packer emits, when statically certain; else None.

    Two shapes count: a single ``return [a, b, c]`` list literal, or a
    body-level ``frames = [...]`` followed only by body-level
    ``frames.append(x)`` statements and ``return frames``. Any starred
    element, conditional append, or loop append -> None (unknown), so
    variable-frame packers like pack_block are skipped, not mis-counted.
    """
    returns = [n for n in walk_scope(fn_node)
               if isinstance(n, ast.Return) and n.value is not None]
    if len(returns) == 1 and isinstance(returns[0].value, ast.List):
        lst = returns[0].value
        if any(isinstance(e, ast.Starred) for e in lst.elts):
            return None
        return len(lst.elts)
    var: Optional[str] = None
    count = 0
    body = getattr(fn_node, "body", [])
    toplevel_appends: Set[int] = set()
    for stmt in body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name) and \
                isinstance(stmt.value, ast.List):
            if any(isinstance(e, ast.Starred) for e in stmt.value.elts):
                return None
            var = stmt.targets[0].id
            count = len(stmt.value.elts)
        elif var is not None and isinstance(stmt, ast.Expr) and \
                isinstance(stmt.value, ast.Call) and \
                isinstance(stmt.value.func, ast.Attribute) and \
                stmt.value.func.attr == "append" and \
                isinstance(stmt.value.func.value, ast.Name) and \
                stmt.value.func.value.id == var:
            count += 1
            toplevel_appends.add(id(stmt.value))
    if var is None:
        return None
    if not (len(returns) == 1 and isinstance(returns[0].value, ast.Name)
            and returns[0].value.id == var):
        return None
    for n in walk_scope(fn_node):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) and \
                n.func.attr in ("append", "extend", "insert") and \
                isinstance(n.func.value, ast.Name) and \
                n.func.value.id == var and id(n) not in toplevel_appends:
            return None  # conditional/looped growth: frame count is dynamic
    return count


def first_positional_param(fn_node: ast.AST) -> Optional[str]:
    args = fn_node.args
    names = [a.arg for a in args.posonlyargs + args.args if a.arg != "self"]
    return names[0] if names else None


def max_positional_index(fn_node: ast.AST,
                         param: str) -> Optional[Tuple[int, ast.Subscript]]:
    """Largest literal integer subscript on ``param`` in the function."""
    best: Optional[Tuple[int, ast.Subscript]] = None
    for node in walk_scope(fn_node):
        if not isinstance(node, ast.Subscript):
            continue
        if dotted_name(node.value) != param:
            continue
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, int) and \
                not isinstance(sl.value, bool) and sl.value >= 0:
            if best is None or sl.value > best[0]:
                best = (sl.value, node)
    return best


# --------------------------------------------------------------------------
# metrics facts (W5)
# --------------------------------------------------------------------------


class SeriesDecl:
    """One literal ``counter/gauge/histogram("name")`` creation."""

    __slots__ = ("name", "kind", "path", "node")

    def __init__(self, name: str, kind: str, path: str, node: ast.Call):
        self.name = name
        self.kind = kind
        self.path = path
        self.node = node


_METRIC_KINDS = {"counter", "gauge", "histogram"}


def collect_series(project: Project) -> List[SeriesDecl]:
    out: List[SeriesDecl] = []
    for path, mod in sorted(project.by_path.items()):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _METRIC_KINDS and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                out.append(SeriesDecl(node.args[0].value, node.func.attr,
                                      path, node))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "counter_pair":
                # two counter series behind one paired counter
                out.extend(SeriesDecl(arg.value, "counter", path, node)
                           for arg in node.args
                           if isinstance(arg, ast.Constant)
                           and isinstance(arg.value, str))
    return out


def counter_bindings(mod: ModuleSyms) -> Dict[str, str]:
    """Dotted variable/attribute name -> counter series name, for every
    ``x = <reg>.counter("name")`` binding in the module."""
    out: Dict[str, str] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign):
            value, targets = node.value, node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            value, targets = node.value, [node.target]
        else:
            continue
        if isinstance(value, ast.Call) and \
                isinstance(value.func, ast.Attribute) and \
                value.func.attr == "counter" and value.args and \
                isinstance(value.args[0], ast.Constant) and \
                isinstance(value.args[0].value, str):
            for t in targets:
                dn = dotted_name(t)
                if dn:
                    out[dn] = value.args[0].value
    return out


def sign_guarded(call: ast.Call, operand_name: str) -> bool:
    """True when ``call`` (an ``.inc(-x)``) is dominated by an ``x < 0`` /
    ``x <= 0`` test — the sign-split idiom that makes the negation safe."""
    cur: ast.AST = call
    while True:
        parent = getattr(cur, "_ba3c_parent", None)
        if parent is None or isinstance(parent, _SCOPE_NODES):
            return False
        if isinstance(parent, ast.If) and _in_stmts(parent.body, cur):
            if _tests_negative(parent.test, operand_name):
                return True
        elif isinstance(parent, ast.IfExp) and parent.body is cur:
            if _tests_negative(parent.test, operand_name):
                return True
        cur = parent


def _tests_negative(test: ast.AST, name: str) -> bool:
    if isinstance(test, ast.BoolOp):
        return any(_tests_negative(v, name) for v in test.values)
    if not isinstance(test, ast.Compare) or len(test.ops) != 1:
        return False
    left, op, right = test.left, test.ops[0], test.comparators[0]
    if dotted_name(left) == name and isinstance(op, (ast.Lt, ast.LtE)) and \
            isinstance(right, ast.Constant) and right.value == 0:
        return True
    if dotted_name(right) == name and isinstance(op, (ast.Gt, ast.GtE)) and \
            isinstance(left, ast.Constant) and left.value == 0:
        return True
    return False


# --------------------------------------------------------------------------
# docs/observability.md series catalog (W5)
# --------------------------------------------------------------------------

_SERIES_TOKEN_RE = re.compile(r"`([a-z][a-z0-9_<>]*)`")
_TEMPLATE_PART_RE = re.compile(r"<[a-z_]+>")


class Catalog:
    """Parsed series tables from docs/observability.md.

    Only rows of tables whose header's first column is ``series`` count —
    endpoint/hop tables and prose mentions never pollute the contract.
    """

    def __init__(self, path: str, display_path: str):
        self.display_path = display_path
        #: exact series name -> first docs line declaring it
        self.names: Dict[str, int] = {}
        #: (compiled template regex, docs line) for `hop_<name>_s` style rows
        self.templates: List[Tuple["re.Pattern[str]", int]] = []
        with open(path, "r", encoding="utf-8") as fh:
            lines = fh.read().splitlines()
        in_series_table = False
        for i, line in enumerate(lines, start=1):
            stripped = line.strip()
            if not stripped.startswith("|"):
                in_series_table = False
                continue
            cells = [c.strip() for c in stripped.strip("|").split("|")]
            if not cells:
                continue
            first = cells[0]
            if first.lower() == "series":
                in_series_table = True
                continue
            if not in_series_table or set(first) <= {"-", ":", " "}:
                continue
            for m in _SERIES_TOKEN_RE.finditer(first):
                tok = m.group(1)
                if "<" in tok:
                    pat = "^" + _TEMPLATE_PART_RE.sub(
                        "[a-z0-9_]+", re.escape(tok).replace(
                            r"\<", "<").replace(r"\>", ">")) + "$"
                    self.templates.append((re.compile(pat), i))
                else:
                    self.names.setdefault(tok, i)

    def documents(self, name: str) -> bool:
        if name in self.names:
            return True
        return any(pat.match(name) for pat, _ in self.templates)


def load_catalog(root: str) -> Optional[Catalog]:
    path = os.path.join(root, "docs", "observability.md")
    if not os.path.isfile(path):
        return None
    return Catalog(path, os.path.normpath(path))
