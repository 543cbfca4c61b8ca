"""tools/ba3clint: per-rule fixtures, suppression semantics, CLI contract.

Every rule must (a) fire on its ``*_flagged.py`` fixture and (b) stay quiet
on its ``*_clean.py`` fixture — the clean fixtures encode the idioms the
real codebase uses, so a rule regression that would spam the repo fails
here first. The CLI tests pin the exit-status contract CI gates on.
"""

import json
import os
import subprocess
import sys

import pytest

from tools.ba3clint import all_rules, lint_file, lint_paths
from tools.ba3clint.engine import suppressions

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RULE_IDS = ["J1", "J2", "J3", "J4", "J5", "J6", "A1", "A2", "A3", "A4", "A5", "A6", "A7", "A8", "A9", "A10", "A11", "A12", "A13", "A14", "A15", "A16"]


def _fixture(name):
    p = os.path.join(FIXTURES, name)
    if not os.path.exists(p):
        # path-gated rules keep their fixtures under the directory that
        # activates them (A9 lives in lint_fixtures/predict/)
        p = os.path.join(FIXTURES, "predict", name)
    return p


def _findings(name, rule_id=None):
    out = lint_file(_fixture(name), all_rules())
    if rule_id is not None:
        out = [f for f in out if f.rule == rule_id]
    return out


def test_rule_registry_complete():
    assert [r.id for r in all_rules()] == RULE_IDS
    for r in all_rules():
        assert r.name and r.summary and r.__doc__


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_flagged_fixture_fires(rule_id):
    name = f"{rule_id.lower()}_flagged.py"
    hits = _findings(name, rule_id)
    assert hits, f"{rule_id} produced no findings on {name}"


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_clean_fixture_is_silent(rule_id):
    name = f"{rule_id.lower()}_clean.py"
    hits = _findings(name, rule_id)
    assert not hits, f"{rule_id} false-positives on {name}: {hits}"


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_clean_fixtures_clean_under_every_rule(rule_id):
    """A clean fixture must not trade one rule's silence for another's noise."""
    hits = _findings(f"{rule_id.lower()}_clean.py")
    assert not hits, hits


def test_expected_flag_counts():
    """Pin a few exact counts so rules don't silently widen or narrow."""
    assert len(_findings("a4_flagged.py", "A4")) == 5
    assert len(_findings("a3_flagged.py", "A3")) == 3
    assert len(_findings("j3_flagged.py", "J3")) == 3
    assert len(_findings("a2_flagged.py", "A2")) == 2
    assert len(_findings("a6_flagged.py", "A6")) == 3
    assert len(_findings("a7_flagged.py", "A7")) == 4
    assert len(_findings("j6_flagged.py", "J6")) == 4
    assert len(_findings("a9_flagged.py", "A9")) == 5
    assert len(_findings("a11_flagged.py", "A11")) == 4
    assert len(_findings("a12_flagged.py", "A12")) == 2
    assert len(_findings("a16_flagged.py", "A16")) == 4


def test_a12_file_level_sockopt_timeout_sanctions(tmp_path):
    """RCVTIMEO/SNDTIMEO anywhere in the file bounds its blocking ops."""
    p = tmp_path / "timeo.py"
    p.write_text(
        "import zmq\n"
        "def make(context, addr):\n"
        "    dealer = context.socket(zmq.DEALER)\n"
        "    dealer.setsockopt(zmq.RCVTIMEO, 2000)\n"
        "    dealer.connect(addr)\n"
        "    return dealer.recv()\n"
    )
    hits = [f for f in lint_file(str(p), all_rules()) if f.rule == "A12"]
    assert not hits, hits


def test_a7_exempts_telemetry_package(tmp_path):
    """The registry's own implementation may use print/time.time freely."""
    d = tmp_path / "telemetry"
    d.mkdir()
    f = d / "exporters.py"
    f.write_text("import time\nfps = 3 / (time.time() - 1)\nprint('fps', fps)\n")
    assert [x for x in lint_file(str(f), all_rules()) if x.rule == "A7"] == []
    g = tmp_path / "loop.py"
    g.write_text("import time\nfps = 3 / (time.time() - 1)\n")
    assert [x for x in lint_file(str(g), all_rules()) if x.rule == "A7"]


def test_a9_applies_only_under_predict(tmp_path):
    """The same unbounded queue outside predict/ is A9-silent (A2/A7 own
    the neighboring hazards elsewhere)."""
    src = "import queue\ntasks = queue.Queue()\n"
    outside = tmp_path / "dataflow.py"
    outside.write_text(src)
    assert [f for f in lint_file(str(outside), all_rules()) if f.rule == "A9"] == []
    d = tmp_path / "predict"
    d.mkdir()
    inside = d / "server2.py"
    inside.write_text(src)
    assert [f for f in lint_file(str(inside), all_rules()) if f.rule == "A9"]


def test_suppressions_silence_real_violations():
    assert _findings("suppressed.py") == []
    # ...and the suppression parser sees all three comment forms
    with open(_fixture("suppressed.py")) as fh:
        sup = suppressions(fh.read())
    assert any("A1" in s for s in sup.values())
    assert any("A2" in s for s in sup.values())
    assert any("ALL" in s for s in sup.values())


def test_standalone_comment_suppresses_next_line():
    sup = suppressions("# ba3clint: disable=A2\nx = q.get()\n")
    assert "A2" in sup.get(1, set()) and "A2" in sup.get(2, set())


def test_syntax_error_is_reported_not_raised(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    out = lint_file(str(bad), all_rules())
    assert [f.rule for f in out] == ["E001"]


def test_submodule_import_does_not_shadow_package_resolution(tmp_path):
    """`import jax.numpy` binds the name `jax`, not `jax.numpy` — J-rules
    must still resolve jax.jit/jax.device_get in such files."""
    f = tmp_path / "sub.py"
    f.write_text(
        "import jax.numpy\n"
        "def run(fns, xs):\n"
        "    for fn in fns:\n"
        "        y = jax.jit(fn)(xs)\n"
        "        print(jax.device_get(y))\n"
    )
    rules = {fi.rule for fi in lint_file(str(f), all_rules())}
    assert {"J1", "J2"} <= rules, rules


def test_missing_lint_path_fails_loudly(tmp_path):
    """A mistyped gate target must error, not pass green over zero files."""
    with pytest.raises(FileNotFoundError):
        lint_paths([str(tmp_path / "no_such_dir")], all_rules())
    r = _run_cli(str(tmp_path / "no_such_dir"))
    assert r.returncode == 2
    assert "does not exist" in r.stderr


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.ba3clint", *args],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )


def test_cli_nonzero_on_flagged_fixture():
    r = _run_cli(_fixture("a4_flagged.py"))
    assert r.returncode == 1
    assert "[A4]" in r.stdout


def test_cli_zero_on_clean_fixture_and_list_rules():
    r = _run_cli(_fixture("a4_clean.py"))
    assert r.returncode == 0
    assert "0 findings" in r.stdout
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid in RULE_IDS:
        assert rid in r.stdout


def test_cli_json_output_and_select():
    r = _run_cli("--format", "json", "--select", "A4", _fixture("a4_flagged.py"))
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    assert payload and all(f["rule"] == "A4" for f in payload)
    assert {"path", "line", "col", "rule", "message"} <= set(payload[0])
    r = _run_cli("--select", "NOPE", _fixture("a4_flagged.py"))
    assert r.returncode == 2


def test_repo_tree_is_lint_clean():
    """The acceptance gate: the shipped tree has no unsuppressed findings."""
    findings = lint_paths(
        [
            os.path.join(REPO_ROOT, "distributed_ba3c_tpu"),
            os.path.join(REPO_ROOT, "scripts"),
            os.path.join(REPO_ROOT, "train.py"),
        ],
        all_rules(),
    )
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in findings
    )


PACKAGE = os.path.join(REPO_ROOT, "distributed_ba3c_tpu")
#: what a module of the package may not import: the root scripts and
#: anything under scripts/ (which puts itself on sys.path, so by bare stem
#: too). "bench" stays named after its deletion in PR 29: it was the one
#: such import the package had.
_ABOVE_THE_PACKAGE = (
    {"bench", "scripts"}
    | {f[:-3] for f in os.listdir(REPO_ROOT) if f.endswith(".py")}
    | {
        f[:-3]
        for f in os.listdir(os.path.join(REPO_ROOT, "scripts"))
        if f.endswith(".py")
    }
)


@pytest.mark.parametrize("sub", ["."] + sorted(
    d for d in os.listdir(PACKAGE)
    if os.path.isfile(os.path.join(PACKAGE, d, "__init__.py"))
))
def test_package_imports_nothing_above_it(sub):
    """Scripts import the package; the package imports no script. Read off
    the AST (function-level and try-guarded imports included), nothing
    imported. ``.`` is the package's own top-level modules."""
    import ast

    if sub == ".":
        files = [os.path.join(PACKAGE, f) for f in os.listdir(PACKAGE)]
    else:
        files = [
            os.path.join(d, f)
            for d, _, fs in os.walk(os.path.join(PACKAGE, sub)) for f in fs
        ]
    files = [f for f in files if f.endswith(".py")]
    assert files
    upward = []
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module]
            else:
                continue
            upward += [
                f"{os.path.relpath(path, REPO_ROOT)}:{node.lineno}: {n}"
                for n in names if n.split(".")[0] in _ABOVE_THE_PACKAGE
            ]
    assert upward == []


def test_cli_sarif_output(tmp_path):
    sarif_path = tmp_path / "lint.sarif"
    r = _run_cli("--sarif", str(sarif_path), _fixture("a4_flagged.py"))
    assert r.returncode == 1
    doc = json.loads(sarif_path.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "ba3clint"
    assert {rd["id"] for rd in run["tool"]["driver"]["rules"]} >= set(RULE_IDS)
    assert run["results"] and run["results"][0]["ruleId"]


def test_cli_check_suppressions(tmp_path):
    live = tmp_path / "live.py"
    live.write_text(
        "import queue\n"
        "def pull(q: 'queue.Queue'):\n"
        "    return q.get()  # ba3clint: disable=A2 — fixture\n"
    )
    stale = tmp_path / "stale.py"
    stale.write_text("x = 1  # ba3clint: disable=A2 — nothing here\n")
    assert _run_cli("--check-suppressions", str(live)).returncode == 0
    r = _run_cli("--check-suppressions", str(stale))
    assert r.returncode == 1
    assert "[S001]" in r.stdout and "A2" in r.stdout
