"""The tree held against itself (PR 28).

Scripts run by hand on the chip, shell entry points and documents rot
because nothing in tier-1 notices when a PR deletes what they name.
These tests read files only (``ast``, ``re``; one subprocess, named
below; no JAX work):

* every ``keystone_tpu`` / ``tools`` / ``benchmarks`` import of a script
  under ``tools/`` and of the two root scripts names a module that
  exists and, for ``from``-imports, a name that module defines, and
  the script uses no name it binds nowhere;
* every ``*.py`` path and ``python -m keystone_tpu <subcommand>`` a
  shell entry point names exists;
* every back-ticked path of this repo in the documents exists;
* the ``KEYSTONE_*`` names the code reads are exactly the rows of the
  one table in ``README.md`` ("Environment knobs");
* the measurement retired in PR 28 stays retired.

History is exempt and lives in ``CHANGES.md``, ``ROADMAP.md``'s
"Recent", ``PERF.md`` section 6, ``ISSUE.md`` and the driver's
``PERF_LEDGER.jsonl`` (it quotes PR titles); the reference's ``.scala``
paths are not paths of this repo.
"""
import ast
import builtins
import fnmatch
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: top-level packages of this repo that a script may import from
OWN_PACKAGES = ("keystone_tpu", "tools", "benchmarks")

SCRIPTS = sorted(
    os.path.join("tools", f) for f in os.listdir(os.path.join(REPO, "tools"))
    if f.endswith(".py")) + ["chip_smoke.py", "__graft_entry__.py"]
SHELL_SCRIPTS = ["bin/ci.sh", "bin/run-pipeline.sh",
                 "bin/keystone-tpu-pod.sh"]
DOCUMENTS = ["README.md", "PERF.md", "PERFORMANCE.md", "MIGRATION.md",
             "PARITY.md", "CLUSTER.md"]

#: names a document gives to files a RUN writes, or to a reader's own
#: files; they are examples, not files of the checkout
EXAMPLE_PATHS = frozenset({
    "trace.json", "run.perfetto.json", "report.json",   # --trace-out / --json
    ".perfetto.json",                                   # a suffix
    ".xla_cache/", "chiprun_out/",                      # made at run time
})

#: files of the reference (KeystoneML) that MIGRATION / PARITY / CLUSTER
#: map onto this repo's (its ``.scala`` paths never look like ours)
REFERENCE_PATHS = frozenset({"EC2.md", "keystone-ec2.sh",
                             "bin/keystone-ec2.sh"})


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


# -- imports of the hand-run scripts -----------------------------------------

def _module_file(dotted):
    """The file of ``dotted`` inside this checkout, or None."""
    base = os.path.join(REPO, *dotted.split("."))
    for cand in (base + ".py", os.path.join(base, "__init__.py")):
        if os.path.isfile(cand):
            return cand
    return None


def _is_module(dotted):
    return (_module_file(dotted) is not None
            or os.path.isdir(os.path.join(REPO, *dotted.split("."))))


def _defined_names(path):
    """Names a module binds at its top level, ``if``/``try`` bodies
    included (re-exports are imports, so they count)."""
    names = set()

    def visit(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names.add(node.name)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for a in node.names:
                    names.add((a.asname or a.name).split(".")[0])
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                names.update(n.id for t in targets for n in ast.walk(t)
                             if isinstance(n, ast.Name))
            elif isinstance(node, (ast.If, ast.Try)):
                visit(node.body + node.orelse
                      + getattr(node, "finalbody", [])
                      + [n for h in getattr(node, "handlers", [])
                         for n in h.body])

    with open(path, encoding="utf-8") as f:
        visit(ast.parse(f.read()).body)
    return names


def _unbound_names(tree):
    """Names a script loads and binds nowhere (whatever the scope: an
    over-estimate of what is bound, so what is left is certainly
    missing). ``tools/fleet_gate.py`` used ``jax`` for seven PRs after
    its import was deleted; ``bin/ci.sh`` died there."""
    bound = set(dir(builtins)) | {"__file__", "__name__", "__doc__"}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            bound.add(n.name)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            bound.update((a.asname or a.name).split(".")[0] for a in n.names)
        elif isinstance(n, ast.arg):
            bound.add(n.arg)
        elif isinstance(n, ast.Name) and not isinstance(n.ctx, ast.Load):
            bound.add(n.id)
        elif isinstance(n, ast.ExceptHandler) and n.name:
            bound.add(n.name)
        elif isinstance(n, (ast.Global, ast.Nonlocal)):
            bound.update(n.names)
    return sorted({f"line {n.lineno}: {n.id}" for n in ast.walk(tree)
                   if isinstance(n, ast.Name) and n.id not in bound})


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_imports_resolve(script):
    tree = ast.parse(_read(script))
    broken = [f"{name} is bound nowhere" for name in _unbound_names(tree)]
    for node in ast.walk(tree):               # function-level imports too
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] in OWN_PACKAGES \
                        and not _is_module(a.name):
                    broken.append(f"line {node.lineno}: import {a.name}")
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and node.module \
                and node.module.split(".")[0] in OWN_PACKAGES:
            if not _is_module(node.module):
                broken.append(f"line {node.lineno}: from {node.module}")
                continue
            path = _module_file(node.module)
            defined = _defined_names(path) if path else set()
            for a in node.names:
                if a.name not in defined \
                        and not _is_module(f"{node.module}.{a.name}"):
                    broken.append(f"line {node.lineno}: from {node.module} "
                                  f"import {a.name}")
    assert not broken, f"{script} imports what the tree lacks: {broken}"


# -- shell entry points ------------------------------------------------------

def _cli_subcommands():
    """The words ``python -m keystone_tpu <word>`` accepts beside an
    app's name: the literals ``main`` compares its first argument with."""
    words = set()
    for node in ast.walk(ast.parse(_read("keystone_tpu/__main__.py"))):
        if isinstance(node, ast.Compare) \
                and isinstance(node.left, ast.Name) and node.left.id == "app":
            for c in node.comparators:
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    words.add(c.value)
    return words


def _exists(path):
    return any(os.path.exists(os.path.join(REPO, base, path))
               for base in ("", "keystone_tpu"))


@pytest.mark.parametrize("script", SHELL_SCRIPTS)
def test_shell_entry_points_name_files_that_exist(script):
    text = _read(script)
    missing = []
    for tok in set(re.findall(r"[\w./${}\"-]*\w\.py\b", text)):
        path = re.sub(r'^"?\$\{?KEYSTONE_HOME\}?"?/', "", tok).strip('"')
        if "$" in path:
            continue                  # built from a variable: not a literal
        if not _exists(path):
            missing.append(path)
    subcommands = _cli_subcommands()
    assert {"check", "serve", "numerics"} <= subcommands
    for word in set(re.findall(r"-m keystone_tpu(\.[\w.]+|\s+[\w.-]+)", text)):
        if word.startswith("."):
            ok = _is_module("keystone_tpu" + word)
        else:
            ok = word.strip() in subcommands
        if not ok:
            missing.append(f"python -m keystone_tpu{word}")
    assert not missing, f"{script} names what the tree lacks: {missing}"


# -- documents ---------------------------------------------------------------

_PATHLIKE = re.compile(r"^([\w.-][\w./-]*?\.(?:py|md|json|sh)|[\w.-][\w./-]*/)"
                       r"(?=$|:)")


def _document_text(doc):
    text = _read(doc)
    if doc == "PERF.md":              # section 6 is history
        start = text.index("\n## 6. ")
        end = text.index("\n## 7. ")
        text = text[:start] + text[end:]
    return re.sub(r"```.*?```", "", text, flags=re.S)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_files_that_exist(doc):
    dead = []
    for tok in set(re.findall(r"`([^`\n]+)`", _document_text(doc))):
        m = _PATHLIKE.match(tok)
        if not m:
            continue
        path = m.group(1)
        if path in EXAMPLE_PATHS or path in REFERENCE_PATHS:
            continue
        if not _exists(path):
            dead.append(path)
    assert not dead, f"{doc} names files the tree lacks: {sorted(dead)}"


# -- environment knobs -------------------------------------------------------

_KNOB = re.compile(r"^KEYSTONE_[A-Z0-9_]+$")


def _knobs_in_code():
    """``KEYSTONE_*`` names that are whole string literals of the Python
    under the package, the benchmark, the tools and ``chip_smoke.py``,
    keys of the benchmark's configuration files, and variables a script
    under ``bin/`` expands without assigning them itself."""
    found = {}
    py = ["chip_smoke.py"]
    for root in ("keystone_tpu", "benchmarks", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), REPO)
                if f.endswith(".py"):
                    py.append(rel)
                elif f.endswith(".json"):
                    for name in re.findall(r'"(KEYSTONE_[A-Z0-9_]+)"\s*:',
                                           _read(rel)):
                        found.setdefault(name, rel)
    for rel in py:
        for node in ast.walk(ast.parse(_read(rel))):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and _KNOB.match(node.value):
                found.setdefault(node.value, rel)
    for f in os.listdir(os.path.join(REPO, "bin")):
        rel = os.path.join("bin", f)
        text = _read(rel)
        assigned = set(re.findall(r"^\s*(?:export\s+)?(KEYSTONE_[A-Z0-9_]+)=",
                                  text, flags=re.M))
        for name in re.findall(r"\$\{?(KEYSTONE_[A-Z0-9_]+)", text):
            if name not in assigned:
                found.setdefault(name, rel)
    return found


def _knobs_in_table():
    text = _read("README.md")
    start = text.index("\n## Environment knobs")
    section = text[start:text.index("\n## ", start + 1)]
    return set(re.findall(r"^\| `(KEYSTONE_[A-Z0-9_]+)` \|", section,
                          flags=re.M))


@pytest.mark.parametrize("direction", ["read_but_undocumented",
                                       "documented_but_unread"])
def test_env_knobs_match_their_table(direction):
    code, table = _knobs_in_code(), _knobs_in_table()
    assert len(table) >= 20           # the table was found and parsed
    if direction == "read_but_undocumented":
        extra = {k: v for k, v in code.items() if k not in table}
        assert not extra, (
            f"read by the code, no row in README 'Environment knobs': {extra}")
    else:
        stale = sorted(table - set(code))
        assert not stale, f"rows of the table that nothing reads: {stale}"


# -- the retired measurement -------------------------------------------------

_RETIRED = re.compile(
    r"\b(bench\.py|benchdiff|BENCH_r[0-9]+|MULTICHIP_r[0-9]+"
    r"|KEYSTONE_BENCH_[A-Z_]+)\b")
_HISTORY = {"CHANGES.md", "ROADMAP.md", "ISSUE.md", "PERF_LEDGER.jsonl",
            os.path.join("tests", "test_tree_consistency.py")}


def _ignored():
    """Directory names, file names and globs that ``.gitignore`` lists:
    what a run leaves behind is not the tree."""
    lines = _read(".gitignore").split()
    dirs = {".git"} | {pat.rstrip("/") for pat in lines if pat.endswith("/")}
    return dirs, [pat for pat in lines if not pat.endswith("/")]


def _tracked_text_files():
    dirs, files = _ignored()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in dirs]
        for f in filenames:
            if any(fnmatch.fnmatch(f, pat) for pat in files):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), REPO)
            try:
                yield rel, _read(rel)
            except UnicodeDecodeError:
                continue              # a binary fixture names nothing


@pytest.mark.parametrize("case", ["no_file_names_it", "cli_refuses_it"])
def test_retired_measurement_stays_retired(case):
    if case == "no_file_names_it":
        hits = []
        for rel, text in _tracked_text_files():
            if rel in _HISTORY:
                continue
            if rel == "PERF.md":
                text = _document_text(rel)
            hits += [f"{rel}: {m.group(0)}" for m in _RETIRED.finditer(text)]
        assert not hits, f"the retired measurement is named again: {hits[:20]}"
    else:
        # the one subprocess of this file: a retired subcommand is an
        # unknown app like any other
        proc = subprocess.run(
            [sys.executable, "-m", "keystone_tpu", "benchdiff", "a", "b"],
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2, proc.stderr[-2000:]
        assert "unknown app 'benchdiff'" in proc.stderr
