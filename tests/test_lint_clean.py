"""The regression ratchet: the full analyzer over the in-tree
`nomad_tpu/` package must report ZERO unsuppressed findings — every
surviving finding carries a justified `# nomad-lint: allow[...]`.

This is the mechanical enforcement of the r6/r7 invariants ("zero host
syncs in the steady-state loop", "no silent recompiles", "no lock held
across dispatch", "no undocumented governor knobs"): a PR that
reintroduces one fails tier-1 here."""

import ast
import os
import subprocess
import sys

from nomad_tpu.analysis import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tree_is_lint_clean():
    findings = run(["nomad_tpu"], root=REPO)
    unsuppressed = [f for f in findings if not f.suppressed]
    assert not unsuppressed, "\n" + "\n".join(
        f.render() for f in unsuppressed)
    # the justified escape hatches exist and stay few: if this number
    # climbs, the fences are being papered over instead of used
    assert len(findings) <= 12


def test_nothing_under_nomad_tpu_imports_the_bench_package():
    """The arrows point down: nomad_tpu/bench/ holds what benchmarks
    and tests build clusters with, and no module of the program
    imports it (until PR 29 the collector and the chaos matrix took
    flatness_verdict from bench/soak.py)."""
    pkg = os.path.join(REPO, "nomad_tpu")
    uphill = []
    for dirpath, _dirs, files in os.walk(pkg):
        rel = os.path.relpath(dirpath, pkg)
        if rel.split(os.sep)[0] in ("bench", "__pycache__"):
            continue
        here = ["nomad_tpu"] + ([] if rel == "." else rel.split(os.sep))
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    base = here[:len(here) - node.level + 1] \
                        if node.level else []
                    mod = ".".join(
                        base + ([node.module] if node.module else []))
                    mods = [mod] + [f"{mod}.{a.name}" for a in node.names]
                else:
                    continue
                if any(m == "nomad_tpu.bench"
                       or m.startswith("nomad_tpu.bench.") for m in mods):
                    uphill.append(f"{os.path.relpath(path, REPO)}:"
                                  f"{node.lineno}")
    assert not uphill, uphill


def test_module_entrypoint_exit_codes():
    """`python -m nomad_tpu.analysis nomad_tpu/` exits 0 on the clean
    tree (the acceptance-criteria invocation) and non-zero when given
    a file with a violation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, "-m", "nomad_tpu.analysis", "nomad_tpu"],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr

    # from OUTSIDE the repo the path-scoped passes must still engage
    # (paths normalize against the repo root, not the cwd) — a silent
    # scope-to-nothing here is a false clean from the ratchet itself
    import json
    expected_suppressed = len(run(["nomad_tpu"], root=REPO))
    out_env = dict(env, PYTHONPATH=REPO)
    outside = subprocess.run(
        [sys.executable, "-m", "nomad_tpu.analysis", "--json"],
        cwd="/tmp", capture_output=True, text=True, env=out_env,
        timeout=120)
    assert outside.returncode == 0, outside.stdout + outside.stderr
    payload = json.loads(outside.stdout)
    assert payload["total"] == 0
    assert payload["suppressed"] == expected_suppressed

    bad = os.path.join(REPO, "nomad_tpu", "ops", "_lint_probe_tmp.py")
    with open(bad, "w") as f:
        f.write("import numpy as np\nA = np.zeros(2, np.int64)\n")
    try:
        res = subprocess.run(
            [sys.executable, "-m", "nomad_tpu.analysis",
             "nomad_tpu/ops/_lint_probe_tmp.py"],
            cwd=REPO, capture_output=True, text=True, env=env,
            timeout=120)
        assert res.returncode == 1
        assert "dtype-discipline" in res.stdout
    finally:
        os.unlink(bad)


def test_cli_dev_lint_verb():
    """`nomad dev lint` is wired and returns the analyzer's exit
    status."""
    from nomad_tpu.cli.main import build_parser
    args = build_parser().parse_args(["dev", "lint", "nomad_tpu"])
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        assert args.fn(args) == 0
    finally:
        os.chdir(cwd)
