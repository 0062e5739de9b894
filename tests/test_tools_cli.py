"""Every tool under tools/ starts: no tier-1 test imports them, so a
module or name a tool imports that has left the tree would otherwise be
found by a user.  One case a tool: its command line comes up (exit 0)
in a process of its own, and every import statement in its source — the
ones inside functions too, which ``--help`` never reaches — resolves."""
import ast
import importlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, 'tools')

# tool -> the arguments that make it start and stop without doing work
# (lint_lite has no parser: it lints the paths it is given)
ARGS = {
    'fault_soak': ['--help'],
    'pod_soak': ['--help'],
    'serve_soak': ['--help'],
    'memwatch': ['--help'],
    'pt_lint': ['--help'],
    'lint_lite': [TOOLS],
}


def test_every_tool_has_a_case():
    found = {f[:-3] for f in os.listdir(TOOLS)
             if f.endswith('.py') and not f.startswith('_')}
    assert found == set(ARGS)


def _unresolved_imports(path):
    tree = ast.parse(open(path).read(), path)
    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            wanted = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            wanted = [(node.module, a.name) for a in node.names]
        else:
            continue
        for mod, attr in wanted:
            try:
                m = importlib.import_module(mod)
                if attr is not None and not hasattr(m, attr):
                    importlib.import_module(mod + '.' + attr)
            except ImportError as e:
                bad.append('%s:%d: %s' % (os.path.basename(path),
                                          node.lineno, e))
    return bad


@pytest.mark.parametrize('tool', sorted(ARGS))
def test_tool_starts_and_its_imports_resolve(tool, monkeypatch):
    path = os.path.join(TOOLS, tool + '.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run([sys.executable, path] + ARGS[tool], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    # the tools put tools/ and the repo on sys.path themselves
    monkeypatch.syspath_prepend(TOOLS)
    monkeypatch.syspath_prepend(REPO)
    assert _unresolved_imports(path) == []
