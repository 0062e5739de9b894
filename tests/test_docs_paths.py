"""README.md and docs/*.md describe the tree as it is: every path of the
repo a document names exists, and every ``PT_*`` switch it names is one
the code still reads.  One case a document."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ['README.md'] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, 'docs', '*.md')))

# a path of this repo: under one of its directories, or a top-level
# script or record
_PATH = re.compile(
    r'^(?:(?:tools|tests|paddle_tpu|benchmarks|docs)/[\w./-]+'
    r'|[\w-]+\.(?:py|json|jsonl|sh|toml))$')
_CODE = re.compile(r'`([^`\n]+)`')
_LINK = re.compile(r'\]\(([^)#\s]+)(?:#[^)]*)?\)')
_SWITCH = re.compile(r'\bPT_[A-Z0-9_]*[A-Z0-9]\b')


def _named_paths(doc, text):
    """(path as written, path from the repo's root) of every backticked
    path and every relative link."""
    out = []
    for m in _CODE.finditer(text):
        # `tools/serve_soak.py --assert-slo` names tools/serve_soak.py;
        # `paddle_tpu/ops/loss.py:42` names the file
        word = m.group(1).split()[0].split(':')[0].rstrip('/.,')
        if _PATH.match(word):
            out.append((word, word))
    for m in _LINK.finditer(text):
        target = m.group(1)
        if '://' in target or target.startswith('mailto:'):
            continue
        out.append((target, os.path.normpath(
            os.path.join(os.path.dirname(doc), target))))
    return out


@pytest.fixture(scope='module')
def sources():
    """{path from the root: text} of the code that reads switches and
    writes artefacts."""
    out = {}
    for top in ('paddle_tpu', 'tools', 'benchmarks', 'tests/conftest.py',
                'chip_smoke.py'):
        path = os.path.join(REPO, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith(('.py', '.sh'))]
        for f in files:
            out[os.path.relpath(f, REPO)] = open(f).read()
    return out


def _exists(rel, sources):
    if os.path.exists(os.path.join(REPO, rel)):
        return True
    if '/' in rel:
        return False
    # a bare name: a module named by its basename (`decode.py`), or an
    # artefact the code writes at run time (`MANIFEST.json`)
    return any(os.path.basename(p) == rel or repr(rel) in text
               for p, text in sources.items())


@pytest.mark.parametrize('doc', DOCS)
def test_paths_a_document_names_exist(doc, sources):
    text = open(os.path.join(REPO, doc)).read()
    missing = sorted({written for written, rel in _named_paths(doc, text)
                      if not _exists(rel, sources)})
    assert missing == [], '%s names paths that are not in the tree' % doc


@pytest.mark.parametrize('doc', DOCS)
def test_switches_a_document_names_are_read(doc, sources):
    text = open(os.path.join(REPO, doc)).read()
    read = set(_SWITCH.findall('\n'.join(sources.values())))
    unknown = sorted(set(_SWITCH.findall(text)) - read)
    assert unknown == [], '%s names switches nothing reads' % doc
