"""Diagnostic objects for the static program analyzer.

The reference framework reports program bugs through ~40 C++ IR passes
(paddle/fluid/framework/ir/) each with ad-hoc logging; here every pass
emits the same structured `Diagnostic` so results render uniformly as
text, JSON (tools/pt_lint.py), or graphviz highlights (debugger.py).

Code table (docs/analysis.md has the full semantics):

  D001 error    def-use violation (read before any definition)
  D002 warning  unknown op (no registered JAX impl)
  D003 error    shape/dtype mismatch or abstract-interp failure
  D004 info     64-bit dtype narrowed to 32-bit under x64-disabled
  D005 warning  dead op (outputs reach no fetch/persistable/sub-block)
  D006 info     unused var (defined, never read)
  D007 warning  parameter read after in-block writeback
  D008 warning  feed shadows a parameter / persistable
  D009 warning  persistable double-write within one block
  D010 warning  retrace hazard: dynamic feed dim not covered by buckets
  D011 warning  retrace hazard: array-valued / per-run-varying attr
  D012 warning  numerical hazard: unclipped log/div/exp
  D013 warning  numerical hazard: softmax built without max-subtraction
  D014 warning  degenerate learning-rate decay constant
  D015 info     op not emit-capable (direct emitter would fall back)
  D017 error    sharding conflict (producers force incompatible specs)
  D018 warning  implicit reshard (consumed spec differs from delivered)
  D019 error    mesh-axis mismatch (spec names an undeclared mesh axis)
  D020 error    memplan over budget (static HBM footprint > device limit)
  D021 warning  donation hazard (host array / param read after donation)
  D099 info     lint pass crashed (analyzer bug, never fatal)
"""

__all__ = ['Diagnostic', 'LintResult', 'LintError', 'SEVERITIES', 'CODES',
           'DIAG_JSON_KEYS', 'RESULT_JSON_KEYS']

SEVERITIES = ('info', 'warning', 'error')
_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}

CODES = {
    'D001': 'def-use violation',
    'D002': 'unknown op',
    'D003': 'shape/dtype mismatch',
    'D004': '64-bit narrowing',
    'D005': 'dead op',
    'D006': 'unused var',
    'D007': 'param read after writeback',
    'D008': 'feed shadows persistable',
    'D009': 'persistable double-write',
    'D010': 'unbucketed dynamic feed dim',
    'D011': 'per-run-varying attr',
    'D012': 'unclipped log/div/exp',
    'D013': 'softmax without max-subtraction',
    'D014': 'degenerate lr decay',
    'D015': 'op not emit-capable',
    'D017': 'sharding conflict',
    'D018': 'implicit reshard',
    'D019': 'mesh-axis mismatch',
    'D020': 'memplan over device limit',
    'D021': 'donation hazard',
    'D099': 'lint pass crashed',
}

# The JSON shapes `Diagnostic.to_dict` / `LintResult.to_dict` emit —
# pinned as constants so tools (pt_lint --json consumers, the ci_smoke
# schema gate) validate against the same source of truth the renderer
# uses instead of a hand-copied list.
DIAG_JSON_KEYS = ('code', 'severity', 'message', 'op_type', 'op_index',
                  'block_idx', 'block_path', 'var', 'fixit', 'source_loc',
                  'pass')
RESULT_JSON_KEYS = ('diagnostics', 'errors', 'warnings', 'infos')


class Diagnostic(object):
    """One finding: code + severity + location (op, var, block path)."""

    __slots__ = ('code', 'severity', 'message', 'op_type', 'op_index',
                 'block_idx', 'block_path', 'var', 'fixit', 'source_loc',
                 'pass_name')

    def __init__(self, code, severity, message, op=None, op_index=None,
                 block_idx=None, block_path=None, var=None, fixit=None,
                 source_loc=None, pass_name=None):
        if code not in CODES:
            raise ValueError('unknown diagnostic code %r' % code)
        if severity not in SEVERITIES:
            raise ValueError('bad severity %r' % severity)
        self.code = code
        self.severity = severity
        self.message = message
        self.op_type = getattr(op, 'type', op)
        self.op_index = op_index
        self.block_idx = block_idx
        self.block_path = block_path
        self.var = var
        self.fixit = fixit
        self.source_loc = source_loc or getattr(op, 'source_loc', None)
        self.pass_name = pass_name

    @property
    def rank(self):
        return _SEV_RANK[self.severity]

    def location(self):
        parts = []
        if self.block_path:
            parts.append(self.block_path)
        elif self.block_idx is not None:
            parts.append('block %d' % self.block_idx)
        if self.op_type is not None:
            parts.append('op#%s %s' % (self.op_index
                                       if self.op_index is not None else '?',
                                       self.op_type))
        if self.var:
            parts.append("var '%s'" % self.var)
        return ' '.join(parts)

    def render(self):
        loc = self.location()
        line = '%s %-7s %s%s' % (self.code, self.severity,
                                 ('[%s] ' % loc) if loc else '',
                                 self.message)
        if self.fixit:
            line += '  (fix: %s)' % self.fixit
        if self.source_loc:
            line += '  @ %s:%s' % tuple(self.source_loc)
        return line

    def to_dict(self):
        return {'code': self.code, 'severity': self.severity,
                'message': self.message, 'op_type': self.op_type,
                'op_index': self.op_index, 'block_idx': self.block_idx,
                'block_path': self.block_path, 'var': self.var,
                'fixit': self.fixit,
                'source_loc': (list(self.source_loc)
                               if self.source_loc else None),
                'pass': self.pass_name}

    __repr__ = __str__ = lambda self: self.render()


class LintResult(object):
    """Ordered collection of diagnostics from one lint run."""

    def __init__(self, diagnostics=None):
        self.diagnostics = list(diagnostics or ())

    def add(self, diag):
        self.diagnostics.append(diag)

    def extend(self, diags):
        self.diagnostics.extend(diags)

    def __iter__(self):
        return iter(self.diagnostics)

    def __len__(self):
        return len(self.diagnostics)

    def __bool__(self):
        return bool(self.diagnostics)

    @property
    def errors(self):
        return [d for d in self.diagnostics if d.severity == 'error']

    @property
    def warnings(self):
        return [d for d in self.diagnostics if d.severity == 'warning']

    @property
    def infos(self):
        return [d for d in self.diagnostics if d.severity == 'info']

    def has_errors(self):
        return any(d.severity == 'error' for d in self.diagnostics)

    def codes(self):
        return sorted({d.code for d in self.diagnostics})

    def at_least(self, severity):
        """Diagnostics at `severity` or worse."""
        floor = _SEV_RANK[severity]
        return [d for d in self.diagnostics if d.rank >= floor]

    def op_findings(self):
        """(block_idx, op_index) -> worst severity, for graph highlighting
        (debugger.draw_block_graphviz / net_drawer.draw_graph)."""
        worst = {}
        for d in self.diagnostics:
            if d.op_index is None or d.block_idx is None:
                continue
            key = (d.block_idx, d.op_index)
            if key not in worst or _SEV_RANK[worst[key]] < d.rank:
                worst[key] = d.severity
        return worst

    def render(self, min_severity='info'):
        diags = sorted(self.at_least(min_severity),
                       key=lambda d: (-d.rank, d.code))
        if not diags:
            return 'lint: no findings at severity >= %s' % min_severity
        lines = [d.render() for d in diags]
        lines.append('lint: %d error(s), %d warning(s), %d info(s)'
                     % (len(self.errors), len(self.warnings),
                        len(self.infos)))
        return '\n'.join(lines)

    def to_dict(self):
        return {'diagnostics': [d.to_dict() for d in self.diagnostics],
                'errors': len(self.errors), 'warnings': len(self.warnings),
                'infos': len(self.infos)}

    __repr__ = __str__ = lambda self: self.render()


class LintError(ValueError):
    """Raised under PT_LINT=strict when error-severity findings exist.
    Subclasses ValueError so callers that caught the old validate_def_use
    error keep working unchanged."""

    def __init__(self, result, header='program lint failed'):
        self.result = result
        errs = result.errors if isinstance(result, LintResult) else [result]
        msg = '%s:\n%s' % (header, '\n'.join(d.render() for d in errs))
        super(LintError, self).__init__(msg)
