"""Graph IR: Program / Block / Operator / Variable.

Capability parity with reference python/paddle/fluid/framework.py
(Program, Block, Operator, Variable, program_guard, name_scope) — redesigned
TPU-first: the IR is pure Python (no protobuf/C++ desc), and a Block is not
interpreted op-by-op like the reference's C++ Executor; it is lowered in one
piece to a single XLA computation by tracing the registered JAX impl of every
op (see core/executor.py).  Shape inference runs `jax.eval_shape` on the op
impls at graph-construction time with two trial batch sizes, so batch dims
stay symbolic (-1) while feature dims are static — exactly what XLA needs.
"""
import contextlib
import copy
import numpy as np

from . import unique_name
from .dtypes import convert_dtype, dtype_str
from . import infer_memo
from . import registry
from .. import observability as _obs

__all__ = [
    'Program', 'Block', 'Operator', 'Variable', 'Parameter', 'program_guard',
    'default_main_program', 'default_startup_program', 'switch_main_program',
    'switch_startup_program', 'name_scope', 'cpu_places', 'cuda_places',
    'CPUPlace', 'CUDAPlace', 'TPUPlace', 'is_compiled_with_cuda',
    'get_flags', 'set_flags',
]

# Imperative (dygraph) mode: slot holds the active _ImperativeState while
# inside imperative.guard(); Block.append_op then executes ops eagerly.
_imperative = [None]

# ---------------------------------------------------------------- places

class Place(object):
    """Device spec. On TPU-native builds every place lowers to the same XLA
    backend; the class is kept for API parity with the reference's
    CPUPlace/CUDAPlace (paddle/fluid/platform/place.h)."""

    kind = 'tpu'

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id


class TPUPlace(Place):
    kind = 'tpu'


class CPUPlace(Place):
    kind = 'cpu'


class CUDAPlace(Place):
    # kept for source compatibility; maps to the default accelerator
    kind = 'tpu'


class CUDAPinnedPlace(Place):
    kind = 'cpu'


def cpu_places(device_count=None):
    return [CPUPlace(0)]


def cuda_places(device_ids=None):
    import jax
    n = len(jax.devices())
    ids = device_ids if device_ids is not None else range(n)
    return [TPUPlace(i) for i in ids]


def tpu_places(device_ids=None):
    return cuda_places(device_ids)


def is_compiled_with_cuda():
    return False


_flags = {}


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _flags.get(k) for k in keys}


def set_flags(d):
    _flags.update(d)


# ---------------------------------------------------------------- op role

class OpRole(object):
    Forward = 'forward'
    Backward = 'backward'
    Optimize = 'optimize'
    LRSched = 'lr_sched'
    Loss = 'loss'
    RPC = 'rpc'
    Dist = 'dist'


_current_role = [OpRole.Forward]


@contextlib.contextmanager
def op_role_guard(role):
    _current_role.append(role)
    try:
        yield
    finally:
        _current_role.pop()


# recompute (rematerialization) scopes: ops appended inside carry a
# recompute_id attr; the executor wraps each contiguous tagged run in
# jax.checkpoint, trading recompute FLOPs for activation memory
_recompute_stack = []
_recompute_counter = [0]


@contextlib.contextmanager
def recompute_scope(name=None):
    """Mark ops built inside for rematerialization (TPU-native replacement
    for the reference's memory_optimize transpiler, SURVEY §2.1): their
    activations are not saved for backward — they recompute in the vjp."""
    _recompute_counter[0] += 1
    rid = name or 'remat_%d' % _recompute_counter[0]
    _recompute_stack.append(rid)
    try:
        yield
    finally:
        _recompute_stack.pop()


_name_scope_stack = ['']


@contextlib.contextmanager
def name_scope(prefix=None):
    _name_scope_stack.append(_name_scope_stack[-1] + (prefix or '') + '/')
    try:
        yield
    finally:
        _name_scope_stack.pop()


# ---------------------------------------------------------------- Variable

class Variable(object):
    """A named tensor in a Block.

    Parity: reference framework.py Variable / VarDesc. `shape` uses -1 for
    the batch dimension.  `lod_level > 0` marks a ragged sequence variable;
    TPU-native representation is dense padded data plus a companion
    `<name>@LENGTH` int32 vector (see core/lod.py), never a CPU-side LoD.
    """

    def __init__(self,
                 block,
                 name=None,
                 shape=None,
                 dtype='float32',
                 lod_level=0,
                 persistable=False,
                 stop_gradient=False,
                 is_data=False,
                 need_check_feed=False,
                 type=None,
                 initializer=None,
                 **kwargs):
        self.block = block
        if name is None:
            name = unique_name.generate('_generated_var')
        self.name = name
        self._shape = tuple(shape) if shape is not None else None
        self._dtype = dtype_str(dtype) if dtype is not None else None
        self.lod_level = lod_level
        self._persistable = persistable
        self._stop_gradient = stop_gradient
        self.is_data = is_data
        self.type = type or 'lod_tensor'
        self._sharding_spec = None  # canonical tuple spec (core/sharding.py)
        self.op = None  # producer op
        self._ivalue = None      # imperative mode: concrete jax.Array
        self._grad_value = None  # imperative mode: last computed gradient

    # ------- imperative (dygraph) API: value/grad access on eager vars -----
    def numpy(self):
        if self._ivalue is None:
            raise ValueError('var %s holds no eager value (imperative mode '
                             'only)' % self.name)
        return np.asarray(self._ivalue)

    _numpy = numpy

    def backward(self):
        from ..imperative import base as _imp_base
        _imp_base.eager_backward(self)

    _backward = backward

    def gradient(self):
        if self._grad_value is None:
            raise ValueError('var %s has no gradient (call backward first)'
                             % self.name)
        return np.asarray(self._grad_value)

    _gradient = gradient

    def clear_gradient(self):
        self._grad_value = None

    _clear_gradient = clear_gradient

    # ------- mutation-tracked attributes --------------------------------
    # In-place edits on an existing var (shape refinement, persistable
    # flips, sharding annotations) must invalidate the executor lowering
    # cache and the lint memo — both key on Program._version — so every
    # setter bumps.  Construction writes the underscore storage directly.

    def _bump_program(self):
        blk = getattr(self, 'block', None)
        if blk is not None:
            blk.program._bump()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, s):
        self._shape = tuple(s) if s is not None else None
        self._bump_program()

    @property
    def persistable(self):
        return self._persistable

    @persistable.setter
    def persistable(self, p):
        self._persistable = p
        self._bump_program()

    @property
    def stop_gradient(self):
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, s):
        self._stop_gradient = s
        self._bump_program()

    @property
    def sharding(self):
        """Canonical sharding spec (tuple per core/sharding.py) or None.
        Setting syncs Program._sharding (the executor's in_shardings
        source) with the PartitionSpec view and bumps the version."""
        return self._sharding_spec

    @sharding.setter
    def sharding(self, spec):
        from .sharding import normalize_spec, to_partition_spec
        spec = normalize_spec(spec)
        self._sharding_spec = spec
        blk = getattr(self, 'block', None)
        if blk is not None:
            prog = blk.program
            if spec is None:
                prog._sharding.pop(self.name, None)
            else:
                prog._sharding[self.name] = to_partition_spec(spec)
            prog._bump()

    @property
    def dtype(self):
        return self._dtype

    @dtype.setter
    def dtype(self, v):
        self._dtype = dtype_str(v)
        self._bump_program()

    @property
    def np_dtype(self):
        return convert_dtype(self._dtype)

    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def to_string(self, throw_on_error=False, with_details=False):
        return "var %s : shape=%s dtype=%s lod=%d%s" % (
            self.name, self.shape, self._dtype, self.lod_level,
            ' persistable' if self.persistable else '')

    __repr__ = __str__ = lambda self: self.to_string()

    # -------- math op patch (reference layers/math_op_patch.py) --------
    def _cur_block(self):
        # ops emit into the program's CURRENT block, not the var's home
        # block — an expression on a root var inside a While body must
        # land in the loop body, or it reads the pre-loop value forever
        return self.block.program.current_block()

    def _binary(self, other, op_type, reverse=False):
        block = self._cur_block()
        if isinstance(other, Variable):
            x, y = (other, self) if reverse else (self, other)
            out = block.create_var(dtype=self._dtype)
            block.append_op(type=op_type,
                           inputs={'X': x, 'Y': y},
                           outputs={'Out': out},
                           attrs={'axis': -1})
            return out
        # scalar path
        v = float(other)
        if op_type == 'elementwise_add':
            return self._scale(1.0, v)
        if op_type == 'elementwise_sub':
            if reverse:
                return self._scale(-1.0, v)
            return self._scale(1.0, -v)
        if op_type == 'elementwise_mul':
            return self._scale(v, 0.0)
        # div / pow / mod etc: materialize a constant
        out = block.create_var(dtype=self._dtype)
        const = block.create_var(dtype=self._dtype)
        block.append_op(type='fill_constant',
                       inputs={}, outputs={'Out': const},
                       attrs={'shape': [1], 'value': v, 'dtype': self._dtype})
        x, y = (const, self) if reverse else (self, const)
        block.append_op(type=op_type, inputs={'X': x, 'Y': y},
                       outputs={'Out': out}, attrs={'axis': -1})
        return out

    def _scale(self, scale, bias):
        blk = self._cur_block()
        out = blk.create_var(dtype=self._dtype)
        blk.append_op(type='scale', inputs={'X': self},
                            outputs={'Out': out},
                            attrs={'scale': float(scale), 'bias': float(bias),
                                   'bias_after_scale': True})
        return out

    def __add__(self, o):
        return self._binary(o, 'elementwise_add')

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, 'elementwise_sub')

    def __rsub__(self, o):
        return self._binary(o, 'elementwise_sub', reverse=True)

    def __mul__(self, o):
        return self._binary(o, 'elementwise_mul')

    __rmul__ = __mul__

    def __div__(self, o):
        return self._binary(o, 'elementwise_div')

    __truediv__ = __div__

    def __rdiv__(self, o):
        return self._binary(o, 'elementwise_div', reverse=True)

    __rtruediv__ = __rdiv__

    def __pow__(self, o):
        return self._binary(o, 'elementwise_pow')

    def __rpow__(self, o):
        return self._binary(o, 'elementwise_pow', reverse=True)

    def __neg__(self):
        return self._scale(-1.0, 0.0)

    def _cmp(self, other, op_type):
        blk = self._cur_block()
        out = blk.create_var(dtype='bool')
        other = other if isinstance(other, Variable) else _const_like(self, other)
        blk.append_op(type=op_type, inputs={'X': self, 'Y': other},
                            outputs={'Out': out}, attrs={})
        return out

    def __lt__(self, o):
        return self._cmp(o, 'less_than')

    def __le__(self, o):
        return self._cmp(o, 'less_equal')

    def __gt__(self, o):
        return self._cmp(o, 'greater_than')

    def __ge__(self, o):
        return self._cmp(o, 'greater_equal')

    def astype(self, dtype):
        blk = self._cur_block()
        out = blk.create_var(dtype=dtype)
        blk.append_op(type='cast', inputs={'X': self},
                            outputs={'Out': out},
                            attrs={'in_dtype': self._dtype,
                                   'out_dtype': dtype_str(dtype)})
        return out


def _const_like(var, value):
    const = var.block.create_var(dtype=var.dtype)
    var.block.append_op(type='fill_constant', inputs={}, outputs={'Out': const},
                       attrs={'shape': [1], 'value': float(value),
                              'dtype': var.dtype})
    return const


class Parameter(Variable):
    """Trainable persistable variable (reference framework.py Parameter)."""

    def __init__(self, block, shape, dtype, **kwargs):
        self.trainable = kwargs.pop('trainable', True)
        self.optimize_attr = kwargs.pop('optimize_attr', {'learning_rate': 1.0})
        self.regularizer = kwargs.pop('regularizer', None)
        self.gradient_clip_attr = kwargs.pop('gradient_clip_attr', None)
        self.do_model_average = kwargs.pop('do_model_average', None)
        self.is_distributed = kwargs.pop('is_distributed', False)
        super(Parameter, self).__init__(
            block, shape=shape, dtype=dtype, persistable=True,
            stop_gradient=False, **kwargs)


# ---------------------------------------------------------------- Operator

# Source-location capture: each Operator remembers the (file, line) of the
# model code that created it, so lint diagnostics (paddle_tpu.analysis)
# point at the user's line instead of deep framework internals.  Frames
# inside the package are skipped, EXCEPT the bundled model zoo — a finding
# in paddle_tpu/models should name the model line.  PT_SOURCE_LOC=0
# disables the walk entirely (it is a few frame hops per op).
import os as _os
import sys as _sys

_PKG_DIR = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_MODELS_DIR = _os.path.join(_PKG_DIR, 'models')
_CAPTURE_SOURCE_LOC = _os.environ.get('PT_SOURCE_LOC', '1') not in (
    '0', 'false', 'False')


def _capture_source_loc():
    if not _CAPTURE_SOURCE_LOC:
        return None
    try:
        f = _sys._getframe(2)
    except ValueError:
        return None
    depth = 0
    while f is not None and depth < 32:
        fn = f.f_code.co_filename
        if not fn.startswith(_PKG_DIR) or fn.startswith(_MODELS_DIR):
            return (fn, f.f_lineno)
        f = f.f_back
        depth += 1
    return None


class _AttrDict(dict):
    """Operator.attrs wrapper: in-place mutation bumps the owning
    program's version so the lowering cache and the lint memo (both
    keyed on Program._version) never serve stale results.  No-op writes
    (setdefault on a present key, re-setting an identical value) do NOT
    bump, keeping versions stable across idempotent rewriter passes."""

    __slots__ = ('_op',)

    def __init__(self, data, op):
        super(_AttrDict, self).__init__(data)
        self._op = op

    def _bump(self):
        blk = getattr(self._op, 'block', None) if self._op is not None \
            else None
        if blk is not None:
            blk.program._bump()

    @staticmethod
    def _same(a, b):
        try:
            return bool(a == b)
        except Exception:       # ndarray-valued attrs and other oddballs
            return False

    def __setitem__(self, k, v):
        if k in self and self._same(dict.__getitem__(self, k), v):
            return
        dict.__setitem__(self, k, v)
        self._bump()

    def __delitem__(self, k):
        dict.__delitem__(self, k)
        self._bump()

    def setdefault(self, k, default=None):
        if k in self:
            return dict.__getitem__(self, k)
        self[k] = default
        return default

    def update(self, *a, **kw):
        for k, v in dict(*a, **kw).items():
            self[k] = v

    def pop(self, k, *default):
        had = k in self
        out = dict.pop(self, k, *default)
        if had:
            self._bump()
        return out

    def popitem(self):
        out = dict.popitem(self)
        self._bump()
        return out

    def clear(self):
        if self:
            dict.clear(self)
            self._bump()

    # deepcopy / pickle must NOT drag the op (and through it the whole
    # program) along — clone() deep-copies attrs and re-wraps on assign
    def __deepcopy__(self, memo):
        return {copy.deepcopy(k, memo): copy.deepcopy(v, memo)
                for k, v in self.items()}

    def __reduce__(self):
        return (dict, (dict(self),))


class Operator(object):
    """One node in a Block: op type + named input/output slots + attrs.

    Parity: reference framework.py Operator / OpDesc.  Unlike the reference,
    there is no per-op kernel: `type` keys into core/registry.py for a JAX
    impl used both for build-time shape inference and whole-block lowering.
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.source_loc = _capture_source_loc()
        self.attrs = dict(attrs or {})
        self.attrs.setdefault('op_role', _current_role[-1])
        if _recompute_stack:
            self.attrs.setdefault('recompute_id', _recompute_stack[-1])
        self.inputs = {}        # slot -> list[str]
        self.outputs = {}       # slot -> list[str]
        self.input_is_list = {}
        self.output_is_list = {}
        for slot, vs in (inputs or {}).items():
            if vs is None:
                continue
            self.input_is_list[slot] = isinstance(vs, (list, tuple))
            vs = vs if isinstance(vs, (list, tuple)) else [vs]
            self.inputs[slot] = [v.name if isinstance(v, Variable) else v
                                 for v in vs]
        for slot, vs in (outputs or {}).items():
            if vs is None:
                continue
            self.output_is_list[slot] = isinstance(vs, (list, tuple))
            vs = vs if isinstance(vs, (list, tuple)) else [vs]
            self.outputs[slot] = [v.name if isinstance(v, Variable) else v
                                  for v in vs]

    @property
    def attrs(self):
        return self._attrs

    @attrs.setter
    def attrs(self, d):
        if isinstance(d, _AttrDict) and d._op is self:
            self._attrs = d
        else:
            self._attrs = _AttrDict(dict(d or {}), self)
        blk = getattr(self, 'block', None)
        if blk is not None:
            blk.program._bump()

    def input_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    def output_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def attr(self, name):
        return self.attrs.get(name)

    def _set_attr(self, name, val):
        self.attrs[name] = val
        self.block.program._bump()

    set_attr = _set_attr

    def has_attr(self, name):
        return name in self.attrs

    def to_string(self, *a, **k):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        hidden = {'op_role'}
        ats = {k: v for k, v in self.attrs.items() if k not in hidden}
        return "{%s} = %s(%s) %s" % (outs, self.type, ins, ats)

    __repr__ = __str__ = lambda self: self.to_string()


# ---------------------------------------------------------------- Block

class Block(object):
    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent(self):
        return (self.program.blocks[self.parent_idx]
                if self.parent_idx >= 0 else None)

    # ------------- vars -------------
    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("var %s not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent
        return None

    def create_var(self, name=None, **kwargs):
        if name is None:
            name = unique_name.generate('_generated_var')
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name=name, **kwargs)
        self.vars[name] = v
        self.program._bump()
        return v

    def create_parameter(self, name=None, shape=None, dtype='float32', **kw):
        if name is None:
            name = unique_name.generate('_param')
        if _imperative[0] is not None:
            # eager mode: a same-named initialized parameter is reused, so a
            # Layer's repeated forward calls share weights across iterations
            existing = self.program.blocks[0].vars.get(name)
            if isinstance(existing, Parameter) and \
                    existing._ivalue is not None:
                return existing
        # parameters always live in the global (root) block, like the ref
        # (and their .block must BE the root block — optimizer passes
        # append update ops to param.block, which must never be a
        # control-flow sub-block)
        root = self.program.blocks[0]
        p = Parameter(root, shape=shape, dtype=dtype, name=name, **kw)
        root.vars[name] = p
        self.program._bump()
        return p

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def iter_parameters(self):
        return iter(self.all_parameters())

    # ------------- ops -------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        self.program._bump()
        for n in op.output_names():
            ov = self._find_var_recursive(n)
            if ov is not None:
                ov.op = op
        if _imperative[0] is not None:
            from ..imperative import base as _imp_base
            _imp_base.eager_run_op(op)
        elif infer_shape and registry.has_op(type):
            self._infer_shapes(op)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None,
                   infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump()
        if infer_shape and registry.has_op(type):
            self._infer_shapes(op)
        return op

    def _infer_shapes(self, op):
        """Dual-batch abstract eval: the op's JAX impl evaluated abstractly
        (core/infer_memo.py: once a signature in a process) with batch
        placeholder 7 and again with 11; output dims that differ between
        the two runs are batch dims (-1)."""
        import jax

        probes = []
        for B in infer_memo.PROBE_BATCHES:
            ins = {}
            for slot, names in op.inputs.items():
                structs = []
                for n in names:
                    v = self._find_var_recursive(n)
                    if v is None or v.shape is None:
                        return  # cannot infer (shapeless input): as-is
                    shape = tuple(B if d in (-1, None) else int(d)
                                  for d in v.shape)
                    structs.append(
                        jax.ShapeDtypeStruct(shape, v.np_dtype))
                ins[slot] = structs if op.input_is_list[slot] else structs[0]
            probes.append(ins)
        try:
            results = infer_memo.abstract_eval(op, probes)
        except Exception as e:
            raise RuntimeError(
                "shape inference failed for op %s: %s\n%s" %
                (op.type, e, op.to_string()))
        r1, r2 = results
        for slot, names in op.outputs.items():
            o1 = r1.get(slot) if isinstance(r1, dict) else None
            o2 = r2.get(slot) if isinstance(r2, dict) else None
            if o1 is None:
                continue
            l1 = o1 if isinstance(o1, (list, tuple)) else [o1]
            l2 = o2 if isinstance(o2, (list, tuple)) else [o2]
            for n, s1, s2 in zip(names, l1, l2):
                v = self._find_var_recursive(n)
                if v is None:
                    continue
                shape = tuple(int(a) if a == b else -1
                              for a, b in zip(s1.shape, s2.shape))
                v.shape = shape
                v.dtype = s1.dtype

    def to_string(self, throw_on_error=False, with_details=False):
        lines = ["block %d:" % self.idx]
        for v in self.vars.values():
            lines.append("  " + v.to_string())
        for op in self.ops:
            lines.append("  " + op.to_string())
        return "\n".join(lines)

    __repr__ = __str__ = lambda self: self.to_string()


# ---------------------------------------------------------------- Program

class Program(object):
    """An ordered collection of Blocks — the full training/inference graph.

    Parity: reference framework.py Program / ProgramDesc.  `_version` is a
    mutation counter used by the Executor's lowering cache (the reference
    recompiles its SSA graph on desc change; we re-trace/re-jit)."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        self._seed_counter = 0
        self._is_test = False
        # sharding annotations attached by parallel/transpiler.py
        self._sharding = {}
        # declared device mesh (tuple of (axis_name, size) pairs), HBM
        # budget in bytes, and serving KV-pool plan (CacheConfig kwargs)
        # — inputs to the sharding/memplan lint passes (analysis/passes)
        self._mesh_axes = None
        self._device_limit_bytes = None
        self._kv_plan = None
        # bf16 auto-mixed-precision for MXU ops (set_amp / contrib amp)
        self._amp = False

    def _bump(self):
        self._version += 1

    def set_amp(self, flag=True):
        """Enable bf16 auto-mixed-precision: matmul-class ops run with
        bfloat16 inputs (MXU native), everything else stays float32.  The
        lowered executable re-jits on change."""
        self._amp = bool(flag)
        self._bump()

    def set_sharding(self, name, spec):
        """Attach a PartitionSpec to var `name`; bumps the version so the
        executor's lowering cache re-jits with the new in_shardings.
        When the var exists in the IR the spec also becomes a
        first-class `Variable.sharding` annotation (canonical tuple
        form, serialized by io.py); unknown names keep the legacy
        side-table-only behavior."""
        for b in self.blocks:
            v = b.vars.get(name)
            if v is not None:
                v.sharding = spec  # setter syncs self._sharding + bumps
                return
        self._sharding[name] = spec
        self._bump()

    def set_mesh_axes(self, axes):
        """Declare the device mesh the sharding specs refer to.  Accepts
        a name->size dict, a sequence of (name, size) pairs, a jax Mesh
        (axis_names/shape), or None to clear.  The D019 lint checks spec
        axes against this declaration."""
        if axes is None:
            self._mesh_axes = None
        elif hasattr(axes, 'axis_names'):  # jax.sharding.Mesh
            self._mesh_axes = tuple((str(a), int(axes.shape[a]))
                                    for a in axes.axis_names)
        elif isinstance(axes, dict):
            self._mesh_axes = tuple((str(k), int(v))
                                    for k, v in axes.items())
        else:
            self._mesh_axes = tuple((str(k), int(v)) for k, v in axes)
        self._bump()

    def mesh_axes(self):
        """Declared mesh as a name->size dict, or None."""
        return dict(self._mesh_axes) if self._mesh_axes is not None else None

    def set_device_limit(self, limit_bytes):
        """Declare the per-device HBM budget the memplan lint (D020)
        checks against; None clears it (the pass then queries the
        runtime's memory_stats when available)."""
        self._device_limit_bytes = (int(limit_bytes)
                                    if limit_bytes is not None else None)
        self._bump()

    def set_kv_plan(self, **cache_config_kwargs):
        """Declare the serving KV-cache pool this program runs against
        (serving.generation.CacheConfig kwargs); the memplan lint folds
        its pool bytes into the per-device footprint.  No kwargs clears
        the plan."""
        self._kv_plan = dict(cache_config_kwargs) or None
        self._bump()

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def block(self, idx):
        return self.blocks[idx]

    def _create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx
        if self.current_block_idx < 0:
            self.current_block_idx = 0

    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    def all_parameters(self):
        return self.global_block().all_parameters()

    @property
    def num_blocks(self):
        return len(self.blocks)

    def clone(self, for_test=False):
        """Deep-copy the program.  for_test=True keeps only forward ops,
        flips is_test attrs on (dropout/batch_norm/...) ops, like the ref."""
        p = Program()
        p.random_seed = self.random_seed
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            for name, v in b.vars.items():
                if isinstance(v, Parameter):
                    nv = Parameter(nb, shape=v.shape, dtype=v.dtype, name=name,
                                   trainable=v.trainable,
                                   optimize_attr=v.optimize_attr,
                                   regularizer=v.regularizer,
                                   gradient_clip_attr=v.gradient_clip_attr)
                else:
                    nv = Variable(nb, name=name, shape=v.shape, dtype=v.dtype,
                                  lod_level=v.lod_level,
                                  persistable=v.persistable,
                                  stop_gradient=v.stop_gradient,
                                  is_data=v.is_data, type=v.type)
                # side-channel markers the lowering reads via getattr:
                # tensor-array vars (control_flow_exec) and ragged-length
                # companions (sequence layers)
                if getattr(v, 'is_tensor_array', False):
                    nv.is_tensor_array = True
                if getattr(v, 'lod_length_name', None):
                    nv.lod_length_name = v.lod_length_name
                if v._sharding_spec is not None:
                    nv._sharding_spec = v._sharding_spec
                nb.vars[name] = nv
            for op in b.ops:
                role = op.attrs.get('op_role', OpRole.Forward)
                if for_test and role in (OpRole.Backward, OpRole.Optimize,
                                         OpRole.LRSched):
                    continue
                nattrs = copy.deepcopy(op.attrs)
                if for_test and 'is_test' in nattrs:
                    nattrs['is_test'] = True
                nop = Operator(nb, op.type)
                nop.attrs = nattrs
                nop.source_loc = op.source_loc
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                nop.input_is_list = dict(op.input_is_list)
                nop.output_is_list = dict(op.output_is_list)
                nb.ops.append(nop)
            p.blocks.append(nb)
        p._sharding = dict(self._sharding)
        p._mesh_axes = self._mesh_axes
        p._device_limit_bytes = self._device_limit_bytes
        p._kv_plan = dict(self._kv_plan) if self._kv_plan else None
        if for_test:
            p._is_test = True
        p._bump()
        return p

    def _prune(self, feeds, fetches):
        """Return a clone keeping only ops needed to compute `fetches` from
        `feeds` (reference Program._prune_with_input, used by
        save_inference_model)."""
        feed_names = set(v.name if isinstance(v, Variable) else v
                        for v in feeds)
        fetch_names = set(v.name if isinstance(v, Variable) else v
                          for v in fetches)
        p = self.clone(for_test=True)
        b = p.global_block()
        needed = set(fetch_names)
        kept = []
        for op in reversed(b.ops):
            if set(op.output_names()) & needed:
                kept.append(op)
                for n in op.input_names():
                    if n not in feed_names:
                        needed.add(n)
        b.ops = list(reversed(kept))
        used = set(feed_names) | set(fetch_names)
        for op in b.ops:
            used.update(op.input_names())
            used.update(op.output_names())
        b.vars = {n: v for n, v in b.vars.items() if n in used}
        p._bump()
        return p

    def lint(self, feed_names=(), fetch_list=(), bucketer=None,
             passes=None, optimize=False):
        """Static analysis without compiling: run the paddle_tpu.analysis
        passes (def-use, shape/dtype abstract interpretation, dead ops,
        donation conflicts, retrace hazards, numerical hazards) and
        return a LintResult.  Never raises — strict enforcement is the
        executor's PT_LINT policy (docs/analysis.md).

        fetch_list anchors the dead-op pass; bucketer (a
        data_feeder.FeedBucketer) tells the retrace pass which dynamic
        feed dims are already padded onto stable bucket signatures.

        optimize=True first runs the PT_OPT rewriter pipeline
        (core/passes, honoring PT_OPT_SKIP) and lints the OPTIMIZED
        program — what the executor actually traces under PT_OPT=1.
        Diagnostics still point at model `source_loc` (folded/fused ops
        inherit their originals').  Default False so findings the
        rewriter would fix (dead ops, 64-bit attrs) stay visible when
        linting the program as written.
        """
        from ..analysis import lint_program
        fetch_names = []
        for f in (fetch_list or ()):
            fetch_names.append(f.name if isinstance(f, Variable) else f)
        target = self
        if optimize:
            from .passes import optimize_program
            target, _ = optimize_program(self, tuple(fetch_names))
        return lint_program(target, feed_names=tuple(feed_names),
                            fetch_names=tuple(fetch_names),
                            bucketer=bucketer, passes=passes)

    def to_string(self, throw_on_error=False, with_details=False):
        return "\n".join(b.to_string() for b in self.blocks)

    __repr__ = __str__ = lambda self: self.to_string()


# ------------------------------------------------- default program stack

_main_program = Program()
_startup_program = Program()


def default_main_program():
    return _main_program


def default_startup_program():
    return _startup_program


def switch_main_program(program):
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program):
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old


_guard_depth = [0]


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    # the OUTERMOST guard is the phase `program.build`: layers,
    # append_backward and minimize run inside it; a nested guard adds
    # nothing to it
    outermost = not _guard_depth[0]
    _guard_depth[0] += 1
    old_main = switch_main_program(main_program)
    old_start = None
    if startup_program is not None:
        old_start = switch_startup_program(startup_program)
    with (_obs.span('program.build', cat='build', counter='program.build_s')
          if outermost else contextlib.nullcontext()) as build:
        memo0 = infer_memo.counts()
        try:
            yield
        finally:
            _guard_depth[0] -= 1
            switch_main_program(old_main)
            if old_start is not None:
                switch_startup_program(old_start)
            if outermost and _obs.enabled():
                block = main_program.global_block()
                build.args.update(infer_memo.span_args(memo0),
                                  ops=len(block.ops), vars=len(block.vars))
