"""The reduction from a trace to busy time, gaps and op times: on
intervals made by hand, and on a small trace recorded on the v5e
(benchmarks/tests/data/small.xplane.pb: three launches of a jitted
3-step scan of matmul+tanh, with bench: annotations, PR 23)."""
import os

import pytest

from lib import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')


class _Ev(object):
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line(object):
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane(object):
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Trace(object):
    def __init__(self, planes):
        self.planes = planes


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert xplane.union_length(iv) == 30
    assert xplane.union_length(iv, lo=8, hi=32) == 12 + 2
    assert xplane.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert xplane.gaps([], 3, 9) == [(3, 9)]


def test_an_enclosing_op_gives_up_what_it_encloses():
    events = [(0, 100, 'while'), (10, 30, 'fusion'), (40, 90, 'fusion'),
              (50, 60, 'copy'), (120, 130, 'fusion')]
    own = xplane.self_times(events)
    assert own == {'while': 100 - 20 - 50, 'fusion': 20 + (50 - 10) + 10,
                   'copy': 10}
    assert sum(own.values()) == xplane.union_length(
        [(s, e) for s, e, _ in events])


def _fake(two_chips=False):
    cat = []
    ops = [_Ev('%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), '
               'kind=kLoop, calls=%f', 1000, 400),
           _Ev('%fusion.2 = f32[8]{0:T(128)} fusion(f32[8]{0} %q), '
               'kind=kLoop, calls=%g', 1500, 300),
           _Ev('%all-reduce-start.7 = (f32[4]{0:T(128)S(1)}, f32[4]{0}) '
               'all-reduce-start(f32[4]{0} %x), replica_groups={}', 2000, 500),
           _Ev('%layer_norm_rows.3 = bf16[8,4]{1,0} custom-call(bf16[8,4] '
               '%y), custom_call_target="tpu_custom_call"', 3000, 1000)]
    mods = [_Ev('jit_step(123)', 1000, 1500), _Ev('jit_step(123)', 3000, 1000)]
    host = [_Ev('bench:traced_window', 0, 5000), _Ev('bench:launch', 100, 850),
            _Ev('bench:fetch', 2450, 600), _Ev('other', 0, 5000)]
    planes = [_Plane('/device:TPU:0', [_Line('XLA Ops', ops),
                                       _Line('XLA Modules', mods),
                                       _Line('Steps', [])]),
              _Plane('/host:CPU', [_Line('main', host)])]
    if two_chips:
        planes.insert(1, _Plane('/device:TPU:1',
                                [_Line('XLA Ops', [_Ev('%fusion.1', 1000, 3000,
                                                       cat)])]))
    return _Trace(planes)


def test_summarize_by_hand():
    s = xplane.summarize(_fake())
    assert s['chips'] == 1
    assert s['window_s'] == pytest.approx(3000e-9)   # first start, last end
    assert s['busy_s'] == pytest.approx(2200e-9)
    ops = dict(map(tuple, s['device_ops']))
    assert ops['fusion:Loop f32[8]'] == pytest.approx(700e-9)  # numbers dropped
    assert 'custom-call layer_norm_rows bf16[8,4]' in ops
    assert 'all-reduce-start (f32[4], f32[4])' in ops
    assert s['collective_s'] == pytest.approx(500e-9)
    assert s['custom_call_s'] == pytest.approx(1000e-9)
    assert s['modules'] == {'jit_step': {'seconds': pytest.approx(2500e-9),
                                         'count': 2.0}}
    gaps = [(n, round(t * 1e9)) for n, t in s['idle_gaps']]
    # longest first: 2500-3000 (the host was in fetch), 1800-2000, 1400-1500
    assert gaps == [('fetch', 500), ('unattributed', 200),
                    ('unattributed', 100)]
    assert sum(t for _, t in gaps) == 3000 - 2200


def test_every_operation_is_kept_with_its_count():
    s = xplane.summarize(_fake())
    assert [k for k, _ in s['device_ops']] == list(s['ops'])[:10]
    assert s['ops']['fusion:Loop f32[8]'] == {
        'seconds': pytest.approx(700e-9), 'count': 2.0}
    assert sum(op['seconds'] for op in s['ops'].values()) \
        == pytest.approx(s['busy_s'])
    assert xplane.op_seconds(s, 'custom-call layer_norm_rows') \
        == pytest.approx(1000e-9)
    assert xplane.op_seconds(s, 'custom-call paged_attention') is None
    assert xplane.op_seconds(None, 'fusion') is None


def test_several_chips_average():
    s = xplane.summarize(_fake(two_chips=True))
    assert s['chips'] == 2
    assert s['busy_s'] == pytest.approx((2200 + 3000) / 2 * 1e-9)


def test_labels_from_hlo_text():
    label, opcode = xplane.parse_op(
        '%copy-done.2 = bf16[256,256]{1,0:T(8,128)(2,1)S(1)} copy-done('
        '(bf16[256,256]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) %copy-start.2)')
    assert (label, opcode) == ('copy-done bf16[256,256]', 'copy-done')
    label, opcode = xplane.parse_op(
        '%while = (s32[]{:T(128)}, bf16[256,256]{1,0:T(8,128)(2,1)S(1)}) '
        'while((s32[]{:T(128)}, bf16[256,256]{1,0}) %tuple), condition=%c')
    assert (label, opcode) == ('while (s32[], bf16[256,256])', 'while')
    assert xplane.parse_op('my_kernel.3') == ('my_kernel', None)


def test_no_device_plane_gives_nothing():
    assert xplane.summarize(_Trace([_Plane('/host:CPU', [])])) is None


@pytest.fixture(scope='module')
def recorded():
    path = os.path.join(DATA, 'small.xplane.pb')
    if not os.path.exists(path):
        pytest.skip('no recorded trace beside the tests')
    return xplane.load(path)


def test_recorded_trace_has_the_layout_the_reduction_assumes(recorded):
    names = [p.name for p in recorded.planes]
    assert '/device:TPU:0' in names and '/host:CPU' in names
    tpu = next(p for p in recorded.planes if p.name == '/device:TPU:0')
    lines = {ln.name for ln in tpu.lines}
    assert {'XLA Ops', 'XLA Modules'} <= lines
    spans = {s[2] for s in xplane.host_spans(recorded)}
    assert {'bench:traced_window', 'bench:launch', 'bench:fetch',
            'bench:feed'} <= spans


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    s = xplane.summarize(recorded)
    assert s['chips'] == 1
    assert 0 < s['busy_s'] < s['window_s'] < 1.0
    # three launches of one jitted module, about 2.34 microseconds each
    (name, mod), = [(k, v) for k, v in s['modules'].items() if 'step' in k]
    assert mod['count'] == 3
    assert mod['seconds'] == pytest.approx(3 * 2.34e-6, rel=0.01)
    assert any(k.startswith('fusion:') and 'bf16[256,256]' in k
               for k, _ in s['device_ops'])
    assert any(k.startswith('while') for k, _ in s['device_ops'])
    # a module's span holds its operations and the slack between them
    assert s['busy_s'] <= mod['seconds'] <= s['busy_s'] * 1.25
    own = sum(t for _, t in s['device_ops'])
    assert own == pytest.approx(s['busy_s'], rel=0.02)
    idle = sum(t for _, t in s['idle_gaps'])
    assert idle <= s['window_s'] - s['busy_s'] + 1e-9
    assert {n for n, _ in s['idle_gaps']} <= {'launch', 'fetch', 'feed',
                                              'unattributed'}


def test_recorded_trace_keeps_every_operation(recorded):
    """`ops` holds ALL the window's operations: it sums to the busy time
    (nothing overlaps on one TensorCore) and `device_ops` is its head."""
    s = xplane.summarize(recorded)
    assert sum(op['seconds'] for op in s['ops'].values()) \
        == pytest.approx(s['busy_s'], rel=1e-9)
    assert [[k, op['seconds']] for k, op in list(s['ops'].items())[:10]] \
        == s['device_ops']
    assert len(s['ops']) == 6
    assert s['ops']['fusion:Output convolution_tanh_fusion bf16[256,256]'][
        'count'] == 9                # three launches of a 3-step scan
