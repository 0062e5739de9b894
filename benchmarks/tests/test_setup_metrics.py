"""The six readers of set-up from inside (PR 37), each on a hand-built ctx
of a training and of a serving run: the arithmetic its docstring states,
no reading (None) where that kind of cell, or the parent's program, has no
such counter, and META equal to the entry BENCHMARK.json gained."""
import pytest

import paddle_tpu.observability as obs

import run

NAMES = ('setup.before_program_s', 'setup.import_s', 'setup.build_s',
         'setup.prepare_s', 'setup.first_launches_s',
         'setup.accounted_share')
PROCESS = {'process.before_import_s': 9.0, 'process.import_s': 0.5}
# a warm training start: both executables come from the disk cache
TRAIN = {'program.build_s': 3.0, 'executor.prepare_s': 6.0,
         'executor.lint_s': 0.25, 'executor.lower_s': 1.0,
         'compile_cache.fingerprint_s': 0.75, 'compile_cache.load_s': 3.5,
         'executor.emit_s': 0.0, 'executor.trace_s': 0.0,
         'executor.backend_compile_s': 0.0, 'executor.run_s': 8.5,
         'executor.launches': 3.0}
# a cold serving start: both executables are compiled and stored
SERVE = {'generation.init_s': 0.25, 'generation.warmup_s': 40.0,
         'compile_cache.fingerprint_s': 0.5, 'compile_cache.load_s': 0.0,
         'generation.compile_s': 38.0, 'compile_cache.store_s': 1.25,
         'generation.compiles': 2.0, 'generation.round_s': 0.75}
TRAIN_CTX = {'setup_counters': TRAIN, 'setup_s': 25.0}
SERVE_CTX = {'setup_counters': SERVE, 'setup_s': 64.0, 'warmup_s': 40.1}

# metric -> (training value, serving value)
WANT = {
    'setup.before_program_s': (9.0, 9.0),
    'setup.import_s': (0.5, 0.5),
    'setup.build_s': (3.0, 0.25),
    'setup.prepare_s': (6.0 - 3.5, 40.0 - 38.0 - 1.25),
    'setup.first_launches_s': (8.5 - 6.0, 0.75),
    'setup.accounted_share': (100.0 * (9.0 + 0.5 + 3.0 + 6.0 + 2.5) / 25.0,
                              100.0 * (9.0 + 0.5 + 0.25 + 40.0 + 0.75)
                              / 64.0),
}


@pytest.fixture
def process(monkeypatch):
    """The two values the readers take from the live registry."""
    live = dict(PROCESS)
    monkeypatch.setattr(obs, 'counters', lambda: dict(live))
    return live


@pytest.mark.parametrize('name', NAMES)
@pytest.mark.parametrize('kind', ['training', 'serving'])
def test_reader_arithmetic(process, name, kind):
    reader = run.load_module('metrics', name)
    ctx = TRAIN_CTX if kind == 'training' else SERVE_CTX
    want = WANT[name][kind == 'serving']
    assert reader.read(ctx) == pytest.approx(want, rel=1e-12)
    if name == 'setup.accounted_share':
        assert 0.0 < reader.read(ctx) <= 100.0


@pytest.mark.parametrize('name', NAMES)
def test_meta_is_the_appended_entry(name):
    reader = run.load_module('metrics', name)
    manifest = run.load_json(run.ROOT, 'BENCHMARK.json')
    entry, = [m for m in manifest['per_layer'] if m['name'] == name]
    assert reader.META == entry
    assert entry['source'] == 'program_counter'
    assert entry['moves'] == 'setup_s' and 'workloads' not in entry
    # appended: the six are the list's last entries, in this order
    assert [m['name'] for m in manifest['per_layer'][-6:]] == list(NAMES)


# what the PARENT's program gives: no process.*, no umbrella counters; the
# serving rounds of set-up are counted there too (generation.round_s, PR 24)
PARENT_TRAIN = {'setup_counters': {'executor.run_s': 8.5,
                                   'compile_cache.load_s': 3.5},
                'setup_s': 25.0}
PARENT_SERVE = {'setup_counters': {'generation.round_s': 0.75,
                                   'generation.compiles': 0.0},
                'setup_s': 16.0, 'warmup_s': 0.9}


@pytest.mark.parametrize('name', NAMES)
@pytest.mark.parametrize('ctx', [PARENT_TRAIN, PARENT_SERVE],
                         ids=['training', 'serving'])
def test_reader_gives_none_without_the_counters(process, name, ctx):
    process.clear()
    reader = run.load_module('metrics', name)
    got = reader.read(ctx)
    if name == 'setup.first_launches_s' and 'warmup_s' in ctx:
        assert got == 0.75
    else:
        assert got is None


def test_a_kind_never_reads_the_other_kinds_counters(process):
    """A training ctx that also holds serving counters (and the reverse)
    reads its own."""
    both = dict(TRAIN, **SERVE)
    build = run.load_module('metrics', 'setup.build_s')
    assert build.read({'setup_counters': both, 'setup_s': 25.0}) == 3.0
    assert build.read({'setup_counters': both, 'setup_s': 25.0,
                       'warmup_s': 1.0}) == 0.25
    # serving has no program.build_s, training no generation.init_s
    assert build.read({'setup_counters': SERVE, 'setup_s': 25.0}) is None
    assert build.read({'setup_counters': TRAIN, 'setup_s': 25.0,
                       'warmup_s': 1.0}) is None


def test_accounted_share_without_a_readable_process_start(process):
    """Off Linux the gauge is absent: the share counts it as 0."""
    del process['process.before_import_s']
    share = run.load_module('metrics', 'setup.accounted_share')
    assert share.read(TRAIN_CTX) == pytest.approx(
        100.0 * (0.5 + 3.0 + 6.0 + 2.5) / 25.0)
    assert share.read(dict(TRAIN_CTX, setup_s=0.0)) is None


def test_prepare_never_reads_below_zero(process):
    prepare = run.load_module('metrics', 'setup.prepare_s')
    c = dict(TRAIN, **{'compile_cache.load_s': 7.0})
    assert prepare.read({'setup_counters': c, 'setup_s': 25.0}) == 0.0
