"""The twelve readers of the program's own counters (PR 24), each on a
synthetic ctx: the arithmetic its docstring states, and no reading (None)
where the denominator did not move, which is also what a program without
the counters gives."""
import pytest

import run

SERVING = {
    'generation.queue_wait_s': 1.2, 'generation.admitted': 12.0,
    'generation.prefill_phase_s': 6.0, 'generation.first_tokens': 10.0,
    'generation.rounds_to_first_token': 35.0,
    'generation.round_s': 40.0, 'generation.idle_wait_s': 10.0,
    'generation.prefill_s': 5.0, 'generation.prefill_fetch_s': 4.5,
    'generation.window_s': 33.0, 'generation.window_fetch_s': 32.0,
    'generation.prefill_chunks': 50.0, 'generation.decode_windows': 200.0,
    'generation.prefill_tokens': 3000.0,
    'generation.prefill_pad_tokens': 1000.0,
    'generation.decode_slot_steps': 6400.0,
    'generation.decode_live_slot_steps': 800.0,
    'generation.kv_tokens_live': 5.0e5, 'generation.kv_rows_read': 1.0e7,
}
TRAINING = {
    'executor.run_s': 0.9, 'executor.host_blocked_s': 0.1,
    'executor.steps': 400.0,
    'prefetch.starvation_s': 0.3, 'prefetch.upload_wait_s': 0.2,
}

# metric, the counters it reads, its value on them, its denominator
CASES = [
    ('scheduler.queue_wait_ms', SERVING, 100.0, ['generation.admitted']),
    ('scheduler.prefill_phase_ms', SERVING, 600.0,
     ['generation.first_tokens']),
    ('scheduler.rounds_to_first_token', SERVING, 3.5,
     ['generation.first_tokens']),
    ('scheduler.host_gap_share', SERVING, 100.0 * 2.0 / 50.0,
     ['generation.round_s', 'generation.idle_wait_s']),
    ('scheduler.idle_wait_share', SERVING, 20.0,
     ['generation.round_s', 'generation.idle_wait_s']),
    ('scheduler.live_slot_share', SERVING, 12.5,
     ['generation.decode_slot_steps']),
    ('prefill.host_ms_per_chunk', SERVING, 10.0,
     ['generation.prefill_chunks']),
    ('prefill.useful_token_share', SERVING, 75.0,
     ['generation.prefill_tokens', 'generation.prefill_pad_tokens']),
    ('decode.host_ms_per_window', SERVING, 5.0,
     ['generation.decode_windows']),
    ('decode.kv_read_useful_share', SERVING, 5.0,
     ['generation.kv_rows_read']),
    ('executor.inside_host_ms_per_step', TRAINING, 2.0, ['executor.steps']),
    ('input.starved_share', TRAINING, 1.0, None),
]


def _ctx(counters, **extra):
    return dict({'counters': dict(counters), 'window_s': 50.0,
                 'traffic': {'slots': 32}}, **extra)


@pytest.mark.parametrize('name,counters,want,_den', CASES,
                         ids=[c[0] for c in CASES])
def test_reader_arithmetic(name, counters, want, _den):
    reader = run.load_module('metrics', name)
    assert reader.META['name'] == name
    assert reader.META['source'] == 'program_counter'
    assert reader.read(_ctx(counters)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize('name,counters,_want,den', CASES,
                         ids=[c[0] for c in CASES])
def test_reader_gives_none_on_a_zero_denominator(name, counters, _want, den):
    reader = run.load_module('metrics', name)
    if den is None:
        # the window's own length: a cell without the prefetcher reads 0,
        # a ctx without a window reads nothing
        assert reader.read(_ctx({})) == 0.0
        assert reader.read({'counters': {}, 'window_s': 0.0}) is None
        return
    zeroed = dict(counters, **{k: 0.0 for k in den})
    assert reader.read(_ctx(zeroed)) is None
    # the scheduler's older counts alone (launches without the runtime's
    # clock: the PR's parent) are no reading either, not a zero
    older = {k: v for k, v in counters.items()
             if k in ('generation.prefill_chunks',
                      'generation.decode_windows')}
    assert reader.read(_ctx(older)) is None
    # a program that lacks the counters altogether (the PR's parent)
    assert reader.read(_ctx({})) is None


def test_the_manifest_gives_the_twelve_to_every_cell_of_their_kind():
    """No entry lists cells: each follows the end-to-end metric it moves
    into every cell that reports it, a later PR's too."""
    manifest = run.load_json(run.ROOT, 'BENCHMARK.json')
    entries = {m['name']: m for m in manifest['per_layer']}
    moved = {m['name']: m for m in manifest['end_to_end']}
    for name, counters, _, _ in CASES:
        assert 'workloads' not in entries[name]
        want = 'tpot_p50_ms' if counters is SERVING else 'train_rate'
        assert entries[name]['moves'] == want
        for cell in moved[want]['workloads']:
            assert name in [m['name'] for m in
                            run.wanted_metrics(manifest, cell, 1)]
