"""The training comparison's control at a tiny size on the CPU: the plain
reference with 8-bit weights in the program's place must come out NOT
correct, while the program itself does (tests/control.py; the readings at
the cells' own sizes on the chip are in PERF.md)."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH

import control


def test_eight_bit_rounds_matrices_and_keeps_vectors():
    import jax.numpy as jnp
    params = {'w': jnp.linspace(-1.0, 1.0, 64).reshape(8, 8) * 0.37,
              'scale': jnp.linspace(0.9, 1.1, 8)}
    out = control.eight_bit(params)
    assert np.array_equal(np.asarray(out['scale']),
                          np.asarray(params['scale']))
    w, r = np.asarray(params['w']), np.asarray(out['w'])
    assert r.dtype == w.dtype and not np.array_equal(r, w)
    # three mantissa bits: within 2**-4 of the value, relatively
    big = np.abs(w) > 0.02
    assert np.max(np.abs(r - w)[big] / np.abs(w)[big]) <= 2.0 ** -4


def test_the_seeds_differ_and_reach_past_32_signed_bits():
    seeds = control.seeds_from(2 ** 31 + 77, 12)
    assert len(set(seeds)) == 12
    assert max(seeds) >= 2 ** 31 and min(seeds) < 2 ** 29
    assert all(0 <= s <= 2 ** 31 + 2 ** 20 for s in seeds)


@pytest.fixture(scope='module', params=[False, True], ids=['f32', 'amp'])
def tiny(request, tmp_path_factory):
    """The readings of a tiny transformer cell over three seeds, the
    control on each: the real files at other sizes, under a manifest of
    one cell; in f32 and under AMP, as the real cells run."""
    import run
    top = str(tmp_path_factory.mktemp('control'))
    real = run.load_json
    config = dict(real(BENCH, 'configs', 'tbase.json'), name='tiny',
                  reference='tbase', n_layer=2, d_model=64, n_head=2,
                  d_inner=128, vocab=512, amp=request.param)
    traffic = dict(real(BENCH, 'traffic', 'wmt_b96_t256.json'), batch=8,
                   seq=32, steps_per_launch=2, pool_batches=4)
    with open(os.path.join(top, 'BENCHMARK.json'), 'w') as f:
        json.dump({'workloads': [{'name': 'tiny.train', 'config': 'tiny',
                                  'traffic': 'tiny_mix', 'chips': 1}],
                   'end_to_end': [], 'per_layer': []}, f)

    def load_json(*parts):
        if parts[-1] == 'tiny.json':
            return dict(config)
        if parts[-1] == 'tiny_mix.json':
            return dict(traffic)
        return real(*parts)
    run.load_json = load_json
    try:
        return control.readings('tiny.train', control.seeds_from(5, 3), 3,
                                allow_cpu=True, root=top)
    finally:
        run.load_json = real


def test_the_program_passes_and_the_control_does_not(tiny):
    rows, limits = tiny
    fields = control.summary(rows, limits)
    assert fields['sound_pass'] is True
    assert fields['control_fails'] is True
    assert len(rows) == 3 and all(r['control'] for r in rows)


@pytest.mark.parametrize('number', ['per_item', 'grads'])
def test_each_vector_comparison_tells_the_control_apart(tiny, number):
    """Each of the two numbers the comparison is decided by holds the
    control off on its own, with the room the contract asks for (three
    times); the scalar loss does not, which is why they are compared."""
    rows, limits = tiny
    got = control.summary(rows, limits)[number]
    assert got['sound_max'] <= got['limit'] < got['control_min']
    assert got['ratio'] >= 3.0


def test_the_mean_loss_separates_worse_than_either_vector(tiny):
    """Why the vectors are compared: a mean over every token averages the
    rounding out, the control's as well as the program's."""
    rows, limits = tiny
    got = control.summary(rows, limits)
    assert got['loss']['ratio'] < min(got['per_item']['ratio'],
                                      got['grads']['ratio'])
