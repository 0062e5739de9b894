"""The seed shuffles; it does not resample."""
import collections

import numpy as np

from lib import traffic


def _mix():
    return traffic.load('chat_steady')


def test_two_seeds_offer_the_same_work_in_another_order():
    """The seed permutes; it does not resample: every seed gets the
    file's multiset of lengths and of gaps, each in an order of its own,
    with token ids of its own."""
    mix = _mix()
    n = int(mix['pairs'])
    seconds = n / float(mix['rate_per_s'])            # one whole cycle

    def offered(seed):
        out = traffic.open_loop(mix, 32000, seed, seconds)
        assert len(out) == n
        dues = [r['due'] for r in out] + [seconds]
        assert dues[0] == 0.0
        gaps = [round(b - a, 6) for a, b in zip(dues, dues[1:])]
        return [(r['prompt'].size, r['max_new']) for r in out], gaps, out
    (a, ga, ra), (b, gb, rb) = offered(7), offered(2 ** 31 + 12)
    assert a != b and ga != gb                         # another order ...
    assert collections.Counter(a) == collections.Counter(b)   # ... of the
    assert collections.Counter(ga) == collections.Counter(gb)  # same work
    assert any(x['prompt'][0] != y['prompt'][0] for x, y in zip(ra, rb))
    pairs = traffic.lognormal_pairs(n, mix['prompt'], mix['output'],
                                    int(mix['shared_prefix']))
    assert collections.Counter(a) == collections.Counter(pairs)
    want = collections.Counter(round(g, 6) for g in traffic.exponential_gaps(
        n, float(mix['rate_per_s'])))
    assert collections.Counter(ga) == want
    # lengths and gaps are permuted independently of each other
    by_len = [g for _, g in sorted(zip(a, ga))]
    assert by_len != [g for _, g in sorted(zip(b, gb))]
    assert 'schedule_seed' not in mix      # no order is frozen in the file


def test_one_pass_of_the_pairs_fills_one_run():
    """pairs = rate x run_seconds, so a run at the manifest's length
    offers every seed the whole multiset once (the last gap's request may
    fall on the boundary)."""
    import json
    import os
    from conftest import ROOT
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        run_seconds = json.load(f)['run_seconds']
    mix = _mix()
    assert abs(mix['pairs'] / mix['rate_per_s'] - run_seconds) < 1e-6
    for seed in (1, 2, 2 ** 31 + 3):
        sent = traffic.open_loop(mix, 32000, seed, run_seconds)
        assert len(sent) == int(mix['pairs'])


def test_gaps_are_exponential_with_the_right_mean():
    gaps = traffic.exponential_gaps(1000, 2.0)
    assert abs(sum(gaps) - 500.0) < 1e-9
    gaps.sort()
    assert abs(gaps[500] - 0.5 * 0.6931) < 0.01      # median = ln 2 / rate
    assert gaps[-1] > 2.5                            # a heavy right tail


def test_same_seed_same_requests_and_shared_prefix():
    mix = _mix()
    a = traffic.open_loop(mix, 32000, 5, 20.0)
    b = traffic.open_loop(mix, 32000, 5, 20.0)
    assert len(a) == len(b) > 10
    k = int(mix['shared_prefix'])
    for x, y in zip(a, b):
        assert x['due'] == y['due'] and x['max_new'] == y['max_new']
        np.testing.assert_array_equal(x['prompt'], y['prompt'])
        np.testing.assert_array_equal(x['prompt'][:k], a[0]['prompt'][:k])
    dues = [r['due'] for r in a]
    assert dues == sorted(dues) and dues[-1] < 20.0


def test_no_context_passes_a_slots_share_of_the_pool():
    mix = _mix()
    pairs = traffic.lognormal_pairs(int(mix['pairs']), mix['prompt'],
                                    mix['output'], int(mix['shared_prefix']))
    page = int(mix['page_len'])
    assert max(p + o for p, o in pairs) <= int(mix['slot_tokens']) - page
    assert min(p for p, _ in pairs) > int(mix['shared_prefix'])
    assert int(mix['pages']) == int(mix['slots']) * int(mix['slot_tokens']) \
        // page + 1
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert abs(prompts[len(prompts) // 2] - mix['prompt']['median']) < 8
    assert abs(outputs[len(outputs) // 2] - mix['output']['median']) < 4


def test_the_rate_is_four_fifths_of_the_knee():
    mix = _mix()
    # ... to the nearest whole number of requests in a run
    assert abs(mix['rate_per_s'] / mix['knee_per_s'] - 0.8) < 0.02


def test_training_feeds_follow_the_models_feed_contract():
    gen, items = traffic.translation_pairs(
        {'batch': 3, 'seq': 8, 'pool_batches': 2}, 50, 9)
    feed = next(gen)
    assert items == 24
    assert feed['src_word'].shape == (3, 8, 1)
    assert (feed['trg_word'][:, 0, 0] == 0).all()          # begin marker
    assert (feed['src_word'][:, -1, 0] == 1).all()         # end marker
    np.testing.assert_array_equal(feed['trg_word'][:, 1:],
                                  feed['src_word'][:, :-1])
    np.testing.assert_array_equal(feed['lbl_word'], feed['src_word'])
    assert feed['trg_pad'].sum() == 0
    third = [next(gen), next(gen)][1]
    np.testing.assert_array_equal(third['src_word'], feed['src_word'])
