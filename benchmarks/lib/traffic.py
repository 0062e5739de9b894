"""One general generator per KIND of traffic; a traffic mix is a data file.

A traffic file names its generator and gives its parameters.  The seed
never resamples the work: it permutes a fixed multiset (of batches, or of
request lengths and arrival gaps), draws token ids, and (in the runner)
makes the weights.  Two seeds therefore offer the same work in another
order.
"""
import itertools
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    path = os.path.join(HERE, 'traffic', name + '.json')
    with open(path) as f:
        return json.load(f)


def rng_for(seed, stream):
    """Independent streams from one seed: seeds run past 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


# ------------------------------------------------------------- training

def translation_pairs(traffic, vocab, seed):
    """Endless per-step feeds for models/transformer.py: full-length
    (source, shifted target, label) rows, the label a copy of the source
    (the feed contract of `transformer.synthetic_batch`, vectorised).
    A pool of `pool_batches` distinct batches is drawn once and cycled in
    a seed-dependent order."""
    B, T = int(traffic['batch']), int(traffic['seq'])
    rng = rng_for(seed, 1)
    pool = []
    for _ in range(int(traffic['pool_batches'])):
        s = rng.integers(3, vocab, (B, T - 1), dtype=np.int64)
        eos = np.ones((B, 1), np.int64)
        bos = np.zeros((B, 1), np.int64)
        src = np.concatenate([s, eos], 1)[:, :, None]
        trg = np.concatenate([bos, s], 1)[:, :, None]
        pad = np.zeros((B, T), np.float32)
        pool.append({'src_word': src, 'trg_word': trg, 'lbl_word': src,
                     'src_pad': pad, 'trg_pad': pad})
    return _cycle(pool), B * T


def images(traffic, classes, seed):
    """Endless per-step feeds for models/resnet.py: uniform [0,1) float32
    images and uniform labels, from a pool drawn once."""
    B, side = int(traffic['batch']), int(traffic['side'])
    rng = rng_for(seed, 1)
    pool = []
    for _ in range(int(traffic['pool_batches'])):
        pool.append({
            'data': rng.random((B, 3, side, side), dtype=np.float32),
            'label': rng.integers(0, classes, (B, 1), dtype=np.int64)})
    return _cycle(pool), B


def _cycle(pool):
    def gen():
        i = 0
        while True:
            yield pool[i % len(pool)]
            i += 1
    return gen()


TRAIN_GENERATORS = {'translation_pairs': translation_pairs,
                    'images': images}


# -------------------------------------------------------------- serving

def lognormal_pairs(n, prompt, output, shared_prefix):
    """The fixed multiset of (prompt length, output length) pairs of a
    serving mix: the i-th of n evenly spaced quantiles of two clipped
    log-normals, so the multiset depends on the parameters alone.  Output
    quantiles are taken in a fixed stride-permuted order so that long
    prompts do not always pair with long outputs."""
    def quantiles(spec):
        med, sigma = float(spec['median']), float(spec['sigma'])
        lo, hi = int(spec['min']), int(spec['max'])
        out = []
        for i in range(n):
            z = _norm_ppf((i + 0.5) / n)
            out.append(int(min(hi, max(lo, round(med * math.exp(sigma * z))))))
        return out
    p, o = quantiles(prompt), quantiles(output)
    stride = next(s for s in range(n // 2 + 1, 2 * n) if math.gcd(s, n) == 1)
    o = [o[(i * stride) % n] for i in range(n)]
    return [(max(pl, shared_prefix + 1), ol) for pl, ol in zip(p, o)]


def _norm_ppf(p):
    """Inverse normal CDF: Acklam's rational approximation, relative error
    under 1.2e-9, far finer than lengths rounded to whole tokens need."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    if p < 0.02425:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / \
               ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    if p > 1 - 0.02425:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5]) * q / \
           (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1)


def exponential_gaps(n, rate):
    """The fixed multiset of n arrival gaps of a Poisson stream at `rate`
    a second: the (i + 1/2)/n quantiles of the exponential distribution,
    scaled so that they sum to exactly n / rate."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / rate / sum(raw)
    return [g * scale for g in raw]


def open_loop(traffic, vocab, seed, seconds, rate=None):
    """The requests of one open-loop window: a list of dicts with `due`
    (seconds from the window's start), `prompt` (int32 ids) and
    `max_new`, sorted by `due`, all due before `seconds`.

    Arrivals are a Poisson stream at `rate` requests a second (the
    traffic file's, unless a sweep passes its own) in this sense: the
    gaps are the fixed multiset `exponential_gaps(pairs, rate)` and the
    (prompt, output) lengths the fixed multiset `lognormal_pairs`; `--seed`
    permutes both, independently, and draws the token ids (and, in the
    runner, the weights).  One cycle of n requests lasts exactly n / rate
    seconds, so a traffic file whose `pairs` is rate x run_seconds offers
    every seed the same work once, in another order.  The order is part
    of what a server is asked: which long prompts arrive together decides
    its tails, so two seeds agree on the work and not on the latencies,
    and the spread over seeds is the spread a deployment sees (PERF.md,
    Findings of PR 23).  Every prompt starts with the same
    `shared_prefix` tokens.
    """
    n = int(traffic['pairs'])
    pairs = lognormal_pairs(n, traffic['prompt'], traffic['output'],
                            int(traffic['shared_prefix']))
    rate = float(traffic['rate_per_s'] if rate is None else rate)
    gaps = exponential_gaps(n, rate)
    order = rng_for(seed, 2).permutation(n)
    gap_order = rng_for(seed, 3).permutation(n)
    tok_rng = rng_for(seed, 4)
    prefix = tok_rng.integers(1, vocab, int(traffic['shared_prefix']),
                              dtype=np.int32)
    out, t = [], 0.0
    for i in itertools.count():
        # the first request is due at the window's start, and a gap
        # FOLLOWS its request: a cycle lasts exactly n / rate seconds
        if t >= seconds - 1e-9:           # sums of floats: a whole cycle
            return out                    # ends ON the boundary
        plen, olen = pairs[int(order[i % n])]
        body = tok_rng.integers(1, vocab, plen - prefix.size, dtype=np.int32)
        out.append({'due': t, 'prompt': np.concatenate([prefix, body]),
                    'max_new': int(olen)})
        t += gaps[int(gap_order[i % n])]
