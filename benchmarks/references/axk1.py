"""Plain reference for `axk1`: the A.X-K1 block as ONE expert-parallel rank
holds it, a full causal forward pass over the whole context in jax.numpy:
float32, matmul precision `highest`, no cache, no pages, no chunks, no
absorption (keys and values are expanded for every head), no grouping (a
loop over the experts held, each over every token), independent of the
program's latent.py and experts.py.

The equations, every number from the published config (ISSUE 47, section 1);
all projections without bias, eps 1e-6:

    x0      = tok_emb[ids]
    layer:    h = RMSNorm(x); x = x + Attn(h); h = RMSNorm(x); x = x + FFN(h)
    Attn:     c_q = RMSNorm(h W_qa)  [T, 1536];  q = c_q W_qb -> 64 heads of
              [q_nope 128 ; q_rope 64];  [c_kv 512 ; k_r 64] = h W_kva;
              c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r), ONE key for all heads;
              [k_nope 128 ; v 128] per head = c_kv W_kvb;  q_r = RoPE(q_rope);
              score = (q_nope . k_nope + q_r . k_r) * s, causal, softmax;
              out = concat_heads(P v) W_o  [8192, 7168]
    RoPE:     interleaved pairs (x0,x1), (x2,x3), ... of the 64 rope columns
              (the repo's public order), YaRN: f_i = 10000^(-2i/64),
              inv_freq_i = f_i/32 * (1 - m_i) + f_i * m_i,
              m_i = 1 - clip((i - lo)/(hi - lo), 0, 1), lo and hi the
              correction range of beta_fast 32 and beta_slow 1 over 4096
              positions; mscale = mscale_all_dim = 1, so cos and sin are
              unscaled and s = 192^(-1/2) * (0.1 ln 32 + 1)^2
    FFN 0:    W_2 (silu(h W_1) * h W_3), width 18432
    FFN >= 1: g = sigmoid(h W_g) [T, 192];  E = top-8(g) (`select`);
              w_e = 2.5 g_e / sum_{e' in E} g_e';
              y = sum_{e in E, e HELD} w_e FFN_e(h) + FFN_shared(h), width 2048
    logits  = RMSNorm(x) W_head, over the rows of the vocabulary held

The share: of a layer's 192 routed experts this rank holds
`model['moe']['rank']`'s contiguous block of 192 / 16 = 12; routing and
the normalisation are over all 192 and all 8 picks, and what the absent
180 would have added is left out here as in the program: that partial
result goes on to the next layer.  tests/test_generation_latent_moe.py
adds the 16 shares up to the uncut layer at a tiny size.

ASSUMED (configs/axk1.json lists it): `topk_method: "none"` is read as
neither group-limited nor bias-corrected, plain top-8 over the 192 scores;
`n_group` 8 and `topk_group` 4 are inert.  The selection is ONE function,
`select`, so another reading changes one place.

Departures from the published model, each the program's too: RoPE rotates
interleaved pairs where transformers' DeepSeek-style code first reorders a
head's rope columns into halves (a fixed permutation of the columns of
W_qb and W_kva, which random weights cannot tell apart); the weights are
the runner's draw (every array normal at `initializer_range`, names ending
in `norm` ones).

The weights are the runtime's own bfloat16 arrays widened to float32 one
matrix (one expert, one block of the dense layer's columns) at a time, and
the scores are taken one head at a time: 64 heads x 6,200 x 6,200 scores
would not fit beside the runtime.

NEAR-TIES.  Routing is discontinuous: where the 8th and the 9th score of a
token lie closer than the program's bfloat16 products resolve, the program
may pick the other expert, and if exactly one of the two is held here (one
case in eight) that expert's whole contribution w_e FFN_e(h) is in one
result and not in the other: at the compared position one such flip moves
the logits by 9-15 per cent (benchmarks/tests/axk1_control.py read it on
the chip), where everything else a sound run differs by is about one per
cent.  The router itself is float32 in the program, as the source computes
it; what differs is its input, the residual stream after bfloat16 products,
and the program picks another set than this reference in about one (token,
layer) pair in nine, at margins (router logit of the 8th score less that of
the 9th) of at most 0.089 in 106 k pairs (PERF.md, Findings of PR 47).  At
earlier positions such flips are diluted by attention over thousands of
tokens and are part of a sound run's reading.  At the COMPARED position
this reference resolves them ITSELF, from its own scores and nothing of the
program's but the logits it is compared with: in every expert layer,
`selections` lists the top-8 sets that are correct within `NEAR_TIE` of its
own float32 router logits (every expert more than NEAR_TIE above the first
one left out is in, none more than NEAR_TIE below the last one picked) and
differ from its own set in an expert HELD here; each is carried through the
remaining layers for that position alone (where it forks again if a later
layer ties), and the alternative nearest to the compared logits is what
`last_logits` returns.  A selection outside that band is no alternative, so
a fault of the program's selection shows in the logits as it would without
this rule, and a control is held to the same rule as the sound reference.
Every call prints a `routing:` line: the margins at the compared position,
the alternatives with their distance from the plain selection's logits (what
a flip costs) and from the compared logits, and the one taken.  LOGIT_RTOL
is NOT widened for flips.

The compared logits are the argument ``got``.  runners/serve.py `compare`
hands a reference ``(weights, model, context)`` and keeps the program's
logits in its local ``logits``; this PR may not edit it, so
`_compared_logits` reads them from that frame.  That is a stopgap with a
one-line end: `compare` passing ``got=`` to a reference that takes it
(PERF.md, section 7, for the next `benchmark` PR).  Without them (the CPU
tests' plain calls) the plain selection is returned.

`control` makes this reference wrong in one named way, for the controls
that must come out NOT correct against the sound program:
  'no_rope'       the rope part of the score dropped (q_r . k_r = 0)
  'unnormalised'  the top-8 weights left unnormalised (w_e = 2.5 g_e)
  'int8_rows'     the cached row [c_kv ; k_r] rounded to int8 (one float32
                  scale a row, amax / 127) before keys and values are
                  expanded from it
  'fp8_weights'   every matrix rounded to float8_e4m3 (the nearest
                  precision below the configuration's bfloat16)

LOGIT_RTOL bounds ||got - want|| / ||want|| over the vocabulary held (the
2-norm); the readings it stands between are in PERF.md (my chip runs,
PR 47) and repeated beside the constant below.
"""
import functools
import itertools
import json
import math
import sys

import numpy as np

# between the sound runs' largest reading and the smallest control's, the
# cached row rounded to int8: PERF.md, Findings of PR 47 (my chip runs,
# PR 47)
LOGIT_RTOL = 0.017
# router logits closer than this are a tie at the program's precision
NEAR_TIE = 0.1
# alternatives one compared position is carried through at most (a fork a
# layer doubles them; beyond this the later forks are left out and said so)
MAX_ALTERNATIVES = 8
DENSE_BLOCK = 4608          # columns of the dense layer widened at a time
CONTROLS = ('no_rope', 'unnormalised', 'int8_rows', 'fp8_weights')


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(lat, theta):
    """[rope / 2] angles per position of the interleaved pairs."""
    rope, yarn = int(lat['rope']), lat['yarn']
    f = float(theta) ** (-np.arange(rope // 2, dtype=np.float64) * 2 / rope)
    factor, orig = float(yarn['factor']), float(yarn['original_max_len'])

    def correction(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    lo = max(math.floor(correction(float(yarn['beta_fast']))), 0)
    hi = min(math.ceil(correction(float(yarn['beta_slow']))), rope - 1)
    m = 1.0 - np.clip((np.arange(rope // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (f / factor * (1 - m) + f * m).astype(np.float32)


def score_scale(lat):
    yarn = lat['yarn']
    return (int(lat['nope']) + int(lat['rope'])) ** -0.5 \
        * (0.1 * float(yarn['mscale_all_dim'])
           * math.log(float(yarn['factor'])) + 1.0) ** 2


def _rope(x, inv_freq, first):
    """x [n, ..., rope], the rows of positions ``first`` onward: rotate
    interleaved pairs by position * inv_freq."""
    import jax.numpy as jnp
    n = x.shape[0]
    ang = (first + jnp.arange(n, dtype=jnp.float32))[:, None] \
        * jnp.asarray(inv_freq)
    ang = ang.reshape((n,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _wide(w, control):
    """A weight in float32; under 'fp8_weights' through float8_e4m3."""
    import jax
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if control == 'fp8_weights' and w.ndim >= 2:
        w = jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
    return w


def _int8(rows):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(rows), -1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(rows / s), -127, 127) * s


def _attention(x, lw, n_query, n_head, lat, theta, eps, control):
    """x [T, D], a layer's input at every position -> the stream after the
    attention at the LAST ``n_query`` positions [n_query, D]: their queries
    against every position's keys and values, causal."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    t0 = T - n_query
    kr, nope = int(lat['kv_rank']), int(lat['nope'])
    rope, v = int(lat['rope']), int(lat['v'])
    wide = functools.partial(_wide, control=control)
    inv_freq = yarn_inv_freq(lat, theta)
    h = _rms(x, wide(lw['att_norm']), eps)
    c_q = _rms(h[t0:] @ wide(lw['att_qa_w']), wide(lw['att_qa_norm']), eps)
    q = (c_q @ wide(lw['att_qb_w'])).reshape(n_query, n_head, nope + rope)
    q_nope, q_r = q[..., :nope], _rope(q[..., nope:], inv_freq, t0)
    ckv_kr = h @ wide(lw['att_kva_w'])
    c_kv = _rms(ckv_kr[:, :kr], wide(lw['att_kva_norm']), eps)
    k_r = _rope(ckv_kr[:, kr:], inv_freq, 0)
    if control == 'int8_rows':
        row = _int8(jnp.concatenate([c_kv, k_r], -1))
        c_kv, k_r = row[:, :kr], row[:, kr:]
    kv = (c_kv @ wide(lw['att_kvb_w'])).reshape(T, n_head, nope + v)
    k_nope, vals = kv[..., :nope], kv[..., nope:]
    if control == 'no_rope':
        q_r = jnp.zeros_like(q_r)
    causal = jnp.arange(T)[None, :] <= t0 + jnp.arange(n_query)[:, None]
    scale = score_scale(lat)

    def head(args):
        qn, qr, kn, vv = args                       # one head's rows
        s = (qn @ kn.T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -1e30), -1) @ vv

    att = jax.lax.map(head, (q_nope.transpose(1, 0, 2), q_r.transpose(1, 0, 2),
                             k_nope.transpose(1, 0, 2),
                             vals.transpose(1, 0, 2)))      # [H, n_query, v]
    return x[t0:] + att.transpose(1, 0, 2).reshape(n_query, n_head * v) \
        @ wide(lw['att_o_w'])


def _swiglu(h, w1, w3, w2, control):
    import jax
    wide = functools.partial(_wide, control=control)
    return (jax.nn.silu(h @ wide(w1)) * (h @ wide(w3))) @ wide(w2)


def _logit(g):
    import jax.numpy as jnp
    return jnp.log(g) - jnp.log1p(-g)


def select(g, top_k):
    """g [T, n_routed] -> (picked experts [T, top_k], the margin: the
    router logit of the last pick less that of the first one left out).
    Plain top-k over all the scores: the reading of `topk_method: none`."""
    import jax.numpy as jnp
    order = jnp.argsort(-g, axis=-1)
    ranked = _logit(jnp.take_along_axis(g, order[:, :top_k + 1], axis=-1))
    return order[:, :top_k], ranked[:, top_k - 1] - ranked[:, top_k]


def _weights(gp, moe, control):
    """The picks' scores [..., k] -> their weights."""
    import jax.numpy as jnp
    if control != 'unnormalised':
        gp = gp / jnp.sum(gp, -1, keepdims=True)
    return gp * float(moe['scale'])


def _router(h, router_w, moe, control):
    """(scores [T, n_routed], picks [T, k], weights [T, k], margins [T])."""
    import jax
    import jax.numpy as jnp
    g = jax.nn.sigmoid(h @ router_w.astype(jnp.float32))
    picks, margin = select(g, int(moe['top_k']))
    return g, picks, _weights(jnp.take_along_axis(g, picks, axis=-1), moe,
                              control), margin


def selections(g, top_k, near_tie, first, held):
    """The top-k sets of the scores g [n_routed] that are correct within
    ``near_tie`` of their router logits (every expert more than
    ``near_tie`` above the first one left out is in, none more than
    ``near_tie`` below the last one picked) and hold OTHER experts of
    [first, first + held) than the plain top-k does: sorted lists, one for
    each such choice among the held experts in the tie (which of the tied
    experts held elsewhere fill the set moves the weights' sum by less
    than ``near_tie`` of one score, and is the ranking's).  numpy."""
    g = np.asarray(g, np.float64)
    logit = np.log(g) - np.log1p(-g)
    order = np.argsort(-logit)
    ranked = logit[order]
    sure = [int(e) for e in order if logit[e] > ranked[top_k] + near_tie]
    tied = [int(e) for e in order
            if ranked[top_k - 1] - near_tie <= logit[e]
            <= ranked[top_k] + near_tie]
    here = [e for e in tied if first <= e < first + held]
    elsewhere = [e for e in tied if e not in here]
    plain = set(int(e) for e in order[:top_k])
    out = []
    for n in range(len(here) + 1):
        fill = top_k - len(sure) - n
        if not 0 <= fill <= len(elsewhere):
            continue
        for mine in itertools.combinations(here, n):
            if set(mine) != plain & set(here):
                out.append(sorted(sure + list(mine) + elsewhere[:fill]))
    return out


def _expert(h, w1, w3, w2, picks, wts, e, control):
    """Expert ``e``'s weighted part for every token (zero weight where it
    was not picked)."""
    import jax.numpy as jnp
    w_e = jnp.sum(jnp.where(picks == e, wts, 0.0), -1, keepdims=True)
    return w_e * _swiglu(h, w1, w3, w2, control)


def _compared_logits():
    """The logits runners/serve.py `compare` is about to hold this
    reference's against (its local ``logits``), or None where
    `last_logits` was not called from there: the module's docstring."""
    frame = sys._getframe(2)
    if frame.f_code.co_name != 'compare' or 'logits' not in frame.f_locals:
        return None
    return np.asarray(frame.f_locals['logits'], np.float32)


def last_logits(weights, model, context, control=None, picks_out=None,
                got=None):
    """float32 logits [vocab held] at the last position of `context`.
    ``control`` (one of CONTROLS) makes the reference wrong in that one
    way; ``got`` are the logits this call's are compared with, which decide
    between the selections a near-tie at that position admits (the
    module's docstring); ``picks_out`` (a list) receives every expert
    layer's plain (picks [T, k], margins [T]) as numpy arrays."""
    import jax
    import jax.numpy as jnp
    if control is not None and control not in CONTROLS:
        raise ValueError('control must be one of %s' % (CONTROLS,))
    if got is None:
        got = _compared_logits()
    eps = float(model.get('rms_eps', 1e-6))
    lat, moe, kinds = model['latent'], model['moe'], model['ffn']
    top_k = int(moe['top_k'])
    held = int(moe['n_routed']) // int(moe['ranks'])
    first = int(moe['rank']) * held
    attention = jax.jit(functools.partial(
        _attention, n_head=int(model['n_head']), lat=lat,
        theta=float(model['theta']), eps=eps, control=control),
        static_argnames=('n_query',))
    norm = jax.jit(lambda x, s: _rms(x, s.astype(jnp.float32), eps))
    swiglu = jax.jit(functools.partial(_swiglu, control=control))
    router = jax.jit(functools.partial(_router, moe=moe, control=control))
    expert = jax.jit(functools.partial(_expert, control=control))
    head = jax.jit(lambda x, w: x @ _wide(w, control))

    def attend(i, x, n_query):
        p = 'layer_%d_' % i
        return attention(x, {s: weights[p + s] for s in (
            'att_norm', 'att_qa_w', 'att_qa_norm', 'att_qb_w', 'att_kva_w',
            'att_kva_norm', 'att_kvb_w', 'att_o_w')}, n_query=n_query)

    def feed_forward(i, x, chosen=None):
        """x [n, D] after layer i's attention -> (x + its feed-forward,
        an expert layer's (scores, picks, margins)); ``chosen`` are the
        LAST row's experts in place of its plain top-k."""
        p = 'layer_%d_' % i
        h = norm(x, weights[p + 'ffn_norm'])
        if kinds[i] == 'dense':
            w1, w3, w2 = (weights[p + 'ffn_fc%d_w' % n] for n in (1, 3, 2))
            for a in range(0, w1.shape[1], DENSE_BLOCK):
                b = a + DENSE_BLOCK
                x = x + swiglu(h, w1[:, a:b], w3[:, a:b], w2[a:b])
            return x, None
        g, picks, wts, margin = router(h, weights[p + 'moe_router_w'])
        routed = (g, picks, margin)
        if chosen is not None:
            chosen = jnp.asarray(chosen, picks.dtype)
            picks = picks.at[-1].set(chosen)
            wts = wts.at[-1].set(_weights(g[-1][chosen], moe, control))
        w1, w3, w2 = (weights[p + 'moe_fc%d_w' % n] for n in (1, 3, 2))
        for e in range(w1.shape[0]):            # the experts held, one by one
            x = x + expert(h, w1[e], w3[e], w2[e], picks, wts, first + e)
        return x + swiglu(h, weights[p + 'moe_shared_fc1_w'],
                          weights[p + 'moe_shared_fc3_w'],
                          weights[p + 'moe_shared_fc2_w']), routed

    def logits_of(row):
        return np.asarray(head(norm(row, weights['final_norm']),
                               weights['lm_proj_w']), np.float32)

    def ties(routed):
        return selections(routed[0][-1], top_k, NEAR_TIE, first, held)

    margins, inputs, fork = [], [], None
    with jax.default_matmul_precision('highest'):
        x = weights['tok_emb'][jnp.asarray(context, jnp.int32)] \
            .astype(jnp.float32)
        for i in range(len(kinds)):
            if got is not None:         # an alternative's earlier positions
                inputs.append(np.asarray(x))
            x, routed = feed_forward(i, attend(i, x, x.shape[0]))
            if routed is None:
                continue
            margins.append(np.asarray(routed[2]))
            if picks_out is not None:
                picks_out.append((np.asarray(routed[1]), margins[-1]))
            if fork is None and got is not None and ties(routed):
                fork = i
        plain = logits_of(x[-1])

        # the compared position alone through layers i onward, its stream
        # entering layer i over the plain pass's earlier positions: every
        # selection a tie admits forks, and each fork runs to the logits
        ends, left_out = [], []

        def finish(i, row, trail):
            for j in range(i, len(kinds)):
                att = attend(j, jnp.concatenate(
                    [jnp.asarray(inputs[j][:-1]), row]), 1)
                row, routed = feed_forward(j, att)
                for chosen in ties(routed) if routed else ():
                    if len(ends) + 1 >= MAX_ALTERNATIVES:
                        left_out.append(j)
                        break
                    finish(j + 1, feed_forward(j, att, chosen)[0],
                           trail + [(j, chosen)])
            ends.append((trail, logits_of(row[0])))

        if fork is not None:
            finish(fork, jnp.asarray(inputs[fork][-1:]), [])

    def apart(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def said(trail):
        return [{'layer': j, 'experts': [int(e) for e in chosen]}
                for j, chosen in trail]

    taken, logits = [], plain
    for trail, alt in ends:
        if trail and apart(got, alt) < apart(got, logits):
            taken, logits = trail, alt
    if margins:
        m = np.stack(margins)                                # [layers, T]
        print('routing: %s' % json.dumps({
            'control': control, 'context': int(m.shape[1]),
            'near_tie': NEAR_TIE,
            'margin_at_compared_position': [float(v) for v in m[:, -1]],
            'share_of_pairs_with_margin_under_near_tie':
                float(np.mean(m < NEAR_TIE)),
            'compared_with_logits': got is not None,
            'alternatives': [
                {'selections': said(trail), 'from_plain': apart(alt, plain),
                 'from_compared': apart(got, alt)}
                for trail, alt in ends if trail],
            'plain_from_compared':
                None if got is None else apart(got, plain),
            'position_alone_from_plain':
                [apart(alt, plain) for trail, alt in ends if not trail],
            'taken': said(taken),
            'forks_left_out_in_layers': left_out}, sort_keys=True),
            flush=True)
    return logits
