"""Plain reference for `kimi_linear`: the Kimi-Linear block as ONE
expert-parallel rank of 4 holds it, a full causal forward pass over the whole
context in jax.numpy: float32, matmul precision `highest`, no cache, no
pages, no chunks, the delta rule a token at a time (`lax.scan`), the latent
layers in the plain form (keys and values expanded for every head), a loop
over the experts held (each over every token), independent of the program's
kda.py, latent.py and experts.py.

The equations (ISSUE 61, section 1); no biases, eps 1e-5:

    x0       = tok_emb[ids]
    layer i:   x = x + mixer_i(RMSNorm(x));  x = x + ffn_i(RMSNorm(x))
    logits   = RMSNorm(x) W_head, over the rows of the vocabulary held

    KDA (model['mixer'][i] == 'kda'; H = 32 heads, d = 128):
      q^, k^, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))
                  conv4: causal, depthwise, 4 taps, one filter a column
                  (tap j multiplies the input 3 - j positions back)
      q, k      = l2norm(q^) * 128^(-1/2), l2norm(k^)        a head
      g         = -exp(A_log[head]) softplus((h W_fa) W_fb + dt_bias + dt_shift)
      beta      = sigmoid(h W_beta)
      S         = Diag(exp(g_t)) S;  u_t = beta_t (v_t - S^T k_t)
      S         = S + k_t u_t^T;     o_t = S^T q_t            S_0 = 0
      y         = concat_heads(RMSNorm_head(o_t) * sigmoid((h W_ga) W_gb)) W_o
    Latent, no positions (model['mixer'][i] == 'latent'):
      q = h W_q -> 32 heads of [q_nope 128 ; q_pe 64];
      [c_kv 512 ; k_pe 64] = h W_kva;  c_kv = RMSNorm(c_kv);
      [k_nope 128 ; v 128] a head = c_kv W_kvb;  ONE k_pe for all heads, NO
      rotation;  score = (q_nope . k_nope + q_pe . k_pe) * 192^(-1/2), causal,
      softmax;  out = concat_heads(P v) W_o
    FFN 0:    W_2 (silu(h W_1) * h W_3), width 9216
    FFN >= 1: g = sigmoid(h W_g) [T, 256];  E = top-8(g + b) (`select`; b the
              choice bias, for the CHOICE only);
              w_e = 2.446 g_e / sum_{e' in E} g_e';
              y = sum_{e in E, e HELD} w_e FFN_e(h) + FFN_shared(h), width 1024

The share: of a layer's 256 routed experts this rank holds
`model['moe']['rank']`'s contiguous block of 64; routing and the
normalisation are over all 256 and all 8 picks, and what the absent 192
would have added is left out here as in the program.
tests/test_generation_kda.py adds the 4 shares up to the uncut layer at a
tiny size.

ASSUMED (configs/kimi_linear.json lists it): the choice bias `b` (the
source's gate has an `e_score_correction_bias`; the catalog row has no key
for it) - the selection is ONE function, `select`, so another reading changes
one place; no bias in the short convolutions; `dt_shift` (model['kda']), a
constant beside `dt_bias`: the runner draws every weight about zero, and a
decay whose bias is about zero forgets a token within a few positions, so
the model dict carries the bias's mean; the weights are the runner's draw
(every array normal at `initializer_range`, names ending in `norm` ones).

The weights are the runtime's own bfloat16 arrays widened to float32 one
matrix (one expert, one block of the dense layer's columns) at a time, and
the latent scores are taken one head at a time.

NEAR-TIES, treated as references/axk1.py treats them.  Routing is
discontinuous: where the 8th and the 9th of a token's selection scores lie
closer than the program's bfloat16 products resolve, the program may pick the
other expert, and if exactly one of the two is held here (three cases in
eight at 4 ranks) that expert's whole contribution is in one result and not
in the other: 7-23 % of the logits at the compared position.  At earlier
positions such flips are diluted (by the state and by attention over the
context) and are part of a sound run's reading.  At the COMPARED position
this reference resolves them ITSELF, from its own scores and nothing of the
program's but the logits it is compared with.  `selections` lists, for one
token's scores in one expert layer, the top-8 sets that are correct within
`NEAR_TIE` router logits of the cut between the 8th and the 9th selection
score (`_above_the_cut`: a score's distance from the cut over g (1 - g), the
slope of the score in its router logit, which the bias does not change) and
differ from the plain set in an expert HELD here.  Behind a flip the stream is
another one: the later layers pick other experts on it and tie elsewhere, so
a layer's options are taken ANEW on the stream that reaches it, as axk1's
recursion does, and a layer that is not moved takes the top-8 of its own
scores there.  With three ties in eight touching a held expert, in up to four
layers of one position, the tree of selections is too wide to search, and
whole passes cannot rank its branches: a wrong flip early can tip a later
coin-flip tie the right way and look nearer than the right one.  The
layers are taken ONCE, in order, on the stream the choices so far leave: where
a layer ties, each selection the tie admits there is weighed by ITS OWN part
of the stream alone (what its experts add to the row, the later layers' parts
held as they stand on the unmoved stream, through the final norm and the
head) against the unmoved selection, and the nearest to the compared logits
is taken.  That decides sharply: an expert's part is one direction of the
2,304 the stream has, so a selection the program made brings the logits
nearer by about its own length, one it did not make moves them away by as
much, and what the other layers still differ in lies across both.  The stream
then goes on from the selection taken, and the last layer's row gives the
logits: always logits this reference computed for one admissible selection a
layer.  Nothing is done where the plain selection already lies within
LOGIT_RTOL (all that `correct` asks).  A selection outside the band is no
alternative, so a fault of the program's selection shows in the logits as it
would without this rule, and a control is held to the same rule as the sound
reference.  Every call prints a `routing:` line.  LOGIT_RTOL is NOT widened
for flips.

The compared logits are the argument ``got``.  runners/serve.py `compare`
hands a reference ``(weights, model, context)`` and keeps the program's
logits in its local ``logits``; this PR may not edit it, so
`_compared_logits` reads them from that frame, as references/axk1.py does
(PERF.md, section 7, for the next `benchmark` PR).  Without them (the CPU
tests' plain calls) the plain selection is returned.

`control` makes this reference wrong in one named way, for the controls that
must come out NOT correct against the sound program:
  'no_delta'     the delta correction dropped: u_t = beta_t v_t
  'mean_decay'   the decay a channel replaced by its mean over the head's
                 channels (a gated delta rule with one gate a head)
  'bf16_state'   the matrix state rounded to bfloat16 after every token
  'no_pe'        q_pe . k_pe dropped from the latent score
  'chunk_reset'  the matrix state zeroed where a chunk begins: at every
                 multiple of `CHUNK` positions and before the last position
                 (the one-token chunk the comparison ends with)
  'fp8_weights'  every matrix rounded to float8_e4m3 (the nearest precision
                 below the configuration's bfloat16)

LOGIT_RTOL bounds ||got - want|| / ||want|| over the vocabulary held (the
2-norm); the readings it stands between are in PERF.md (my chip runs, PR 61)
and repeated beside the constant below.
"""
import functools
import itertools
import json
import sys

import numpy as np

# between the sound runs' largest reading and the smallest control's worst
# prompt, the matrix state rounded to bfloat16 between steps: the readings
# are in PERF.md, Findings of PR 61 (my chip runs, PR 61).  Little room: most
# of a sound reading is routing at EARLIER positions (the program's picks
# differ from this reference's in a held expert in 8 % of (token, layer)
# pairs), which no rule resolves.
LOGIT_RTOL = 0.035
# selection scores this many router logits from the cut or nearer are a tie
# at the program's precision
NEAR_TIE = 0.1
DENSE_BLOCK = 4608          # columns of the dense layer widened at a time
CHUNK = 512                 # the cell's prefill chunk ('chunk_reset')
CONTROLS = ('no_delta', 'mean_decay', 'bf16_state', 'no_pe', 'chunk_reset',
            'fp8_weights')


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _wide(w, control):
    """A weight in float32; under 'fp8_weights' through float8_e4m3."""
    import jax
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if control == 'fp8_weights' and w.ndim >= 2:
        w = jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
    return w


def _conv(x, taps):
    """x [T, n], taps [K, n]: the causal depthwise convolution, zeros
    before the first position."""
    import jax.numpy as jnp
    K, T = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + T] * taps[j] for j in range(K))


def _kda(x, lw, n_query, kda, eps, control, chunk):
    """x [T, D], a layer's input at every position -> the stream after the
    KDA mixer at the LAST ``n_query`` positions [n_query, D]."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    H, d = int(kda['n_heads']), int(kda['head_dim'])
    wide = functools.partial(_wide, control=control)
    h = _rms(x, wide(lw['att_norm']), eps)

    def heads(name):
        out = jax.nn.silu(_conv(h @ wide(lw['kda_%s_w' % name]),
                                wide(lw['kda_%s_conv' % name])))
        return out.reshape(T, H, d)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = unit(heads('q')) * d ** -0.5, unit(heads('k')), heads('v')
    raw = (h @ wide(lw['kda_fa_w'])) @ wide(lw['kda_fb_w']) \
        + wide(lw['kda_dt_bias']) + float(kda.get('dt_shift', 0.0))
    g = -jnp.exp(wide(lw['kda_A_log']))[:, None] \
        * jax.nn.softplus(raw).reshape(T, H, d)
    if control == 'mean_decay':
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ wide(lw['kda_beta_w']))            # [T, H]
    t = jnp.arange(T)
    reset = ((t % chunk == 0) & (t > 0)) | (t == T - 1) \
        if control == 'chunk_reset' else jnp.zeros((T,), bool)

    def step(S, xs):
        qt, kt, vt, gt, bt, zero = xs
        S = jnp.where(zero, 0.0, S) * jnp.exp(gt)[..., None]
        u = vt - jnp.einsum('hkv,hk->hv', S, kt)
        if control == 'no_delta':
            u = vt
        S = S + kt[..., None] * (bt[:, None] * u)[:, None, :]
        if control == 'bf16_state':
            # not a pair of casts: XLA:TPU may keep the excess precision
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum('hkv,hk->hv', S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, g, beta, reset))
    t0 = T - n_query
    o = _rms(o[t0:], wide(lw['kda_o_norm']), eps)
    gate = jax.nn.sigmoid((h[t0:] @ wide(lw['kda_ga_w']))
                          @ wide(lw['kda_gb_w'])).reshape(n_query, H, d)
    return x[t0:] + (o * gate).reshape(n_query, H * d) @ wide(lw['kda_o_w'])


def _attention(x, lw, n_query, n_head, lat, eps, control):
    """x [T, D], a layer's input at every position -> the stream after the
    latent attention at the LAST ``n_query`` positions [n_query, D]: their
    queries against every position's keys and values, causal, no
    positions."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    t0 = T - n_query
    kr, nope = int(lat['kv_rank']), int(lat['nope'])
    rope, v = int(lat['rope']), int(lat['v'])
    wide = functools.partial(_wide, control=control)
    h = _rms(x, wide(lw['att_norm']), eps)
    q = (h[t0:] @ wide(lw['att_q_w'])).reshape(n_query, n_head, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv_kpe = h @ wide(lw['att_kva_w'])
    c_kv = _rms(ckv_kpe[:, :kr], wide(lw['att_kva_norm']), eps)
    k_pe = ckv_kpe[:, kr:]
    kv = (c_kv @ wide(lw['att_kvb_w'])).reshape(T, n_head, nope + v)
    k_nope, vals = kv[..., :nope], kv[..., nope:]
    if control == 'no_pe':
        q_pe = jnp.zeros_like(q_pe)
    causal = jnp.arange(T)[None, :] <= t0 + jnp.arange(n_query)[:, None]
    scale = (nope + rope) ** -0.5

    def head(args):
        qn, qp, kn, vv = args                       # one head's rows
        s = (qn @ kn.T + qp @ k_pe.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -1e30), -1) @ vv

    att = jax.lax.map(head, (q_nope.transpose(1, 0, 2),
                             q_pe.transpose(1, 0, 2),
                             k_nope.transpose(1, 0, 2),
                             vals.transpose(1, 0, 2)))      # [H, n_query, v]
    return x[t0:] + att.transpose(1, 0, 2).reshape(n_query, n_head * v) \
        @ wide(lw['att_o_w'])


def _swiglu(h, w1, w3, w2, control):
    import jax
    wide = functools.partial(_wide, control=control)
    return (jax.nn.silu(h @ wide(w1)) * (h @ wide(w3))) @ wide(w2)


def _above_the_cut(g, b, top_k, xp):
    """How far each expert's selection score g + b lies above the cut
    between the last pick and the first one left out, in ROUTER LOGITS: the
    score's distance from the cut's midpoint over d(score)/d(logit) =
    g (1 - g).  (The bias shifts a score and not its slope: a score's logit
    would misstate the distance of every expert with a large bias, and those
    are the ones a biased choice picks.)  g [..., n_routed]; positive for
    exactly the top_k picks."""
    s = g + b
    ranked = -xp.sort(-s, axis=-1)
    cut = 0.5 * (ranked[..., top_k - 1:top_k] + ranked[..., top_k:top_k + 1])
    return (s - cut) / xp.maximum(g * (1.0 - g), 1e-6)


def select(g, b, top_k):
    """g [T, n_routed] scores, b [n_routed] the choice bias -> (picked
    experts [T, top_k], the margin: how far the last pick lies above the cut
    plus how far the first one left out lies below it, in router logits).
    The BIAS-CORRECTED choice, top-k over g + b; the bias enters nothing
    else."""
    import jax.numpy as jnp
    order = jnp.argsort(-(g + b), axis=-1)
    above = jnp.take_along_axis(_above_the_cut(g, b, top_k, jnp),
                                order[:, :top_k + 1], axis=-1)
    return order[:, :top_k], above[:, top_k - 1] - above[:, top_k]


def _weights(gp, moe):
    """The picks' scores [..., k] -> their weights."""
    import jax.numpy as jnp
    return gp / jnp.sum(gp, -1, keepdims=True) * float(moe['scale'])


def _router(h, router_w, bias, moe):
    """(scores [T, n_routed], picks [T, k], weights [T, k], margins [T])."""
    import jax
    import jax.numpy as jnp
    g = jax.nn.sigmoid(h @ router_w.astype(jnp.float32))
    picks, margin = select(g, bias.astype(jnp.float32), int(moe['top_k']))
    return g, picks, _weights(jnp.take_along_axis(g, picks, axis=-1),
                              moe), margin


def selections(g, b, top_k, near_tie, first, held):
    """The top-k sets of the scores g [n_routed] under the choice bias b that
    are correct within ``near_tie`` router logits of the cut (`_above_the_cut`:
    every expert more than ``near_tie`` above it is in, none more than
    ``near_tie`` below it) and hold OTHER experts of [first, first + held)
    than the plain top-k does: sorted lists, one for each such choice among
    the held experts in the tie (which of the tied experts held elsewhere
    fill the set is the ranking's).  numpy."""
    above = _above_the_cut(np.asarray(g, np.float64),
                           np.asarray(b, np.float64), top_k, np)
    order = np.argsort(-above)
    sure = [int(e) for e in order if above[e] > near_tie]
    tied = [int(e) for e in order if abs(above[e]) <= near_tie]
    here = [e for e in tied if first <= e < first + held]
    elsewhere = [e for e in tied if e not in here]
    plain = set(int(e) for e in order if above[e] > 0)
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
    reference's against (its local ``logits``), or None where `last_logits`
    was not called from there: the module's docstring."""
    frame = sys._getframe(2)
    if frame.f_code.co_name != 'compare' or 'logits' not in frame.f_locals:
        return None
    return np.asarray(frame.f_locals['logits'], np.float32)


_MIXER_WEIGHTS = {
    'kda': ('att_norm', 'kda_q_w', 'kda_k_w', 'kda_v_w', 'kda_q_conv',
            'kda_k_conv', 'kda_v_conv', 'kda_fa_w', 'kda_fb_w', 'kda_A_log',
            'kda_dt_bias', 'kda_beta_w', 'kda_ga_w', 'kda_gb_w',
            'kda_o_norm', 'kda_o_w'),
    'latent': ('att_norm', 'att_q_w', 'att_kva_w', 'att_kva_norm',
               'att_kvb_w', 'att_o_w')}


def last_logits(weights, model, context, control=None, picks_out=None,
                got=None, chunk=None):
    """float32 logits [vocab held] at the last position of `context`.
    ``control`` (one of CONTROLS) makes the reference wrong in that one
    way (``chunk``: where 'chunk_reset' zeroes, `CHUNK` unless given);
    ``got`` are the logits this call's are compared with, which decide
    between the selections a near-tie at that position admits (the module's
    docstring); ``picks_out`` (a list) receives every expert layer's plain
    (picks [T, k], margins [T]) as numpy arrays."""
    import jax
    import jax.numpy as jnp
    if control is not None and control not in CONTROLS:
        raise ValueError('control must be one of %s' % (CONTROLS,))
    if got is None:
        got = _compared_logits()
    eps = float(model.get('rms_eps', 1e-6))
    moe, kinds, mixers = model['moe'], model['ffn'], model['mixer']
    top_k = int(moe['top_k'])
    held = int(moe['n_routed']) // int(moe['ranks'])
    first = int(moe['rank']) * held
    mix = {'latent': jax.jit(functools.partial(
               _attention, n_head=int(model['n_head']), lat=model['latent'],
               eps=eps, control=control), static_argnames=('n_query',)),
           'kda': jax.jit(functools.partial(
               _kda, kda=model.get('kda'), eps=eps, control=control,
               chunk=int(chunk or CHUNK)), static_argnames=('n_query',))}
    norm = jax.jit(lambda x, s: _rms(x, s.astype(jnp.float32), eps))
    swiglu = jax.jit(functools.partial(_swiglu, control=control))
    router = jax.jit(functools.partial(_router, moe=moe))
    expert = jax.jit(functools.partial(_expert, control=control))
    head = jax.jit(lambda x, w: x @ _wide(w, control))

    def attend(i, x, n_query):
        p = 'layer_%d_' % i
        return mix[mixers[i]](
            x, {s: weights[p + s] for s in _MIXER_WEIGHTS[mixers[i]]},
            n_query=n_query)

    def feed_forward(i, x, chosen=None):
        """x [n, D] after layer i's mixer -> (x + its feed-forward, an
        expert layer's ((scores, choice bias), picks, margins)); ``chosen`` are
        the LAST row's experts in place of its plain top-k."""
        p = 'layer_%d_' % i
        h = norm(x, weights[p + 'ffn_norm'])
        if kinds[i] == 'dense':
            w1, w3, w2 = (weights[p + 'ffn_fc%d_w' % n] for n in (1, 3, 2))
            for a in range(0, w1.shape[1], DENSE_BLOCK):
                b = a + DENSE_BLOCK
                x = x + swiglu(h, w1[:, a:b], w3[:, a:b], w2[a:b])
            return x, None
        bias = weights[p + 'moe_router_bias']
        g, picks, wts, margin = router(h, weights[p + 'moe_router_w'], bias)
        routed = ((g, bias.astype(jnp.float32)), picks, margin)
        if chosen is not None:
            chosen = jnp.asarray(chosen, picks.dtype)
            picks = picks.at[-1].set(chosen)
            wts = wts.at[-1].set(_weights(g[-1][chosen], moe))
        w1, w3, w2 = (weights[p + 'moe_fc%d_w' % n] for n in (1, 3, 2))
        for e in range(w1.shape[0]):            # the experts held, one by one
            x = x + expert(h, w1[e], w3[e], w2[e], picks, wts, first + e)
        return x + swiglu(h, weights[p + 'moe_shared_fc1_w'],
                          weights[p + 'moe_shared_fc3_w'],
                          weights[p + 'moe_shared_fc2_w']), routed

    def logits_of(row):
        return np.asarray(head(norm(row, weights['final_norm']),
                               weights['lm_proj_w']), np.float32)

    def apart(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def ties(routed):
        (g, b), _, _ = routed
        return selections(g[-1], b, top_k, NEAR_TIE, first, held)

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

        # the compared position alone from layer `fork` on, its stream
        # entering each layer over the plain pass's earlier positions
        def mixed(j, row):
            return attend(j, jnp.concatenate(
                [jnp.asarray(inputs[j][:-1]), row]), 1)

        def finish(j, row):
            """The stream after layers j onward, each taking the top-k of
            ITS OWN scores on the stream it is handed, as the program's
            router does behind its own flip."""
            for i in range(j, len(kinds)):
                row = feed_forward(i, mixed(i, row))[0]
            return row

        # layer by layer, in order, on the stream the choices so far leave:
        # where a layer ties, each selection the tie admits THERE is weighed
        # by its own part of the stream alone (what its experts add, the
        # later layers' parts held as they stand) against the unmoved one,
        # and the nearest to the compared logits is taken
        weighed, taken, logits = [], [], plain
        if fork is not None and apart(got, plain) > LOGIT_RTOL:
            row = jnp.asarray(inputs[fork][-1:])
            for j in range(fork, len(kinds)):
                att = mixed(j, row)
                row, routed = feed_forward(j, att)
                options = ties(routed) if routed else []
                if not options:
                    continue
                end = finish(j + 1, row)
                unmoved = near = apart(got, logits_of(end[0]))
                for chosen in options:
                    moved = feed_forward(j, att, chosen)[0]
                    dist = apart(got, logits_of((end + moved - row)[0]))
                    weighed.append({'layer': j, 'unmoved': unmoved,
                                    'experts': [int(e) for e in chosen],
                                    'from_compared': dist})
                    if dist < near:
                        near, kept = dist, (chosen, moved)
                if near < unmoved:
                    taken.append({'layer': j,
                                  'experts': [int(e) for e in kept[0]]})
                    row = kept[1]
            logits = logits_of(row[0])
            if apart(got, logits) >= apart(got, plain):
                taken, logits = [], plain

    if margins:
        m = np.stack(margins)                                # [layers, T]
        print('routing: %s' % json.dumps({
            'control': control, 'context': int(m.shape[1]),
            'near_tie': NEAR_TIE,
            'margin_at_compared_position': [float(v) for v in m[:, -1]],
            'share_of_pairs_with_margin_under_near_tie':
                float(np.mean(m < NEAR_TIE)),
            'compared_with_logits': got is not None,
            'weighed': weighed,
            'plain_from_compared':
                None if got is None else apart(got, plain),
            'taken': taken}, sort_keys=True),
            flush=True)
    return logits
