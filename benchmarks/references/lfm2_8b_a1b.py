"""Plain reference for `lfm2_8b_a1b`: the LFM2-8B-A1B block, a full causal
forward pass over the whole context in jax.numpy: float32, matmul precision
`highest`, no cache, no pages, no chunks, no kernels, the convolution over the
whole context at once, the attention one head at a time, a loop over the 32
experts (each over every token), independent of the program's shortconv.py,
decode.py, experts.py and ops/attention.py.

PRECISION, as the equations state it (ISSUE 63, section 1: "the two products
in the weights' dtype with float32 accumulation"; residual stream, norms,
softmax, router and tails float32): every array and every operation here is
float32 at `highest`, and the OPERAND of each product with a weight, and each
row the cache holds (the normed, rotated key, the value, the query that meets
them, the probabilities that weigh the values), is first rounded to the
weights' dtype (`_operand`: to bfloat16 for the cell's bfloat16 weights, not
at all for the CPU tests' float32 ones), then multiplied exactly and summed in
float32.  That is the configuration's stated precision and nothing below it;
what is compared is then the mathematics and not the rounding of one side.
Why it has to be so HERE: every expert is held (ranks = 1) and the weights are
drawn, so a rounding difference of 1 % in a layer's input moves the choice of
one (token, layer) pair in seven, each such flip swaps a whole expert, and the
flips travel down the sequence through every convolution's tail; the same
reference with no operand rounded (`control='f32_operands'`, a READING the
control script reports) differs from the sound program's picks in 12-24 % of
the pairs and reads 0.013-0.14 in logits, as much as two of the controls (CPU
rehearsal at a width of 512, and PERF.md, Findings of PR 63, for the chip's).

The equations (ISSUE 63, section 1); no biases, eps 1e-5:

    x0       = tok_emb[ids]
    layer i:   x = x + mixer_i(RMSNorm(x; att_norm));
               x = x + ffn_i(RMSNorm(x; ffn_norm))
    logits   = RMSNorm(x; final_norm) W_head

    conv (model['mixer'][i] == 'conv'; L = 3 taps):
      [B ; C ; x~] = h W_in             W_in [2048, 6144], split in that order
      u_t = B_t * x~_t
      c_t = sum_{j=0..2} k_j * u_{t-2+j}   u zero before the stream's start;
            k [3, 2048] (the source's [2048, 3] with the channels along the
            lanes)
      y_t = (C_t * c_t) W_out
    attention (model['mixer'][i] == 'gqa'; 32 query / 8 key-value heads of 64):
      q, k, v  = h W_q, h W_k, h W_v
      q, k     = RMSNorm_head(q; q_norm), RMSNorm_head(k; k_norm)   over 64
      q, k     = rotary(q), rotary(k)       theta 1e6, whole head, pairs
                 (2i, 2i + 1): the runtime's public convention (`assumed`)
      score    = q . k * 64^(-1/2), causal, softmax; four query heads a
                 key-value head;  out = concat_heads(P v) W_o
    FFN 0-1:  W_2 (silu(h W_1) * h W_3), width 7168
    FFN >= 2: g = sigmoid(h W_g) [T, 32];  E = top-4(g + b) (`select`; b the
              choice bias, for the CHOICE only);
              w_e = 1.0 g_e / (sum_{e' in E} g_e' + 1e-6);
              y = sum_{e in E} w_e FFN_e(h), width 1792.  NO shared expert.

ASSUMED (configs/lfm2_8b_a1b.json lists it): the rotary pairs (the source
pairs column i with i + 32; one fixed permutation of a head's q/k columns and
of q_norm / k_norm maps one convention to the other, so with seeded weights
it is the same model); embedding and head as two arrays (the source ties
them); the weights are the runner's draw (every array normal at
`initializer_range`, names ending in `norm` ones; the choice bias and the
filter are weights like any other).

The weights are the runtime's own bfloat16 arrays widened to float32 one
matrix (one expert, one block of the dense layer's or the head's columns) at
a time.

NEAR-TIES, treated as references/kimi_linear.py treats them (its docstring
has the argument; `selections`, `select` and `_above_the_cut` are IMPORTED from
it).  Routing is discontinuous: where the 4th and the 5th of a token's
selection scores lie closer than the program's bfloat16 products resolve, the
program may pick the other expert, and here EVERY expert is held (ranks = 1),
so every such flip puts one expert's whole part into one result and not into
the other.  At the COMPARED position this reference resolves them itself:
one pass over the layers, in order, on the stream the choices so far leave;
where a layer ties (within `NEAR_TIE` router logits of the cut), each
selection the tie admits there is weighed by its own part of the stream
against the unmoved one and the nearest to the compared logits is taken.
Nothing is done where the plain selection already lies within LOGIT_RTOL.  A
selection outside the band is no alternative, and a control is held to the
same rule as the sound reference.  Flips at EARLIER positions are part of a
sound run's reading: they reach the compared position through the attention
layers' keys and values and, from the two positions before it, through every
convolution's tail.  LOGIT_RTOL is NOT widened for flips.

The compared logits are the argument ``got``; runners/serve.py `compare`
keeps them in its local ``logits``, and `_compared_logits` reads them from
that frame as references/axk1.py and references/kimi_linear.py do (PERF.md,
section 7, for the next `benchmark` PR).

`control` makes this reference wrong in one named way, for the controls that
must come out NOT correct against the sound program:
  'tail_reset'   the convolution's tail zeroed where a chunk begins: at every
                 multiple of `CHUNK` positions and before the last position
                 (the one-token chunk the comparison ends with)
  'no_qk_norm'   the query/key head norms left out
  'no_bias'      the experts chosen without b: top-4 of g
  'no_rope'      the rotation left out
  'fp8_weights'  every matrix rounded to float8_e4m3 (the nearest precision
                 below the configuration's bfloat16)
and two READINGS that the control script reports and nobody judges:
  'bf16_stream'  the residual stream rounded to bfloat16 after every mixer
                 and every feed-forward: 0.2 % an element 32 times, and the
                 flips that follows; it reads one and a half to twice a sound
                 run and cannot be told from one
  'f32_operands' no operand rounded (above)

LOGIT_RTOL bounds ||got - want|| / ||want|| over the vocabulary (the
2-norm); the readings it stands between are in PERF.md (my chip runs, PR 63)
and repeated beside the constant below.
"""
import functools
import importlib.util
import json
import os
import sys

import numpy as np

# between the sound runs' largest reading, 0.0236 (68 prompts of 17 weight
# seeds at the published widths: 0.0029-0.0236, median 0.0040; the shortest
# context, 73 tokens, reads highest: 0.0046-0.0236), and the weakest
# control's worst prompt, the rotation left out, 0.117 (then 0.304 no choice
# bias, 0.380 no head norms, 0.986 float8 weights, 1.31 the tail zeroed): 2.3
# times over the one and 2.1 times under the other (my chip runs, PR 63;
# PERF.md, Findings of PR 63)
LOGIT_RTOL = 0.055
# selection scores this many router logits from the cut or nearer are a tie
# at the program's precision
NEAR_TIE = 0.1
DENSE_BLOCK = 3584          # columns of a dense layer widened at a time
HEAD_BLOCK = 8192           # columns of the head widened at a time
CHUNK = 512                 # the cell's prefill chunk ('tail_reset')
CONTROLS = ('tail_reset', 'no_qk_norm', 'no_bias', 'no_rope', 'fp8_weights')
# read, not judged (the module's docstring)
READINGS = ('bf16_stream', 'f32_operands')


def _kimi():
    """references/kimi_linear.py, for its near-tie helpers."""
    name = 'bench_references_kimi_linear'
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               'kimi_linear.py'))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


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


def _operand(x, like, control):
    """x as the operand of a product with the weight ``like`` (or as a row
    of the cache, which holds the weights' dtype): rounded to bfloat16
    where ``like`` is bfloat16, as the equations state the products
    (operands in the weights' dtype, float32 accumulation); itself for
    float32 weights and under 'f32_operands'.  `reduce_precision`, not a
    pair of casts: XLA:TPU may keep the excess precision."""
    import jax
    import jax.numpy as jnp
    if like.dtype != jnp.bfloat16 or control == 'f32_operands':
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot(x, w, control):
    """x @ w: the operand rounded as `_operand` says, the weight widened,
    float32 accumulation at `highest`."""
    return _operand(x, w, control) @ _wide(w, control)


def _stream(x, control):
    """The residual stream as a sublayer leaves it; under 'bf16_stream'
    through bfloat16 (not a pair of casts: XLA:TPU may keep the excess
    precision)."""
    import jax
    if control == 'bf16_stream':
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _conv(x, lw, n_query, eps, control, chunk):
    """x [T, D], a layer's input at every position -> the stream after the
    gated short convolution at the LAST ``n_query`` positions."""
    import jax.numpy as jnp
    T, D = x.shape
    wide = functools.partial(_wide, control=control)
    h = _rms(x, wide(lw['att_norm']), eps)
    b, c, xt = jnp.split(_dot(h, lw['conv_in_w'], control), 3, axis=-1)
    u = b * xt
    taps = wide(lw['conv_taps'])
    L = taps.shape[0]
    t = jnp.arange(T)
    out = jnp.zeros_like(u)
    for j in range(L):
        back = L - 1 - j                       # tap j: the row `back` before
        rows = jnp.concatenate([jnp.zeros((back, D), u.dtype),
                                u[:T - back]]) if back else u
        if control == 'tail_reset' and back:
            # a chunk that begins at position s sees no row before s
            start = jnp.where(t == T - 1, T - 1, (t // chunk) * chunk)
            rows = jnp.where((t - back >= start)[:, None], rows, 0.0)
        out = out + rows * taps[j]
    t0 = T - n_query
    return _stream(x[t0:] + _dot(c[t0:] * out[t0:], lw['conv_out_w'],
                                 control), control)


def _rope(x, pos, theta):
    """x [T, heads, dh]: pairs (2i, 2i + 1) turn by pos * theta^(-2i/dh)."""
    import jax.numpy as jnp
    dh = x.shape[-1]
    ang = pos[:, None, None].astype(jnp.float32) \
        * theta ** (-jnp.arange(0, dh // 2) * 2.0 / dh)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(x, lw, n_query, n_head, n_kv_head, dh, theta, eps, control):
    """x [T, D] -> the stream after grouped-query attention at the LAST
    ``n_query`` positions: their queries against every position's keys and
    values, causal."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    t0 = T - n_query
    wide = functools.partial(_wide, control=control)
    h = _rms(x, wide(lw['att_norm']), eps)
    q = _dot(h[t0:], lw['att_q_w'], control).reshape(n_query, n_head, dh)
    k = _dot(h, lw['att_k_w'], control).reshape(T, n_kv_head, dh)
    v = _dot(h, lw['att_v_w'], control).reshape(T, n_kv_head, dh)
    if control != 'no_qk_norm':
        q = _rms(q, wide(lw['att_q_norm']), eps)
        k = _rms(k, wide(lw['att_k_norm']), eps)
    if control != 'no_rope':
        q = _rope(q, t0 + jnp.arange(n_query), theta)
        k = _rope(k, jnp.arange(T), theta)
    # the cache holds the normed, rotated key and the value in the
    # weights' dtype, and the query meets it there
    cached = functools.partial(_operand, like=lw['att_k_w'], control=control)
    q, k, v = cached(q), cached(k), cached(v)
    causal = jnp.arange(T)[None, :] <= t0 + jnp.arange(n_query)[:, None]
    group = n_head // n_kv_head

    def head(args):
        qh, kh, vh = args                           # one query head's rows
        s = (qh @ kh.T) * dh ** -0.5
        return cached(jax.nn.softmax(jnp.where(causal, s, -1e30), -1)) @ vh

    att = jax.lax.map(head, (q.transpose(1, 0, 2),
                             jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
                             jnp.repeat(v.transpose(1, 0, 2), group, axis=0)))
    return _stream(x[t0:] + _dot(
        cached(att).transpose(1, 0, 2).reshape(n_query, n_head * dh),
        lw['att_o_w'], control), control)


def _swiglu(h, w1, w3, w2, control):
    import jax
    return _dot(jax.nn.silu(_dot(h, w1, control)) * _dot(h, w3, control), w2,
                control)


def _weights(gp, moe):
    """The picks' scores [..., k] -> their weights."""
    import jax.numpy as jnp
    return gp / (jnp.sum(gp, -1, keepdims=True)
                 + float(moe.get('norm_eps', 0.0))) * float(moe['scale'])


def _router(h, router_w, bias, moe, control):
    """(scores [T, n_routed], the bias the choice was made with, picks [T,
    k], weights [T, k], margins [T])."""
    import jax
    import jax.numpy as jnp
    g = jax.nn.sigmoid(h @ router_w.astype(jnp.float32))
    b = bias.astype(jnp.float32)
    if control == 'no_bias':
        b = jnp.zeros_like(b)
    picks, margin = _kimi().select(g, b, int(moe['top_k']))
    return g, b, picks, _weights(jnp.take_along_axis(g, picks, axis=-1),
                                 moe), margin


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
    'conv': ('att_norm', 'conv_in_w', 'conv_taps', 'conv_out_w'),
    'gqa': ('att_norm', 'att_q_w', 'att_k_w', 'att_v_w', 'att_o_w',
            'att_q_norm', 'att_k_norm')}


def last_logits(weights, model, context, control=None, picks_out=None,
                got=None, chunk=None):
    """float32 logits [vocab] at the last position of `context`.
    ``control`` (one of CONTROLS) makes the reference wrong in that one way
    (``chunk``: where 'tail_reset' zeroes, `CHUNK` unless given); ``got``
    are the logits this call's are compared with, which decide between the
    selections a near-tie at that position admits (the module's docstring);
    ``picks_out`` (a list) receives every expert layer's plain (picks [T,
    k], margins [T]) as numpy arrays."""
    import jax
    import jax.numpy as jnp
    if control is not None and control not in CONTROLS + READINGS:
        raise ValueError('control must be one of %s' % (CONTROLS + READINGS,))
    if got is None:
        got = _compared_logits()
    eps = float(model.get('rms_eps', 1e-6))
    moe, kinds, mixers = model['moe'], model['ffn'], model['mixer']
    top_k, held = int(moe['top_k']), int(moe['n_routed'])
    if int(moe['ranks']) != 1 or int(moe.get('n_shared', 1)):
        raise ValueError('this reference is of the whole expert layer '
                         'without a shared expert')
    mix = {'conv': jax.jit(functools.partial(
               _conv, eps=eps, control=control,
               chunk=int(chunk or CHUNK)), static_argnames=('n_query',)),
           'gqa': jax.jit(functools.partial(
               _attention, n_head=int(model['n_head']),
               n_kv_head=int(model['n_kv_head']),
               dh=int(model['head_dim']), theta=float(model['theta']),
               eps=eps, control=control), static_argnames=('n_query',))}
    norm = jax.jit(lambda x, s: _rms(x, s.astype(jnp.float32), eps))
    swiglu = jax.jit(functools.partial(_swiglu, control=control))
    router = jax.jit(functools.partial(_router, moe=moe, control=control))
    expert = jax.jit(functools.partial(_expert, control=control))
    stream = jax.jit(functools.partial(_stream, control=control))
    head = jax.jit(lambda x, w: _dot(x, w, control))

    def attend(i, x, n_query):
        p = 'layer_%d_' % i
        return mix[mixers[i]](
            x, {s: weights[p + s] for s in _MIXER_WEIGHTS[mixers[i]]},
            n_query=n_query)

    def feed_forward(i, x, chosen=None):
        """x [n, D] after layer i's mixer -> (x + its feed-forward, an
        expert layer's ((scores, choice bias), picks, margins)); ``chosen``
        are the LAST row's experts in place of its plain top-k."""
        p = 'layer_%d_' % i
        h = norm(x, weights[p + 'ffn_norm'])
        y = jnp.zeros_like(x)
        if kinds[i] == 'dense':
            w1, w3, w2 = (weights[p + 'ffn_fc%d_w' % n] for n in (1, 3, 2))
            for a in range(0, w1.shape[1], DENSE_BLOCK):
                b = a + DENSE_BLOCK
                y = y + swiglu(h, w1[:, a:b], w3[:, a:b], w2[a:b])
            return stream(x + y), None
        g, bias, picks, wts, margin = router(
            h, weights[p + 'moe_router_w'], weights[p + 'moe_router_bias'])
        routed = ((g, bias), picks, margin)
        if chosen is not None:
            chosen = jnp.asarray(chosen, picks.dtype)
            picks = picks.at[-1].set(chosen)
            wts = wts.at[-1].set(_weights(g[-1][chosen], moe))
        w1, w3, w2 = (weights[p + 'moe_fc%d_w' % n] for n in (1, 3, 2))
        for e in range(w1.shape[0]):            # every expert, one by one
            y = y + expert(h, w1[e], w3[e], w2[e], picks, wts, e)
        return stream(x + y), routed

    def logits_of(row):
        h = norm(row, weights['final_norm'])
        w = weights['lm_proj_w']
        return np.concatenate([
            np.asarray(head(h, w[:, a:a + HEAD_BLOCK]), np.float32)
            for a in range(0, w.shape[1], HEAD_BLOCK)], axis=-1)

    def apart(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def ties(routed):
        (g, b), _, _ = routed
        return _kimi().selections(g[-1], b, top_k, NEAR_TIE, 0, held)

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
