"""Plain reference for `falconh1_34b`: the Falcon-H1 block as a full causal
forward pass over the whole context in jax.numpy: float32, matmul
precision `highest`, no cache, no pages, no chunks, no batching, and the
Mamba-2 recurrence as a SEQUENTIAL scan over time, one position after
another (the program runs it in blocks of 128 for prefill and step by step
in a decode window: two other paths).

The equations, from the published config (every number from it):

    x0     = tok_emb[ids] * embedding_multiplier
    block:   h = RMSNorm(x)                       eps 1e-5, no biases
             x = x + Attn(h * attention_in_multiplier) * attention_out_multiplier
                   + Mixer(h) * ssm_out_multiplier       both read the SAME h
             x = x + MLP(RMSNorm(x))
    Attn:    20 query and 4 key/value heads of 128; k = (h Wk) * key_multiplier;
             RoPE at theta 1e11; causal softmax at 1/sqrt(128); Wo [2560, 5120]
    MLP:     down(silu(gate(x) * mlp_multipliers[0]) * up(x)) * mlp_multipliers[1]
    Mixer:   p = ((h * ssm_in_multiplier) W_in) * m, m piecewise constant from
             ssm_multipliers over the parts z 4096, x 4096, B 2x256, C 2x256, dt 32;
             (x, B, C) = silu(causal depthwise conv1d, 4 taps, with bias);
             per head j of 32, group g = j // 16:
               dt_t = softplus(dt_t + dt_bias_j),  A_j = -exp(A_log_j)
               S_t  = exp(dt_t A_j) S_{t-1} + dt_t x_t (x) B_t[g]      S [128, 256]
               y_t  = S_t C_t[g] + D_j x_t
             y = w * RMSNorm_per_group(y * silu(z)) over 2 groups of 2048
             (mamba_rms_norm, mamba_norm_before_gate false); out = y W_out
    logits = (RMSNorm(x) @ lm_head) * lm_head_multiplier

Departures from the published model, each the program's too:
  * RoPE rotates interleaved pairs (x0,x1), (x2,x3), ... as the program's
    `_rope_at` does, where transformers rotates the two halves of a head:
    a fixed permutation of the columns of Wq and Wk, which random weights
    cannot tell apart.
  * transformers' FalconH1 folds `ssm_multipliers` and `ssm_in_multiplier`
    into one vector applied after the projection; here the input is scaled
    first and the parts after, as ISSUE 32 writes it: the same product.
  * The weights are the runner's draw (runners/serve.py:make_weights):
    every array normal at `initializer_range` 0.1 (the configuration's
    `assumed`), `A_log`, `dt_bias`, `D` and the convolution's bias too;
    only names ending in `norm` are ones, so the gated norm's scale is
    named `ssm_gate_norm`.  That draw makes A_j = -exp(N(0, 0.1)), about -1
    (-0.8 to -1.25), and dt = softplus(N(0, 0.64)), about 0.4 to 1.1 with a
    median of 0.7: the state decays by about exp(-0.7) = 0.5 a position
    and forgets within a few tokens, where the published model's heads
    remember for hundreds.  On the chip the comparison therefore sees the
    LATEST hand-off of the state alone (decode window -> chunk);
    tests/test_generation_ssm.py draws slow decay (dt A about -0.01) and
    sees every hand-off.

The weights are the runtime's own bfloat16 arrays, widened to float32 one
layer at a time, and the output head in blocks of the vocabulary (1.34 B
entries in f32 would not fit beside the pool and the state).

LOGIT_RTOL bounds ||got - want|| / ||want|| over the vocabulary (the 2-norm).
It stands between the readings below (my chip runs, PR 32; PERF.md, Findings
of PR 32), 4.3 times the largest of the sound runs and 4.9 times under the
smallest of the controls:
  * sound runs, 0.0073 to 0.0092 over 52 prompts of thirteen runs of the cell
    and its comparison: the program computes in bfloat16 end to end
    (activations, residual stream, cache rows; the scan state and the
    mixer's arithmetic in f32), the reference in f32 from the same bf16
    weights, over six blocks whose residual additions the multipliers damp;
  * this reference on weights rounded to float8_e4m3 (the nearest precision
    below the configuration's bf16; `lax.reduce_precision`, 8 contexts over
    two seeds) against itself on the bf16 weights: 0.195 to 0.216;
  * the control ISSUE 32 asks for: the same comparison with the program's
    scan state zeroed before the comparison's last one-token chunk (a
    scratch wrapper around `DecodeRuntime.prefill`, not a switch of the
    program), 8 prompts over two seeds, each NOT correct: 0.295 to 0.610
    (the convolution's tail zeroed instead: 0.611 to 0.973).
"""
import functools

import numpy as np

LOGIT_RTOL = 0.04
HEAD_BLOCK = 32768          # columns of the output head widened at a time


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [H, T, dh]: rotate interleaved pairs by position * theta^(-2i/dh)."""
    import jax.numpy as jnp
    dh, T = x.shape[-1], x.shape[-2]
    freqs = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(h, lw, n_head, n_kv_head, dh, theta, mu):
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    h = h * mu['attention_in']
    q = (h @ lw['att_q_w']).reshape(T, n_head, dh).transpose(1, 0, 2)
    k = ((h @ lw['att_k_w']) * mu['key']).reshape(T, n_kv_head, dh) \
        .transpose(1, 0, 2)
    v = (h @ lw['att_v_w']).reshape(T, n_kv_head, dh).transpose(1, 0, 2)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_head // n_kv_head, axis=0)
    v = jnp.repeat(v, n_head // n_kv_head, axis=0)
    s = jnp.einsum('hqd,hkd->hqk', q, k) * (dh ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    att = jnp.einsum('hqk,hkd->hqd', jax.nn.softmax(s, -1), v)
    return att.transpose(1, 0, 2).reshape(T, n_head * dh) @ lw['att_o_w']


def _mixer(h, lw, ssm, mu, eps):
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    d, H, G = ssm['d_ssm'], ssm['n_heads'], ssm['n_groups']
    N, K = ssm['d_state'], ssm['d_conv']
    P = d // H
    sizes = (d, d, G * N, G * N, H)
    m = np.repeat(np.asarray(mu['ssm'], np.float32), sizes)
    p = ((h * mu['ssm_in']) @ lw['ssm_in_w']) * m
    z, xbc, dt = p[:, :d], p[:, d:2 * d + 2 * G * N], p[:, 2 * d + 2 * G * N:]
    # causal depthwise convolution: tap k weighs the input K - 1 - k back
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = lw['ssm_conv_b'] + sum(padded[k:k + T] * lw['ssm_conv_w'][k]
                                  for k in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d].reshape(T, H, P)
    B = jnp.repeat(xbc[:, d:d + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, d + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lw['ssm_dt_bias'])                  # [T, H]
    A = -jnp.exp(lw['ssm_A_log'])                                 # [H]

    def step(S, t):
        x_t, B_t, C_t, dt_t = t                    # [H,P] [H,N] [H,N] [H]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, B, C, dt))
    y = (y + lw['ssm_D'][:, None] * x).reshape(T, d)
    gated = (y * jax.nn.silu(z)).reshape(T, G, d // G)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return (normed.reshape(T, d) * lw['ssm_gate_norm']) @ lw['ssm_out_w']


def _layer(x, lw, n_head, n_kv_head, dh, theta, eps, ssm, mu):
    import jax
    import jax.numpy as jnp
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    h = _rms(x, lw['att_norm'], eps)
    x = x + _attention(h, lw, n_head, n_kv_head, dh, theta, mu) \
        * mu['attention_out'] + _mixer(h, lw, ssm, mu, eps) * mu['ssm_out']
    h = _rms(x, lw['ffn_norm'], eps)
    gate = jax.nn.silu((h @ lw['ffn_fc1_w']) * mu['mlp_gate'])
    return x + ((gate * (h @ lw['ffn_fc3_w'])) @ lw['ffn_fc2_w']) \
        * mu['mlp_down']


def last_logits(weights, model, context):
    """float32 logits [vocab] at the last position of `context`."""
    import jax
    import jax.numpy as jnp
    mu, eps = model['multipliers'], float(model['rms_eps'])
    ssm = {k: int(v) for k, v in model['ssm'].items()}
    layer = jax.jit(functools.partial(
        _layer, n_head=int(model['n_head']), n_kv_head=int(model['n_kv_head']),
        dh=int(model['head_dim']), theta=float(model['theta']), eps=eps,
        ssm=ssm, mu=mu))
    head = jax.jit(lambda x, w: x @ w.astype(jnp.float32))
    with jax.default_matmul_precision('highest'):
        x = weights['tok_emb'][jnp.asarray(context, jnp.int32)] \
            .astype(jnp.float32) * mu['embedding']
        for i in range(int(model['n_layer'])):
            p = 'layer_%d_' % i
            x = layer(x, {k[len(p):]: v for k, v in weights.items()
                          if k.startswith(p)})
        last = _rms(x[-1], weights['final_norm'].astype(jnp.float32), eps)
        w = weights['lm_proj_w']
        logits = [np.asarray(head(last, w[:, a:a + HEAD_BLOCK]), np.float32)
                  for a in range(0, w.shape[1], HEAD_BLOCK)]
    return np.concatenate(logits) * np.float32(mu['lm_head'])
