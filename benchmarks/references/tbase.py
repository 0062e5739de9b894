"""Plain reference for `tbase`: Transformer base of "Attention Is All You
Need" (arXiv:1706.03762) as models/transformer.py builds it, forward pass
and loss in jax.numpy, float32, matmul precision `highest`.  No kernels,
no AMP, no fused projections' tricks: the fused [d, 3d] weight is simply
split.  Departures of the program from the paper, followed here because
the reference checks the program: pre-norm residual blocks, a final
LayerNorm on each stack, sinusoids as [sin | cos] halves, embeddings not
tied, label smoothing 0.1 in closed form.

LOSS_RTOL, TRAINED_RTOL: the program runs under AMP (bf16 matmul
operands, f32 accumulation, f32 LayerNorm and softmax statistics, bf16
logits), the reference in f32.  The loss is a mean over the batch's
24,576 tokens of a log-sum-exp near ln(32000) = 10.37, and rounding
averages out: over 14 runs on the chip the first step's loss differed from
the reference's by 8e-8 to 1.0e-6 relative, and the loss of a step after
the window (parameters trained for ~400 steps, loss 9.7-9.8) by 1.1e-6 to
1.1e-5 (my chip runs, PR 23).  1e-4 is nine times the largest seen.  At
initialisation the loss is ln(vocab) plus half the logits' variance
whatever the layers below do, so the first comparison checks the
embedding-to-loss plumbing and little else; the trained one depends on
every layer, mask and projection, which is why it is made.  Neither can
tell f32 from bf16 matmuls: AMP is what the configuration states.
"""
import jax
import jax.numpy as jnp

LOSS_RTOL = 1e-4
TRAINED_RTOL = 1e-4
LN_EPS = 1e-5
NEG = -1e9


def _ln(x, w, b):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + LN_EPS) * w + b


def _heads(x, n_head):
    B, T, D = x.shape
    return x.reshape(B, T, n_head, D // n_head).transpose(0, 2, 1, 3)


def _attend(q, k, v, mask, n_head):
    q, k, v = _heads(q, n_head), _heads(k, n_head), _heads(v, n_head)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * (q.shape[-1] ** -0.5) + mask
    ctx = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)
    B, H, T, Dh = ctx.shape
    return ctx.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


def _embed(p, name, ids, d_model):
    x = p[name][ids[..., 0]] * (d_model ** 0.5)
    T, half = x.shape[1], d_model // 2
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    div = jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32) / half)
    return x + jnp.concatenate([jnp.sin(pos / div), jnp.cos(pos / div)], 1)


def _ffn(p, name, x):
    h = jax.nn.relu(x @ p[name + '_fc1_w'] + p[name + '_fc1_b'])
    return h @ p[name + '_fc2_w'] + p[name + '_fc2_b']


def _forward(p, feed, n_layer, n_head, d_model, eps):
    src_mask = (feed['src_pad'] * NEG)[:, None, None, :]
    T = feed['trg_pad'].shape[1]
    causal = jnp.triu(jnp.full((T, T), NEG, jnp.float32), k=1)[None, None]
    trg_mask = (feed['trg_pad'] * NEG)[:, None, None, :] + causal

    x = _embed(p, 'src_emb', feed['src_word'], d_model)
    for i in range(n_layer):
        n = 'enc_%d' % i
        h = _ln(x, p[n + '_att_ln_w'], p[n + '_att_ln_b'])
        q, k, v = jnp.split(h @ p[n + '_att_qkv_w'], 3, -1)
        x = x + _attend(q, k, v, src_mask, n_head) @ p[n + '_att_o_w']
        h = _ln(x, p[n + '_ffn_ln_w'], p[n + '_ffn_ln_b'])
        x = x + _ffn(p, n + '_ffn', h)
    enc = _ln(x, p['enc_post_ln_w'], p['enc_post_ln_b'])

    x = _embed(p, 'trg_emb', feed['trg_word'], d_model)
    for i in range(n_layer):
        n = 'dec_%d' % i
        h = _ln(x, p[n + '_satt_ln_w'], p[n + '_satt_ln_b'])
        q, k, v = jnp.split(h @ p[n + '_satt_qkv_w'], 3, -1)
        x = x + _attend(q, k, v, trg_mask, n_head) @ p[n + '_satt_o_w']
        h = _ln(x, p[n + '_xatt_ln_w'], p[n + '_xatt_ln_b'])
        k, v = jnp.split(enc @ p[n + '_xatt_kv_w'], 2, -1)
        x = x + _attend(h @ p[n + '_xatt_q_w'], k, v, src_mask,
                        n_head) @ p[n + '_xatt_o_w']
        h = _ln(x, p[n + '_ffn_ln_w'], p[n + '_ffn_ln_b'])
        x = x + _ffn(p, n + '_ffn', h)
    x = _ln(x, p['dec_post_ln_w'], p['dec_post_ln_b'])

    logits = x @ p['proj_w'] + p['proj_b']                 # [B, T, V]
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, feed['lbl_word'], -1)[..., 0]
    per_tok = lse - (1.0 - eps) * tgt - eps * jnp.mean(logits, -1)
    w = 1.0 - feed['trg_pad']
    return jnp.sum(per_tok * w), jnp.sum(w)


def loss(params, feed, config, rows=16):
    """Mean label-smoothed cross entropy per non-pad target token over the
    batch, computed `rows` sequences at a time (the f32 logits of a whole
    96 x 256 x 32000 batch would take 3 GB)."""
    fwd = jax.jit(_forward, static_argnums=(2, 3, 4, 5))
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    B = feed['src_pad'].shape[0]
    total = count = 0.0
    with jax.default_matmul_precision('highest'):
        for lo in range(0, B, rows):
            part = {k: jnp.asarray(v[lo:lo + rows]) for k, v in feed.items()}
            s, n = fwd(p, part, int(config['n_layer']), int(config['n_head']),
                       int(config['d_model']),
                       float(config['label_smooth_eps']))
            total += float(s)
            count += float(n)
    return total / count
