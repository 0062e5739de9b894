"""Plain reference for `tbase`: Transformer base of "Attention Is All You
Need" (arXiv:1706.03762) as models/transformer.py builds it, forward pass
and loss in jax.numpy, float32, matmul precision `highest`.  No kernels,
no AMP, no fused projections' tricks: the fused [d, 3d] weight is simply
split.  Departures of the program from the paper, followed here because
the reference checks the program: pre-norm residual blocks, a final
LayerNorm on each stack, sinusoids as [sin | cos] halves, embeddings not
tied, label smoothing 0.1 in closed form.

What is compared, and why (PERF.md, PR 26 finding 3).  The program runs
under AMP (bf16 matmul operands, f32 accumulation, f32 LayerNorm and
softmax statistics, bf16 logits), the reference in f32.  The MEAN loss
over a batch's 24,576 tokens (a chip) of a log-sum-exp near ln(32000) =
10.37 averages the rounding out, the program's and a lower precision's
alike: the float8 control (tests/control.py: this reference with every
matrix and table rounded to float8_e4m3, the precision below the one the
configuration states) read 9.2e-6 to 3.7e-5 on it, under LOSS_RTOL, and
so did every control run before (PR 26, first round).  So two VECTORS are
compared as well, both from the first step of the first launch: seeded
weights, the seed's first batch, a state that no run's speed changes.
`probes` gives the reference's side; the distance is |program -
reference| over |reference - its mean| for the losses and over
|reference| for the gradients, all probes as one vector.

* ITEM_TOL, every target token's own loss (the forward pass of every
  layer, mask and projection): sound runs 0.00501 to 0.00582 on 13 seeds
  of tbase.train_1chip; the control 0.0954, 0.0981, 0.1004, 0.1073 on
  four (my chip runs, PR 26).  Smallest control over largest sound: 16.4.
  The limit 0.02 is 3.4 times the sound runs' largest and a fifth of the
  control's smallest.
* GRAD_TOL, the gradient of the mean loss with respect to every
  LayerNorm scale (`probe_names`: the backward pass down to the first
  layer of each stack and, over a mesh, the all-reduce): sound runs
  0.01427 to 0.01702 on the same 13 seeds; the control 0.1011, 0.1097,
  0.1107, 0.1221.  Ratio 5.9.  The limit 0.04 is 2.35 times the sound
  runs' largest and 0.4 of the control's smallest.  (One probe alone
  reads up to 0.061 in a sound run and 0.073 in a control: single
  vectors of 512 numbers are not compared, their whole is.)
* The readings at tbase.train_dp4's own size are in PERF.md with these.
* LOSS_RTOL 1e-4 stays on the mean loss of that step (sound runs 0 to
  1.0e-6 over some sixty runs, PR 23 and PR 26): it checks the
  embedding-to-loss plumbing, and tells no precision from another.
* TRAINED_RTOL is None since PR 26: the mean loss of a step after the
  window is printed and not judged.  Its error grows with the steps the
  window trained (1.3e-5 at most after 400 steps, 1.5e-4 after 768 at
  24 sequences a step), so a limit that held float8 off (it read 3.9e-5
  to 3.8e-4 there) would refuse a sound program made twice as fast.
  What it was there for, a comparison that depends on every layer, the
  two vectors do on a fixed state.
Nothing here can tell f32 from bf16 matmuls: AMP is what the
configuration states.
"""
import jax
import jax.numpy as jnp
import numpy as np

LOSS_RTOL = 1e-4
TRAINED_RTOL = None
ITEM_TOL = 0.02
GRAD_TOL = 0.04
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


def _per_token(p, feed, n_layer, n_head, d_model, eps):
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
    return per_tok * w, w


def _forward(p, feed, n_layer, n_head, d_model, eps):
    per_tok, w = _per_token(p, feed, n_layer, n_head, d_model, eps)
    return jnp.sum(per_tok), jnp.sum(w)


def _probed(probe, rest, feed, n_layer, n_head, d_model, eps):
    per_tok, w = _per_token(dict(rest, **probe), feed, n_layer, n_head,
                            d_model, eps)
    return jnp.sum(per_tok), (per_tok, jnp.sum(w))


def _sizes(config):
    return (int(config['n_layer']), int(config['n_head']),
            int(config['d_model']), float(config['label_smooth_eps']))


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
            s, n = fwd(p, part, *_sizes(config))
            total += float(s)
            count += float(n)
    return total / count


def probe_names(config):
    """The parameters whose gradient is compared: the scale of every
    LayerNorm, two or three to a layer and one after each stack.  Each is
    a vector of d_model numbers that the whole backward pass above it
    (and, over a mesh, the all-reduce) has to get right, and costs the
    program a fetch of 2 KB a step."""
    n = int(config['n_layer'])
    return (['enc_%d_%s_ln_w' % (i, k) for i in range(n)
             for k in ('att', 'ffn')] + ['enc_post_ln_w']
            + ['dec_%d_%s_ln_w' % (i, k) for i in range(n)
               for k in ('satt', 'xatt', 'ffn')] + ['dec_post_ln_w'])


def probes(params, feed, config, rows=16):
    """What the comparison reads beside the mean loss, for one batch:
    `per_item`, every target token's own loss [B, T] (0 where padded),
    and `grads`, the gradient of the MEAN loss with respect to each of
    `probe_names`; `loss` is the mean itself."""
    names = probe_names(config)
    step = jax.jit(jax.value_and_grad(_probed, has_aux=True),
                   static_argnums=(3, 4, 5, 6))
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    probe = {k: p.pop(k) for k in names}
    B = feed['src_pad'].shape[0]
    total = count = 0.0
    grads, items = None, []
    with jax.default_matmul_precision('highest'):
        for lo in range(0, B, rows):
            part = {k: jnp.asarray(v[lo:lo + rows]) for k, v in feed.items()}
            (s, (per_tok, n)), g = step(probe, p, part, *_sizes(config))
            total += float(s)
            count += float(n)
            items.append(np.asarray(per_tok))
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    return {'loss': total / count,
            'per_item': np.concatenate(items),
            'grads': {k: np.asarray(v) / count for k, v in grads.items()}}
