"""Transformer for NMT (parity: reference benchmark transformer /
machine_translation model family; fluid transformer config in
benchmark/fluid/models/machine_translation.py's role).

TPU-first: fixed max_len padded batches + boolean masks (no LoD walk),
pre-norm residual blocks, attention as batched MXU matmuls; the scaled-dot
product can route through the pallas flash-attention kernel
(ops/attention.py) with use_flash=True.
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.initializer import Normal


def _linear(x, size, name, bias=True, amp_keep_bf16=False, init=None):
    # Xavier init (the fluid fc default): keeps attention logits at O(1)
    # scale so gradients reach the encoder from step 0
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=ParamAttr(name=name + '_w',
                                          initializer=init),
                     bias_attr=ParamAttr(name=name + '_b') if bias else False,
                     amp_keep_bf16=amp_keep_bf16)


def multi_head_attention(q_in, kv_in, mask, d_model, n_head, dropout,
                         is_train, name, use_flash=False, causal=False,
                         kv_lengths=None):
    """mask: [B, 1, Tq, Tk] additive (-1e9 on invalid); kv_lengths int [B]
    (used by the flash path, where pad is a suffix)."""
    d_head = d_model // n_head
    # fused projections: self-attention projects q, k, v as ONE d x 3d
    # product (cross-attention k, v as d x 2d) and splits the result, the
    # reference's layout of the weights.  What lowers depends on the
    # attention's route (ops/attention.py): on the whole-batch and Pallas
    # routes the one GEMM and the split, as written; where the attention
    # tiles the batch (tbase.train_1chip: 96 x 256 tokens) the rewriter
    # (core/passes/attn_layout.py) lets the op slice the WEIGHT and write
    # each operand in the tile loop's layout, since the split and the
    # copies into that layout cost 5.7 ms of a 103.4 ms step while the
    # [512,512] products cost what the fused ones did (0.166 s against
    # 0.160 s over 32 traced steps; PERF.md section 6, PR 50, chip
    # runs).  Separate q/k/v fc's would lower the same there and read
    # the activations three times elsewhere.
    # AMP: the block interior casts back to f32 after each product
    # (core/executor.py _AMP_CAST_OPS); only the logits projection keeps
    # bf16 (amp_keep_bf16).  The numbers that chose this (bf16 interior
    # -0.5 %) are from an installation that no longer exists (PERF.md
    # section 6, "PRs 1-20: not verified"); no cell has re-read them.
    # the fused [d, 3d] weight pins Xavier fans to the SEPARATE
    # projections' (d, d) so each q/k/v slice keeps the exact init
    # distribution of three unfused fc's (fan_out would otherwise
    # triple and shrink the init std ~1.4x)
    from paddle_tpu.initializer import Xavier
    per_proj = Xavier(fan_in=d_model, fan_out=d_model)
    if q_in is kv_in:
        qkv = _linear(q_in, 3 * d_model, name + '_qkv', bias=False,
                      init=per_proj)
        q, k, v = layers.split(qkv, 3, dim=-1)
    else:
        q = _linear(q_in, d_model, name + '_q', bias=False)
        kv = _linear(kv_in, 2 * d_model, name + '_kv', bias=False,
                     init=per_proj)
        k, v = layers.split(kv, 2, dim=-1)

    def split_heads(x):
        x = layers.reshape(x, [0, 0, n_head, d_head])
        return layers.transpose(x, perm=[0, 2, 1, 3])  # [B, H, T, Dh]

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    # the fused kernel has no attention-weight dropout: use it only when
    # dropout is off (inference / LLM-style training); else compose ops
    if use_flash and dropout and is_train:
        use_flash = False
    if use_flash:
        if mask is not None and kv_lengths is None:
            raise ValueError(
                'use_flash with a padding mask requires kv_lengths '
                '(suffix-padding lengths); got None')
        ctx = layers.flash_attention(q, k, v, causal=causal,
                                     k_lengths=kv_lengths)
    else:
        q = layers.scale(q, scale=d_head ** -0.5)
        scores = layers.matmul(q, k, transpose_y=True)  # [B, H, Tq, Tk]
        if mask is not None:
            scores = layers.elementwise_add(scores, mask)
        weights = layers.softmax(scores)
        if dropout and is_train:
            weights = layers.dropout(
                weights, dropout, is_test=not is_train,
                dropout_implementation='upscale_in_train')
        ctx = layers.matmul(weights, v)  # [B, H, Tq, Dh]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, [0, 0, d_model])
    return _linear(ctx, d_model, name + '_o', bias=False)


def ffn(x, d_model, d_inner, dropout, is_train, name):
    h = _linear(x, d_inner, name + '_fc1')
    h = layers.relu(h)
    if dropout and is_train:
        h = layers.dropout(h, dropout, is_test=not is_train,
                           dropout_implementation='upscale_in_train')
    return _linear(h, d_model, name + '_fc2')


def _prenorm(x, sub, name):
    ln = layers.layer_norm(x, begin_norm_axis=2,
                           param_attr=ParamAttr(name=name + '_ln_w'),
                           bias_attr=ParamAttr(name=name + '_ln_b'))
    return layers.elementwise_add(x, sub(ln))


def encoder_layer(x, mask, cfg, is_train, name, lengths=None):
    x = _prenorm(x, lambda h: multi_head_attention(
        h, h, mask, cfg['d_model'], cfg['n_head'], cfg['dropout'], is_train,
        name + '_att', cfg.get('use_flash', False),
        kv_lengths=lengths), name + '_att')
    x = _prenorm(x, lambda h: ffn(
        h, cfg['d_model'], cfg['d_inner'], cfg['dropout'], is_train,
        name + '_ffn'), name + '_ffn')
    return x


def decoder_layer(x, enc, self_mask, cross_mask, cfg, is_train, name,
                  src_lengths=None, trg_lengths=None):
    x = _prenorm(x, lambda h: multi_head_attention(
        h, h, self_mask, cfg['d_model'], cfg['n_head'], cfg['dropout'],
        is_train, name + '_satt', cfg.get('use_flash', False), causal=True,
        kv_lengths=trg_lengths), name + '_satt')
    x = _prenorm(x, lambda h: multi_head_attention(
        h, enc, cross_mask, cfg['d_model'], cfg['n_head'], cfg['dropout'],
        is_train, name + '_xatt', cfg.get('use_flash', False),
        kv_lengths=src_lengths), name + '_xatt')
    x = _prenorm(x, lambda h: ffn(
        h, cfg['d_model'], cfg['d_inner'], cfg['dropout'], is_train,
        name + '_ffn'), name + '_ffn')
    return x


def _embed(ids, vocab, d_model, max_len, dropout, is_train, name):
    emb = layers.embedding(
        ids, size=[vocab, d_model],
        param_attr=ParamAttr(name=name + '_emb',
                             initializer=Normal(0., d_model ** -0.5)))
    emb = layers.scale(emb, scale=d_model ** 0.5)
    emb = layers.add_position_encoding(emb, alpha=1.0, beta=1.0)
    if dropout and is_train:
        emb = layers.dropout(emb, dropout, is_test=not is_train,
                             dropout_implementation='upscale_in_train')
    return emb


def _pad_mask(pad_flags, neg=-1e9):
    """pad_flags: [B, T] float 1.0 where PAD.  -> [B, 1, 1, T] additive."""
    m = layers.scale(pad_flags, scale=neg)
    m = layers.unsqueeze(m, axes=[1, 2])
    return m


def _causal_mask_const(max_len):
    tri = np.triu(np.full((max_len, max_len), -1e9, 'float32'), k=1)
    return tri.reshape(1, 1, max_len, max_len)


def transformer(src_vocab, trg_vocab, max_len=64, n_layer=6, n_head=8,
                d_model=512, d_inner=2048, dropout=0.1, is_train=True,
                use_flash=False, label_smooth_eps=0.1):
    """Returns dict with loss/feeds/fetches.  Feeds (all dense, [B, T]):
    src_word, trg_word (shifted-in), lbl_word (shifted-out), plus float
    pad masks src_pad [B, T], trg_pad [B, T]."""
    cfg = {'d_model': d_model, 'n_head': n_head, 'd_inner': d_inner,
           'dropout': dropout, 'use_flash': use_flash}
    src = layers.data('src_word', shape=[max_len, 1], dtype='int64')
    trg = layers.data('trg_word', shape=[max_len, 1], dtype='int64')
    lbl = layers.data('lbl_word', shape=[max_len, 1], dtype='int64')
    src_pad = layers.data('src_pad', shape=[max_len], dtype='float32')
    trg_pad = layers.data('trg_pad', shape=[max_len], dtype='float32')

    src_mask = _pad_mask(src_pad)                       # [B,1,1,Ts]
    cross_mask = src_mask
    ones = layers.fill_constant_batch_size_like(src_pad, [-1, max_len],
                                                'float32', 1.0)
    src_len = layers.cast(layers.reduce_sum(
        layers.elementwise_sub(ones, src_pad), dim=1), 'int32')
    trg_len = layers.cast(layers.reduce_sum(
        layers.elementwise_sub(ones, trg_pad), dim=1), 'int32')
    causal = layers.assign(_causal_mask_const(max_len))  # [1,1,Tt,Tt]
    trg_mask = layers.elementwise_add(_pad_mask(trg_pad), causal)

    enc = _embed(src, src_vocab, d_model, max_len, dropout, is_train,
                 'src')
    for i in range(n_layer):
        enc = encoder_layer(enc, src_mask, cfg, is_train, 'enc_%d' % i,
                            lengths=src_len)
    enc = layers.layer_norm(enc, begin_norm_axis=2,
                            param_attr=ParamAttr(name='enc_post_ln_w'),
                            bias_attr=ParamAttr(name='enc_post_ln_b'))

    dec = _embed(trg, trg_vocab, d_model, max_len, dropout, is_train,
                 'trg')
    for i in range(n_layer):
        dec = decoder_layer(dec, enc, trg_mask, cross_mask, cfg, is_train,
                            'dec_%d' % i, src_lengths=src_len,
                            trg_lengths=trg_len)
    dec = layers.layer_norm(dec, begin_norm_axis=2,
                            param_attr=ParamAttr(name='dec_post_ln_w'),
                            bias_attr=ParamAttr(name='dec_post_ln_b'))

    # the [B, T, V] logits stay bf16 under AMP: their only consumer is
    # the CE, whose reductions are internally f32, and the backward then
    # carries a bf16 dlogits into the two big vocab GEMMs — this buffer
    # is the largest in the model and was measured f32 in the per-HLO
    # ledger (PERF.md r5)
    logits = _linear(dec, trg_vocab, 'proj',            # [B, T, V]
                     amp_keep_bf16=True)
    # fused label smoothing: the one_hot -> label_smooth -> soft-CE chain
    # would materialize two [B, T, V] f32 buffers (>1 GB at bench shapes);
    # the closed form needs only reductions over V
    per_tok = layers.softmax_with_cross_entropy(
        logits, lbl, label_smooth_eps=label_smooth_eps)
    # mask out PAD target positions: weight = 1 - trg_pad
    w = layers.elementwise_sub(
        layers.fill_constant_batch_size_like(trg_pad, [-1, max_len],
                                             'float32', 1.0), trg_pad)
    per_tok = layers.elementwise_mul(layers.squeeze(per_tok, axes=[2]), w)
    sum_cost = layers.reduce_sum(per_tok)
    token_num = layers.reduce_sum(w)
    avg_cost = layers.elementwise_div(sum_cost, token_num)
    return {'loss': avg_cost, 'sum_cost': sum_cost, 'token_num': token_num,
            'feeds': [src, trg, lbl, src_pad, trg_pad], 'logits': logits}


def build(src_vocab=10000, trg_vocab=10000, max_len=64, n_layer=6, n_head=8,
          d_model=512, d_inner=2048, dropout=0.1, lr=2.0,
          warmup_steps=8000, is_train=True, use_flash=False):
    out = transformer(src_vocab, trg_vocab, max_len, n_layer, n_head,
                      d_model, d_inner, dropout, is_train, use_flash)
    opt = None
    if is_train:
        lr_var = layers.noam_decay(d_model, warmup_steps)
        lr_var = layers.scale(lr_var, scale=float(lr))
        opt = fluid.optimizer.Adam(learning_rate=lr_var, beta1=0.9,
                                   beta2=0.997, epsilon=1e-9)
        opt.minimize(out['loss'])
    out['optimizer'] = opt
    return out


def synthetic_batch(rng, batch_size, max_len, vocab=32000):
    """Full-length synthetic (src, trg_in, trg_out) feeds (chip_smoke.py;
    benchmarks/lib/traffic.py vectorises the same contract)."""
    rows = []
    for _ in range(batch_size):
        s = rng.randint(3, vocab, (max_len - 1,))
        rows.append((np.concatenate([s, [1]]), np.concatenate([[0], s]),
                     np.concatenate([s, [1]])))
    return make_batch(rows, max_len)


def make_batch(reader_batch, max_len, rng=None):
    """Convert wmt16-style (src, trg_in, trg_out) rows into dense feeds."""
    B = len(reader_batch)
    src = np.zeros((B, max_len, 1), 'int64')
    trg = np.zeros((B, max_len, 1), 'int64')
    lbl = np.zeros((B, max_len, 1), 'int64')
    src_pad = np.ones((B, max_len), 'float32')
    trg_pad = np.ones((B, max_len), 'float32')
    for i, (s, t, l) in enumerate(reader_batch):
        s = s[:max_len]
        t = t[:max_len]
        l = l[:max_len]
        src[i, :len(s), 0] = s
        trg[i, :len(t), 0] = t
        lbl[i, :len(l), 0] = l
        src_pad[i, :len(s)] = 0.0
        trg_pad[i, :len(t)] = 0.0
    return {'src_word': src, 'trg_word': trg, 'lbl_word': lbl,
            'src_pad': src_pad, 'trg_pad': trg_pad}
