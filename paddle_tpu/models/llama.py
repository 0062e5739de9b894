"""LLaMA-family LLM built on the fluid layers API.

BASELINE stretch target (SURVEY §2.6): a modern decoder-only LLM expressed
in the same declarative Program/layers API as the fluid-era models, showing
the framework carries current model families, not just 2019-era ones.
Architecture: RMSNorm pre-norm, rotary position embeddings, grouped-query
attention, SwiGLU FFN, no biases — LLaMA-3 layout.

TPU-first mapping:
  * attention runs `layers.ring_attention`: flash-attention pallas kernel on
    one chip, exact ppermute ring over the mesh's 'seq' axis for
    long-context (the SAME program serves both — the op picks its strategy
    from the executor mesh at lowering time)
  * parameter names follow parallel/tp.py's Megatron layout rules, so
    `shard_program_tp(main)` gives column/row-parallel attention + FFN and
    a vocab-sharded embedding over the 'model' axis
  * the whole train step (fwd + vjp bwd + Adam) lowers to ONE XLA
    executable; bf16 via build(dtype='bfloat16') keeps matmuls on the MXU
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import Normal
from paddle_tpu.param_attr import ParamAttr

# rough LLaMA-3-8B shape plus scaled-down variants for bench/tests
CONFIGS = {
    'llama3_8b': dict(vocab=128256, d_model=4096, n_layer=32, n_head=32,
                      n_kv_head=8, d_ffn=14336, theta=500000.0,
                      max_len=8192),
    'llama_1b': dict(vocab=32000, d_model=2048, n_layer=16, n_head=16,
                     n_kv_head=8, d_ffn=5504, theta=500000.0, max_len=2048),
    'tiny': dict(vocab=256, d_model=64, n_layer=2, n_head=4, n_kv_head=2,
                 d_ffn=128, theta=10000.0, max_len=32),
}


def _linear(x, size, name):
    # all llama projections are bias-free; names end in _w so the tp.py
    # Megatron rules shard them (q/k/v/fc1/fc3 column, o/fc2 row)
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=ParamAttr(name=name + '_w'), bias_attr=False)


def _split_heads(x, n_head, max_len, d_head):
    x = layers.reshape(x, [0, max_len, n_head, d_head])
    return layers.transpose(x, perm=[0, 2, 1, 3])        # [B, H, T, Dh]


def attention(x, cfg, name, use_ring=False):
    d_model, H = cfg['d_model'], cfg['n_head']
    Hkv, T = cfg['n_kv_head'], cfg['max_len']
    d_head = d_model // H
    q = _linear(x, H * d_head, name + '_q')
    k = _linear(x, Hkv * d_head, name + '_k')
    v = _linear(x, Hkv * d_head, name + '_v')
    q = _split_heads(q, H, T, d_head)
    k = _split_heads(k, Hkv, T, d_head)
    v = _split_heads(v, Hkv, T, d_head)
    q = layers.rope(q, theta=cfg['theta'])
    k = layers.rope(k, theta=cfg['theta'])
    # K/V stay at Hkv width: both attention paths serve GQA natively, so
    # HBM and ring-hop ICI traffic keep the grouped-head savings
    if use_ring:
        ctxv = layers.ring_attention(q, k, v, causal=True)
    else:
        ctxv = layers.flash_attention(q, k, v, causal=True)
    ctxv = layers.transpose(ctxv, perm=[0, 2, 1, 3])
    ctxv = layers.reshape(ctxv, [0, T, d_model])
    return _linear(ctxv, d_model, name + '_o')


def swiglu_ffn(x, cfg, name):
    gate = _linear(x, cfg['d_ffn'], name + '_fc1')      # column-parallel
    up = _linear(x, cfg['d_ffn'], name + '_fc3')        # column-parallel
    h = layers.elementwise_mul(layers.swish(gate, beta=1.0), up)
    return _linear(h, cfg['d_model'], name + '_fc2')    # row-parallel


def decoder_layer(x, cfg, name, use_ring=False):
    h = layers.rms_norm(x, param_attr=ParamAttr(name=name + '_att_norm'))
    x = layers.elementwise_add(x, attention(h, cfg, name + '_att',
                                            use_ring))
    h = layers.rms_norm(x, param_attr=ParamAttr(name=name + '_ffn_norm'))
    return layers.elementwise_add(x, swiglu_ffn(h, cfg, name + '_ffn'))


def llama(config='tiny', use_ring=False, dtype='float32', **overrides):
    """Build the forward + loss.  Feeds: tokens [B, T, 1] int64 (inputs),
    labels [B, T, 1] int64 (shifted targets), loss_mask [B, T] float32."""
    cfg = dict(CONFIGS[config] if isinstance(config, str) else config)
    cfg.update(overrides)
    T, V, D = cfg['max_len'], cfg['vocab'], cfg['d_model']

    tokens = layers.data('tokens', shape=[T, 1], dtype='int64')
    labels = layers.data('labels', shape=[T, 1], dtype='int64')
    loss_mask = layers.data('loss_mask', shape=[T], dtype='float32')

    x = layers.embedding(
        tokens, size=[V, D],
        param_attr=ParamAttr(name='tok_emb',
                             initializer=Normal(0., 0.02)),
        dtype=dtype)
    for i in range(cfg['n_layer']):
        x = decoder_layer(x, cfg, 'layer_%d' % i, use_ring)
    x = layers.rms_norm(x, param_attr=ParamAttr(name='final_norm'))
    logits = _linear(x, V, 'lm_proj')                    # [B, T, V]
    if dtype != 'float32':
        logits = layers.cast(logits, 'float32')

    per_tok = layers.softmax_with_cross_entropy(logits, labels)  # [B,T,1]
    per_tok = layers.elementwise_mul(
        layers.squeeze(per_tok, axes=[2]), loss_mask)
    sum_cost = layers.reduce_sum(per_tok)
    token_num = layers.reduce_sum(loss_mask)
    loss = layers.elementwise_div(sum_cost, token_num)
    return {'loss': loss, 'logits': logits, 'sum_cost': sum_cost,
            'token_num': token_num,
            'feeds': [tokens, labels, loss_mask], 'config': cfg}


def build(config='tiny', use_ring=False, dtype='float32', lr=3e-4,
          grad_clip=1.0, is_train=True, **overrides):
    out = llama(config, use_ring, dtype, **overrides)
    opt = None
    if is_train:
        if grad_clip:
            fluid.set_gradient_clip(
                fluid.clip.GradientClipByGlobalNorm(grad_clip))
        opt = fluid.optimizer.Adam(learning_rate=lr, beta1=0.9, beta2=0.95,
                                   epsilon=1e-8)
        opt.minimize(out['loss'])
    out['optimizer'] = opt
    return out


def shard(main_program):
    """Apply Megatron TP layout + extra rules for the SwiGLU third matrix
    and the llama norms (replicated)."""
    import re
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel.tp import shard_program_tp
    extra = [
        (re.compile(r'.*_fc3_w$'), lambda nd: P(None, 'model')),
        (re.compile(r'.*tok_emb$'), lambda nd: P('model', None)),
    ]
    return shard_program_tp(main_program, extra_rules=extra)


def make_batch(token_rows, max_len):
    """Pack next-token-prediction batches from rows of token ids."""
    B = len(token_rows)
    toks = np.zeros((B, max_len, 1), 'int64')
    lbls = np.zeros((B, max_len, 1), 'int64')
    mask = np.zeros((B, max_len), 'float32')
    for i, row in enumerate(token_rows):
        row = np.asarray(row)[:max_len + 1]
        n = len(row) - 1
        toks[i, :n, 0] = row[:-1]
        lbls[i, :n, 0] = row[1:]
        mask[i, :n] = 1.0
    return {'tokens': toks, 'labels': lbls, 'loss_mask': mask}


# ----------------------------------------- serving programs (zoo/lint)

def generation_program(config='tiny', mode='decode', temperature=0.0,
                       top_k=0, kv_slots=4, **overrides):
    """The serving-side llama paths as declarative Programs, so the
    static analyzer covers what serving/generation/ actually runs:

      * mode='prefill': full-window forward, fetch [B, T, V] logits —
        the shape of DecodeRuntime's prompt pass
      * mode='decode': forward + last-position slice + `sample_tokens`,
        fetch [B] next token ids — one decode step (the op's
        `(seed, position)` stream keeps replay deterministic)

    decode mode also declares the slotted KV pool on the program
    (`set_kv_plan`, CacheConfig arithmetic) so the memplan pass folds
    the cache bytes a real serving deployment would pin into its
    per-device footprint.  Weights use the training parameter names —
    a trained scope serves directly.
    """
    cfg = dict(CONFIGS[config] if isinstance(config, str) else config)
    cfg.update(overrides)
    T, V, D = cfg['max_len'], cfg['vocab'], cfg['d_model']

    tokens = layers.data('tokens', shape=[T, 1], dtype='int64')
    x = layers.embedding(
        tokens, size=[V, D],
        param_attr=ParamAttr(name='tok_emb',
                             initializer=Normal(0., 0.02)))
    for i in range(cfg['n_layer']):
        x = decoder_layer(x, cfg, 'layer_%d' % i)
    x = layers.rms_norm(x, param_attr=ParamAttr(name='final_norm'))
    logits = _linear(x, V, 'lm_proj')                    # [B, T, V]
    out = {'logits': logits, 'feeds': [tokens], 'config': cfg,
           'fetches': [logits]}
    if mode == 'decode':
        last = layers.slice(logits, axes=[1], starts=[T - 1], ends=[T])
        last = layers.squeeze(last, axes=[1])            # [B, V]
        nxt = layers.sample_tokens(last, temperature=temperature,
                                   top_k=top_k)
        out['next_token'] = nxt
        out['fetches'] = [nxt]
        tokens.block.program.set_kv_plan(
            slots=kv_slots, layers=cfg['n_layer'],
            kv_heads=cfg['n_kv_head'], max_len=T,
            head_dim=D // cfg['n_head'])
    return out


# ------------------------------------------------- streaming generation

def generation_weights(scope, config='tiny', **overrides):
    """Pull the decode-side weight dict (host arrays, llama parameter
    names) a trained llama program left in `scope` — the input format of
    serving.generation.DecodeRuntime."""
    from paddle_tpu.serving.generation.decode import weight_names
    cfg = dict(CONFIGS[config] if isinstance(config, str) else config)
    cfg.update(overrides)
    return {n: np.asarray(scope.vars[n]) for n in weight_names(cfg)}


def make_streaming_runtime(scope, config='tiny', slots=4, prefill_chunk=8,
                           mesh=None, **overrides):
    """Build a serving.generation.DecodeRuntime over a trained scope:
    the decode path beside the Program (same weights; a slotted
    multi-request KV cache, fused K-token decode windows, and
    chunked/ring prefill — the device half of GenerationEngine).

        rt = llama.make_streaming_runtime(scope, 'tiny', slots=8)
        engine = GenerationEngine(rt).start()
    """
    from paddle_tpu.serving.generation.decode import DecodeRuntime
    cfg = dict(CONFIGS[config] if isinstance(config, str) else config)
    cfg.update(overrides)
    return DecodeRuntime(generation_weights(scope, cfg), cfg, slots=slots,
                         prefill_chunk=prefill_chunk, mesh=mesh)
