"""How the program is asked for LFM2-8B-A1B as one pipeline stage holds its
first 16 layers: the model dict `DecodeRuntime` takes for its `latent_moe`
block with a mixer per layer (the gated short convolution, shortconv.py, in
three layers of four; grouped-query attention with a norm on every query and
key head, the dense block's own code, in the fourth; a dense SwiGLU in the
two leading layers and 32 routed experts, chosen through a choice bias and
with NO shared expert, in the others, the WHOLE layer on this chip,
experts.py; paddle_tpu/serving/generation/decode.py), every weight's shape
under the program's own names, and the least bytes of a decode step (the
rooflines' numerators).
"""
import numpy as np

ROW_BYTES = 2               # the K and V pools are bfloat16
TAIL_BYTES = 4              # the convolution's tail is float32
_MIXERS = {'conv': 'conv', 'full_attention': 'gqa'}


def model_dict(config, traffic):
    if not config['norm_topk_prob'] or not config['use_expert_bias']:
        raise ValueError('the experts branch normalises its top-k weights '
                         'and chooses through a bias')
    if config['conv_bias']:
        raise ValueError('the short convolution has no bias')
    layers = int(config['num_hidden_layers'])
    kinds = list(config['layer_types'])
    if len(kinds) != layers or any(k not in _MIXERS for k in kinds):
        raise ValueError('layer_types must give every one of the %d layers '
                         'one mixer of %s' % (layers, sorted(_MIXERS)))
    dense = int(config['num_dense_layers'])
    heads = int(config['num_attention_heads'])
    return {
        'block': 'latent_moe',
        'vocab': int(config['vocab_size']),
        'd_model': int(config['hidden_size']),
        'n_layer': layers,
        'n_head': heads,
        'n_kv_head': int(config['num_key_value_heads']),
        'head_dim': int(config['hidden_size']) // heads,
        'd_ffn': int(config['intermediate_size']),
        'theta': float(config['rope_theta']),
        'rms_eps': float(config['norm_eps']),
        'max_len': int(traffic['slot_tokens']),
        'qk_norm': True,
        # per layer, which mixer and which feed-forward it has
        'mixer': [_MIXERS[k] for k in kinds],
        'ffn': ['dense'] * dense + ['experts'] * (layers - dense),
        'conv': {'taps': int(config['conv_L_cache'])},
        # the whole layer on this chip: ranks = 1, every expert held
        'moe': {'n_routed': int(config['num_experts']),
                'top_k': int(config['num_experts_per_tok']),
                'd_expert': int(config['moe_intermediate_size']),
                'n_shared': 0,
                'scale': float(config['routed_scaling_factor']),
                'norm_eps': 1e-6,
                'bias': True, 'ranks': 1, 'rank': 0}}


def mixer_shapes(model, kind):
    d = model['d_model']
    if kind == 'conv':
        return {'conv_in_w': (d, 3 * d),
                'conv_taps': (model['conv']['taps'], d),
                'conv_out_w': (d, d)}
    h, hkv, dh = model['n_head'], model['n_kv_head'], model['head_dim']
    return {'att_q_w': (d, h * dh), 'att_k_w': (d, hkv * dh),
            'att_v_w': (d, hkv * dh), 'att_o_w': (h * dh, d),
            'att_q_norm': (dh,), 'att_k_norm': (dh,)}


def layer_shapes(model, mixer, kind):
    """{weight: shape} of ONE block with that mixer whose feed-forward is
    `kind`."""
    d = model['d_model']
    shapes = dict(mixer_shapes(model, mixer), att_norm=(d,), ffn_norm=(d,))
    if kind == 'dense':
        f = model['d_ffn']
        shapes.update(ffn_fc1_w=(d, f), ffn_fc3_w=(d, f), ffn_fc2_w=(f, d))
        return shapes
    f, n = model['moe']['d_expert'], model['moe']['n_routed']
    shapes.update(moe_router_w=(d, n), moe_router_bias=(n,),
                  moe_fc1_w=(n, d, f), moe_fc3_w=(n, d, f),
                  moe_fc2_w=(n, f, d))
    return shapes


def weight_shapes(model):
    d, v = model['d_model'], model['vocab']
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i, (mixer, kind) in enumerate(zip(model['mixer'], model['ffn'])):
        for k, s in layer_shapes(model, mixer, kind).items():
            shapes['layer_%d_%s' % (i, k)] = s
    return shapes


def _count(shapes, skip=()):
    return sum(int(np.prod(s)) for k, s in shapes.items()
               if len(s) >= 2 and k not in skip)


def expert_bytes(model, weight_bytes=2):
    """One routed expert's three matrices."""
    return 3 * model['d_model'] * model['moe']['d_expert'] * weight_bytes


def resident_bytes(model, weight_bytes=2):
    """What every decode step reads whatever is routed: every layer's
    mixer, the two dense layers, the routers and the output head (the
    embedding is read by row)."""
    routed = ('moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w')
    per = sum(_count(layer_shapes(model, mixer, kind), skip=routed)
              for mixer, kind in zip(model['mixer'], model['ffn']))
    return (per + model['d_model'] * model['vocab']) * weight_bytes


def kv_bytes_per_token(model):
    """What one token leaves in the cache in ONE attention layer: its
    normed, rotated key and its value, 2 x 8 heads of the PUBLISHED 64."""
    return 2 * model['n_kv_head'] * model['head_dim'] * ROW_BYTES


def tail_bytes(model):
    """The convolution's tail ONE stream holds in ONE `conv` layer: the
    taps - 1 = 2 rows of u a step reads, float32."""
    return TAIL_BYTES * (model['conv']['taps'] - 1) * model['d_model']


def attention_layers(model):
    return model['mixer'].count('gqa')


def conv_layers(model):
    return model['mixer'].count('conv')


def attention_bytes(model, live_kv_tokens):
    """The least bytes the decode step's attention must move: every live
    row's key and value, in every attention layer."""
    return attention_layers(model) * kv_bytes_per_token(model) \
        * live_kv_tokens


def bytes_per_decode_step(model, live_slots, live_kv_tokens,
                          experts_touched, weight_bytes=2):
    """The least bytes ONE decode step (one token for every live stream)
    must move through HBM: the resident weights once, the routed experts
    TOUCHED (experts with at least one token, summed over the expert
    layers: `generation.moe_experts_touched` a step; not all 32 a layer, or
    a step that skips idle experts would read over 100 %), the embedding
    rows of the fed tokens, every LIVE stream's convolution tails read once
    and written once, and the live K and V rows, 2,048 B a token an
    attention layer.  Norm scales and activations are left out (under
    0.01 %)."""
    return (resident_bytes(model, weight_bytes)
            + experts_touched * expert_bytes(model, weight_bytes)
            + live_slots * model['d_model'] * weight_bytes
            + 2.0 * conv_layers(model) * live_slots * tail_bytes(model)
            + attention_bytes(model, live_kv_tokens))
