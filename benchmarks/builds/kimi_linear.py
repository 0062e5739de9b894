"""How the program is asked for Kimi-Linear-48B-A3B-Instruct as ONE
expert-parallel rank of 4 holds it: the model dict `DecodeRuntime` takes for
its `latent_moe` block with a mixer per layer (Kimi Delta Attention, kda.py,
in three layers of four; latent attention without positions and without a
low-rank query step, latent.py, in the fourth; a dense SwiGLU in the leading
layer and routed experts, chosen through a choice bias, beside a shared one
in the others, experts.py; paddle_tpu/serving/generation/decode.py), every
weight's shape under the program's own names, and the least bytes of a decode
step and of the delta rule's step (the rooflines' numerators).
"""
import numpy as np

RANKS = 4                   # chips that share a layer's routed experts
ROW_BYTES = 2               # the latent pool is bfloat16
STATE_BYTES = 4             # the matrix state is float32
GATE_RANK = 128             # W_fa / W_ga: [hidden, 128] (the source's head_dim
#                             of linear_attn_config; ISSUE 61, section 1)


def model_dict(config, traffic):
    if not config['moe_renormalize']:
        raise ValueError('the experts branch normalises its top-k weights')
    if config['q_lora_rank'] is not None or not config['mla_use_nope']:
        raise ValueError('this build file is of the position-free latent '
                         'layer without a low-rank query step')
    layers = int(config['num_hidden_layers'])
    dense = int(config['first_k_dense_replace'])
    lin = config['linear_attn_config']
    kda_layers = set(int(i) for i in lin['kda_layers'])      # one-based
    full = set(int(i) for i in lin['full_attn_layers'])
    if kda_layers | full != set(range(1, layers + 1)) or kda_layers & full:
        raise ValueError('linear_attn_config must give every one of the %d '
                         'layers one mixer' % layers)
    return {
        'block': 'latent_moe',
        'vocab': int(config['vocab_size']),
        'd_model': int(config['hidden_size']),
        'n_layer': layers,
        'n_head': int(config['num_attention_heads']),
        'd_ffn': int(config['intermediate_size']),
        'theta': float(config['rope_theta']),
        'rms_eps': float(config['rms_norm_eps']),
        'max_len': int(traffic['slot_tokens']),
        # per layer, which mixer and which feed-forward it has
        'mixer': ['kda' if i + 1 in kda_layers else 'latent'
                  for i in range(layers)],
        'ffn': ['dense'] * dense + ['experts'] * (layers - dense),
        'latent': {'q_rank': None,
                   'kv_rank': int(config['kv_lora_rank']),
                   'nope': int(config['qk_nope_head_dim']),
                   'rope': int(config['qk_rope_head_dim']),
                   'v': int(config['v_head_dim']),
                   'rotate': False},
        'kda': {'n_heads': int(lin['num_heads']),
                'head_dim': int(lin['head_dim']),
                'd_conv': int(lin['short_conv_kernel_size']),
                'gate_rank': GATE_RANK,
                # ASSUMED: the mean of dt_bias, which the runner draws
                # about zero (configs/kimi_linear.json, `assumed`)
                'dt_shift': float(config['kda_dt_bias_mean'])},
        # the router's published width and picks; `ranks` chips share a
        # layer and this one is `rank`: it holds n_routed / ranks experts
        'moe': {'n_routed': int(config['num_experts_published']),
                'top_k': int(config['num_experts_per_token']),
                'd_expert': int(config['moe_intermediate_size']),
                'n_shared': int(config['num_shared_experts']),
                'scale': float(config['routed_scaling_factor']),
                'bias': True,
                'ranks': RANKS, 'rank': int(config['expert_parallel_rank'])}}


def held_experts(model):
    return model['moe']['n_routed'] // model['moe']['ranks']


def mixer_shapes(model, kind):
    d = model['d_model']
    if kind == 'latent':
        h, lat = model['n_head'], model['latent']
        return {'att_q_w': (d, h * (lat['nope'] + lat['rope'])),
                'att_kva_w': (d, lat['kv_rank'] + lat['rope']),
                'att_kva_norm': (lat['kv_rank'],),
                'att_kvb_w': (lat['kv_rank'], h * (lat['nope'] + lat['v'])),
                'att_o_w': (h * lat['v'], d)}
    kda = model['kda']
    H, dh, r = kda['n_heads'], kda['head_dim'], kda['gate_rank']
    n, taps = H * dh, kda['d_conv']
    return {'kda_q_w': (d, n), 'kda_k_w': (d, n), 'kda_v_w': (d, n),
            'kda_q_conv': (taps, n), 'kda_k_conv': (taps, n),
            'kda_v_conv': (taps, n), 'kda_fa_w': (d, r), 'kda_fb_w': (r, n),
            'kda_A_log': (H,), 'kda_dt_bias': (n,), 'kda_beta_w': (d, H),
            'kda_ga_w': (d, r), 'kda_gb_w': (r, n), 'kda_o_norm': (dh,),
            'kda_o_w': (n, d)}


def layer_shapes(model, mixer, kind):
    """{weight: shape} of ONE block with that mixer whose feed-forward is
    `kind`."""
    d = model['d_model']
    shapes = dict(mixer_shapes(model, mixer), att_norm=(d,), ffn_norm=(d,))
    if kind == 'dense':
        f = model['d_ffn']
        shapes.update(ffn_fc1_w=(d, f), ffn_fc3_w=(d, f), ffn_fc2_w=(f, d))
        return shapes
    f, n = model['moe']['d_expert'], held_experts(model)
    fs = f * model['moe']['n_shared']
    shapes.update(moe_router_w=(d, model['moe']['n_routed']),
                  moe_router_bias=(model['moe']['n_routed'],),
                  moe_fc1_w=(n, d, f), moe_fc3_w=(n, d, f),
                  moe_fc2_w=(n, f, d), moe_shared_fc1_w=(d, fs),
                  moe_shared_fc3_w=(d, fs), moe_shared_fc2_w=(fs, d))
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
    mixer, the dense layer, the routers and shared experts, and the output
    head (the embedding is read by row)."""
    routed = ('moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w')
    per = sum(_count(layer_shapes(model, mixer, kind), skip=routed)
              for mixer, kind in zip(model['mixer'], model['ffn']))
    return (per + model['d_model'] * model['vocab']) * weight_bytes


def row_bytes(model):
    """What one token leaves in the cache a latent layer: [c_kv ; k_pe]."""
    return (model['latent']['kv_rank'] + model['latent']['rope']) * ROW_BYTES


def state_bytes(model):
    """The matrix state ONE stream holds in ONE `kda` layer: [heads, 128,
    128] float32."""
    kda = model['kda']
    return STATE_BYTES * kda['n_heads'] * kda['head_dim'] ** 2


def tail_bytes(model):
    """The three convolutions' last inputs ONE stream holds in ONE `kda`
    layer, float32."""
    kda = model['kda']
    return STATE_BYTES * (kda['d_conv'] - 1) * 3 * kda['n_heads'] \
        * kda['head_dim']


def kda_layers(model):
    return model['mixer'].count('kda')


def kda_step_bytes(model, live_slot_steps):
    """The least bytes the delta rule's step must move for
    `live_slot_steps` (stream, step) pairs: every `kda` layer's matrix state
    of a LIVE stream read once and written once."""
    return 2.0 * kda_layers(model) * state_bytes(model) * live_slot_steps


def bytes_per_decode_step(model, live_slots, live_kv_tokens,
                          experts_touched, weight_bytes=2):
    """The least bytes ONE decode step (one token for every live stream)
    must move through HBM: the resident weights once, the routed experts
    TOUCHED (held experts with at least one token, summed over the expert
    layers: `generation.moe_experts_touched` a step; not all held ones, or
    a step that skips idle experts would read over 100 %), the embedding
    rows of the fed tokens, every LIVE stream's matrix state and
    convolution tails read once and written once, and the live latent rows,
    1,152 B a token a latent layer.  Norm scales and activations are left
    out (under 0.01 %)."""
    latent_layers = model['mixer'].count('latent')
    return (resident_bytes(model, weight_bytes)
            + experts_touched * expert_bytes(model, weight_bytes)
            + live_slots * model['d_model'] * weight_bytes
            + 2.0 * kda_layers(model) * live_slots
            * (state_bytes(model) + tail_bytes(model))
            + latent_layers * row_bytes(model) * live_kv_tokens)
