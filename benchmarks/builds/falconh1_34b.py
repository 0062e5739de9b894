"""How the program is asked for Falcon-H1-34B-Instruct: the model dict
`DecodeRuntime` takes for its `falcon_h1` block (attention and a Mamba-2
mixer side by side in every layer; paddle_tpu/serving/generation/decode.py
and ssm.py), every weight's shape under the program's own names, and the
least bytes one decode step must move (the roofline's numerator).
"""
import numpy as np

STATE_BYTES = 4                     # the recurrent state is float32


def model_dict(config, traffic):
    gate_mult, down_mult = config['mlp_multipliers']
    return {
        'block': 'falcon_h1',
        'vocab': int(config['vocab_size']),
        'd_model': int(config['hidden_size']),
        'n_layer': int(config['num_hidden_layers']),
        'n_head': int(config['num_attention_heads']),
        'n_kv_head': int(config['num_key_value_heads']),
        'head_dim': int(config['head_dim']),
        'd_ffn': int(config['intermediate_size']),
        'theta': float(config['rope_theta']),
        'rms_eps': float(config['rms_norm_eps']),
        'max_len': int(traffic['slot_tokens']),
        'ssm': {'d_ssm': int(config['mamba_d_ssm']),
                'n_heads': int(config['mamba_n_heads']),
                'n_groups': int(config['mamba_n_groups']),
                'd_state': int(config['mamba_d_state']),
                'd_conv': int(config['mamba_d_conv']),
                'chunk': int(config['mamba_chunk_size'])},
        'multipliers': {
            'embedding': float(config['embedding_multiplier']),
            'lm_head': float(config['lm_head_multiplier']),
            'attention_in': float(config['attention_in_multiplier']),
            'attention_out': float(config['attention_out_multiplier']),
            'key': float(config['key_multiplier']),
            'ssm_in': float(config['ssm_in_multiplier']),
            'ssm_out': float(config['ssm_out_multiplier']),
            'ssm': [float(m) for m in config['ssm_multipliers']],
            'mlp_gate': float(gate_mult), 'mlp_down': float(down_mult)}}


def _conv_channels(ssm):
    return ssm['d_ssm'] + 2 * ssm['n_groups'] * ssm['d_state']


def layer_shapes(model):
    """{weight: shape} of ONE block."""
    d, f = model['d_model'], model['d_ffn']
    h, hkv, dh = model['n_head'], model['n_kv_head'], model['head_dim']
    ssm = model['ssm']
    ch, heads = _conv_channels(ssm), ssm['n_heads']
    return {
        'att_q_w': (d, h * dh), 'att_k_w': (d, hkv * dh),
        'att_v_w': (d, hkv * dh), 'att_o_w': (h * dh, d),
        'att_norm': (d,), 'ffn_norm': (d,), 'ffn_fc1_w': (d, f),
        'ffn_fc3_w': (d, f), 'ffn_fc2_w': (f, d),
        # the mixer: z, x, B, C, dt out of one projection; the gated
        # norm's scale ends in `norm`, so the runner makes it ones
        'ssm_in_w': (d, ssm['d_ssm'] + ch + heads),
        'ssm_conv_w': (ssm['d_conv'], ch), 'ssm_conv_b': (ch,),
        'ssm_dt_bias': (heads,), 'ssm_A_log': (heads,), 'ssm_D': (heads,),
        'ssm_gate_norm': (ssm['d_ssm'],),
        'ssm_out_w': (ssm['d_ssm'], d)}


def weight_shapes(model):
    d, v = model['d_model'], model['vocab']
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i in range(model['n_layer']):
        for k, s in layer_shapes(model).items():
            shapes['layer_%d_%s' % (i, k)] = s
    return shapes


def state_bytes_per_slot(model):
    """The recurrent state ONE stream holds over every layer: the scan
    state [heads, d_ssm / heads, d_state] and the convolution's last
    d_conv - 1 inputs, float32."""
    ssm = model['ssm']
    per_layer = ssm['d_ssm'] * ssm['d_state'] \
        + (ssm['d_conv'] - 1) * _conv_channels(ssm)
    return STATE_BYTES * model['n_layer'] * per_layer


def bytes_per_decode_step(model, live_slots, live_kv_tokens, weight_bytes=2,
                          kv_bytes=2):
    """The least bytes ONE decode step (one token for every live stream)
    must move through HBM: every block's matrices and the output head
    once, the embedding rows of the fed tokens, the K and V rows of every
    live cached token, and every live stream's recurrent state READ AND
    WRITTEN.  Norm scales, the mixer's vectors and activations are left
    out (under 0.01 %).  What the program moves beyond this, the state of
    dead slots above all, is what the roofline share exposes."""
    per_layer = sum(int(np.prod(s)) for k, s in layer_shapes(model).items()
                    if len(s) == 2 and k != 'ssm_conv_w')
    weights = (model['n_layer'] * per_layer
               + model['d_model'] * model['vocab']) * weight_bytes
    embed = live_slots * model['d_model'] * weight_bytes
    kv = 2.0 * model['n_layer'] * model['n_kv_head'] * model['head_dim'] \
        * kv_bytes * live_kv_tokens
    state = 2.0 * state_bytes_per_slot(model) * live_slots
    return weights + embed + kv + state
