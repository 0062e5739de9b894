"""How the program is asked for A.X-K1 as ONE expert-parallel rank of 16
holds it: the model dict `DecodeRuntime` takes for its `latent_moe` block
(latent attention over a one-row-a-token pool, latent.py; a dense SwiGLU in
the leading layer and routed experts beside a shared one in the others,
experts.py; paddle_tpu/serving/generation/decode.py), every weight's shape
under the program's own names, and the least bytes and operations of a
decode step and of the decode kernel (the rooflines' numerators).
"""
import numpy as np

RANKS = 16                  # chips that share a layer's routed experts
ROW_BYTES = 2               # the latent pool is bfloat16


def model_dict(config, traffic):
    if not config['norm_topk_prob']:
        raise ValueError('the experts branch normalises its top-k weights')
    layers = int(config['num_hidden_layers'])
    dense = int(config['first_k_dense_replace'])
    scaling = config['rope_scaling']
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
        # per layer, which feed-forward it has
        'ffn': ['dense'] * dense + ['experts'] * (layers - dense),
        'latent': {'q_rank': int(config['q_lora_rank']),
                   'kv_rank': int(config['kv_lora_rank']),
                   'nope': int(config['qk_nope_head_dim']),
                   'rope': int(config['qk_rope_head_dim']),
                   'v': int(config['v_head_dim']),
                   'yarn': {
                       'factor': float(scaling['factor']),
                       'beta_fast': float(scaling['beta_fast']),
                       'beta_slow': float(scaling['beta_slow']),
                       'original_max_len': int(
                           scaling['original_max_position_embeddings']),
                       'mscale': float(scaling['mscale']),
                       'mscale_all_dim': float(scaling['mscale_all_dim'])}},
        # the router's published width and picks; `ranks` chips share a
        # layer and this one is `rank`: it holds n_routed / ranks experts
        'moe': {'n_routed': int(config['n_routed_experts_published']),
                'top_k': int(config['num_experts_per_tok']),
                'd_expert': int(config['moe_intermediate_size']),
                'n_shared': int(config['n_shared_experts']),
                'scale': float(config['routed_scaling_factor']),
                'ranks': RANKS, 'rank': int(config['expert_parallel_rank'])}}


def held_experts(model):
    return model['moe']['n_routed'] // model['moe']['ranks']


def attention_shapes(model):
    d, h, lat = model['d_model'], model['n_head'], model['latent']
    return {'att_qa_w': (d, lat['q_rank']), 'att_qa_norm': (lat['q_rank'],),
            'att_qb_w': (lat['q_rank'], h * (lat['nope'] + lat['rope'])),
            'att_kva_w': (d, lat['kv_rank'] + lat['rope']),
            'att_kva_norm': (lat['kv_rank'],),
            'att_kvb_w': (lat['kv_rank'], h * (lat['nope'] + lat['v'])),
            'att_o_w': (h * lat['v'], d)}


def layer_shapes(model, kind):
    """{weight: shape} of ONE block whose feed-forward is `kind`."""
    d = model['d_model']
    shapes = dict(attention_shapes(model), att_norm=(d,), ffn_norm=(d,))
    if kind == 'dense':
        f = model['d_ffn']
        shapes.update(ffn_fc1_w=(d, f), ffn_fc3_w=(d, f), ffn_fc2_w=(f, d))
        return shapes
    f, n = model['moe']['d_expert'], held_experts(model)
    fs = f * model['moe']['n_shared']
    shapes.update(moe_router_w=(d, model['moe']['n_routed']),
                  moe_fc1_w=(n, d, f), moe_fc3_w=(n, d, f),
                  moe_fc2_w=(n, f, d), moe_shared_fc1_w=(d, fs),
                  moe_shared_fc3_w=(d, fs), moe_shared_fc2_w=(fs, d))
    return shapes


def weight_shapes(model):
    d, v = model['d_model'], model['vocab']
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i, kind in enumerate(model['ffn']):
        for k, s in layer_shapes(model, kind).items():
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
    attention, the dense layer, the routers and shared experts, and the
    output head (the embedding is read by row)."""
    routed = ('moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w')
    per = sum(_count(layer_shapes(model, kind), skip=routed)
              for kind in model['ffn'])
    return (per + model['d_model'] * model['vocab']) * weight_bytes


def row_bytes(model):
    """What one token leaves in the cache a layer: [c_kv ; k_r]."""
    return (model['latent']['kv_rank'] + model['latent']['rope']) * ROW_BYTES


def bytes_per_decode_step(model, live_slots, live_kv_tokens,
                          experts_touched, weight_bytes=2):
    """The least bytes ONE decode step (one token for every live stream)
    must move through HBM: the resident weights once, the routed experts
    TOUCHED (held experts with at least one token, summed over the expert
    layers: `generation.moe_experts_touched` a step; not all held ones, or
    a step that skips idle experts would read over 100 %), the embedding
    rows of the fed tokens, and the live latent rows, 1,152 B a token a
    layer.  Norm scales and activations are left out (under 0.01 %)."""
    return (resident_bytes(model, weight_bytes)
            + experts_touched * expert_bytes(model, weight_bytes)
            + live_slots * model['d_model'] * weight_bytes
            + model['n_layer'] * row_bytes(model) * live_kv_tokens)


def latent_attention_cost(model, rows):
    """(operations, bytes) the decode kernel needs for `rows` cached rows
    read (one row is read once for all heads): per row and head a score
    over kv_rank + rope columns and a value sum over kv_rank, a
    multiply-add as two; the row's own bytes."""
    lat = model['latent']
    per_row = 2 * model['n_head'] * (2 * lat['kv_rank'] + lat['rope'])
    return per_row * rows, row_bytes(model) * rows
