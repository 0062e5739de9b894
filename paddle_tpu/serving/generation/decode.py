"""Fused prefill/decode executables over the PAGED KV pool.

The decode hot loop keeps the few-large-fused-primitives shape: one AOT
executable advances ALL cache slots K tokens as a single `lax.scan`
with the page pools as DONATED carry — no per-token Python dispatch, no
host round-trips inside the window.  K/V now live in a shared page pool
(kv_cache.py); every read and write goes through a per-slot BLOCK TABLE
passed as a plain ``[slots, max_pages]`` int32 argument.  Block tables
are DATA, never part of an executable signature: one warm executable
serves every batch composition and every page assignment forever (the
``generation.compiles == 2`` pin survives paging untouched).

Inactive slots ride along under a mask with their write target forced
to the GARBAGE page 0 (a freed slot's pages may already belong to
someone else — most importantly a shared prefix page — so the old
"write into your own row's next free position" trick is replaced by an
explicitly harmless destination).  Active slots past their reservation
also fall through to page 0: unmapped block-table entries are 0 by
construction.

Prefill is chunked exactly as before, but each chunk scatters its K/V
rows into the pages its block table maps and attends against the
GATHERED logical row (pages reassembled to ``[Hkv, max_len, head_dim]``
inside the executable, positional mask ``kpos <= qpos`` unchanged).
With ``quant='int8'`` rows are stored as int8 with one float32 scale
per (token, kv head), quantized on write and dequantized inside the
gather — attention math stays float32.

The decode step does NOT gather: it attends over the pool in place
(`ops.attention.paged_attention`), each active slot reading the pages
its length covers and an inactive slot nothing, so the KV bytes a step
moves follow the live tokens, not ``slots * max_len``.  An int8 pool
and a runtime over a mesh of several devices keep the composed path
(`_logical_rows` + `cached_attention`); which one runs is read off the
pool and the mesh (`DecodeRuntime.paged`), and
``generation.kv_rows_read`` counts what that path reads
(`DecodeRuntime._window_rows_read`).

`_verify_fn` is the speculative-decode twin of the decode window: the
same step body, but each scan step feeds a HOST-PROVIDED token (last
emitted token + draft proposals) instead of the carry token, and the
returned per-step samples are the target model's verdicts.  The
rerun-deterministic ``(seed, position)`` sampling makes acceptance
replay-stable: a verified prefix is bitwise what sequential decode
would have produced.

Every executable is compiled ahead of time and persisted through the
compile-cache disk tier (core/compile_cache.callable_fingerprint) — the
cache spec now carries page_len/pages/quant, so geometry changes get
fresh fingerprints.  `dense_reference` is the independent, page-free
parity oracle.

Weights as the launches read them.  `DecodeRuntime` prepares each
layer's q, k and v projection ONCE, where it adopts the weights
(`_params_from`), instead of every launch and every layer re-deriving
that form on the chip.  LAYOUT: the public ``[D, N]`` matrix is stored
``[N, D]`` and `_qkv` contracts it on its second axis, the operand
layout XLA:TPU asks of these three products in both executables (fed
``[D, N]``, the window copied all 3 x layers of them before its loop
on every launch, and so did every prefill chunk).  ROTARY PAIRS AS
HALVES: within every head the output columns of q and of k are
reordered even-then-odd (`_head_rows`), and `_rope_at` turns
``x[..., :dh/2]`` against ``x[..., dh/2:]`` by the same angles
``pos * theta^(-2i/dh)`` the interleaved rotation gives pair ``(2i,
2i + 1)``: two contiguous halves where the pairs were a stride of two
along the lanes.  q and k are then the public q and k under ONE fixed
permutation of the head dimension, so every score ``q . k``, softmax
and output is the same number up to the order of a sum; v,
``att_o_w``, the ``key`` multiplier and the int8 row scale (a maximum
over the head dimension) never see it.  A K PAGE THEREFORE HOLDS ITS
ROWS IN ROTATED-HALF ORDER, whoever wrote it (prefill, decode, verify,
ring prefill: all through `_qkv` and `_rope_at`) and whoever shares it
(the prefix cache).  The public face is unchanged: the constructor
takes `weight_names(cfg)` in the public shapes, ``rt.w[name]`` answers
with the public names, shapes and values bit for bit (`_PublicWeights`
undoes the data movement on read; the runtime itself holds q, k and v
once, prepared: ``rt.params``), and `cache_row` returns K in the
public order.  `dense_reference` keeps the interleaved rotation on the
raw weights and so checks the preparation.
tests/test_generation_layout.py holds the compiled text (no copy of a
weight's extent in either launch) and the exactness.

The model dict chooses the BLOCK.  Without a ``block`` key it is the
dense decoder above (RMSNorm, GQA, RoPE, SwiGLU), whose ``head_dim``
and ``rms_eps`` default to ``d_model // n_head`` and 1e-6.  ``block:
'falcon_h1'`` is the Falcon-H1 block: the same attention and
feed-forward under the model's ``multipliers``, and BESIDE the
attention, reading the same normalised input, a Mamba-2 mixer (ssm.py)
whose outputs are both added to the residual stream.  Such a model has
a second kind of state: per slot and layer a float32 scan state and the
convolution's last inputs (``ssm`` / ``conv`` in the state dict,
kv_cache.py), donated and carried like the pools.  A prefill chunk
starts from the slot's state (from zeros at offset 0) and leaves it at
``true_count``; a decode step advances the live slots' scan state in
place (`ssm.ssm_step`, a Pallas kernel that touches no other slot's;
under a mesh `ssm.scan_step` steps every slot and masks:
`DecodeRuntime.state_kernel`) and keeps an inactive slot's state as it
was, bit for bit.  That state can neither be
shared between prompts nor rolled back, so such a runtime takes no
prefix-cache hit (`generation.prefix_refused_recurrent` counts the
begins), no speculative window and no ring prefill.

``block: 'latent_moe'`` is the third kind: multi-head LATENT attention
(latent.py; or another mixer a layer, below) and, per layer as
``cfg['ffn']`` says, a dense SwiGLU or routed experts beside a shared
one (or none), held as ONE expert-parallel rank
(experts.py; ``cfg['moe']`` says which of ``ranks`` this is).  Its
weights have other names and shapes (`weight_shapes`), its prepared
forms are not per-head q/k/v (`latent.PREPARED`: the up-projection
split into its key and value halves for absorption, rope columns as
rotated halves), and its pool has the second geometry: ONE row
``[c_kv ; k_r]`` a token a layer and no V pool (`CacheConfig.latent`),
under the same pages, block tables and prefix cache.  A prefill chunk
expands keys and values from the gathered rows, its scores kept on chip
(`ops.attention.latent_prefill`; a loop of XLA operations under a mesh:
`DecodeRuntime.prefill_kernel`); a decode step attends
in the absorbed form over the pool in place
(`ops.attention.latent_attention`; gathered rows under a mesh:
`DecodeRuntime.paged`).  The residual stream is float32.  Its two
launches hand back, beside their tokens, a few counts
(`_LAUNCH_STATS`: routing's and the latent rows read) that move into
``generation.moe_*`` / ``generation.latent_rows_read`` behind the next
read of a result (`DecodeRuntime._count_stats`): no launch and no wait
of their own.  No ring prefill and no int8 rows.  The other two kinds'
programs are untouched by it, bit for bit (tests/
test_generation_pipeline.py pins their lowered text).

A ``latent_moe`` model may give a MIXER PER LAYER as data, as it gives
its feed-forward: ``cfg['mixer']``, for each layer ``'latent'`` (the
default, every layer), ``'kda'``, ``'gqa'`` or ``'conv'``; it must
ATTEND in at least one layer, through ``'latent'`` layers or through
``'gqa'`` ones (it need have no ``'latent'`` layer, and then no
``cfg['latent']``).  A ``'kda'`` layer is Kimi Delta
Attention (kda.py): a float32 matrix state a head and three short
convolutions' tails, per slot, in the recurrent arrays of the state
dict (``[slots, layers, H, d, d]`` and ``[slots, layers, d_conv - 1,
3 H, d]``), and NO rows in the pool.  The pool's layer axis then counts the
layers that attend and the state's the layers that hold state
(`CacheConfig.layers` / ``recurrent_layers``; `_layer_axes` gives each
layer its index on its own).  Such a model is `recurrent` like
``falcon_h1``: a chunk starts from the slot's state (zeros at offset
0), a step advances the live slots' and keeps the dead ones' bit for
bit, and the runtime takes no prefix-cache hit, no speculative window
and no ring prefill.  Its launches' stats carry three counts more
(`_launch_stats`: the state and the tails the windows moved, the tokens
the chunk scan took).  A model without the key lowers to the program it had
(tests/test_generation_kda.py pins the text).

Two more mixers may stand there.  ``'gqa'`` is the dense block's own
attention as a layer's mixer: `_qkv`, `_rope_at`, `_write_rows` and
`ops.attention.paged_attention` over the K and V pools of the FIRST
geometry, whose layer axis counts the layers that attend; with
``cfg['qk_norm']`` a learned RMS norm on every query and key head
between the projection and the rotation (weights ``att_q_norm`` /
``att_k_norm`` ``[head_dim]``, the pool's K row is the normed, rotated
key).  A model attends through ``'latent'`` OR ``'gqa'`` layers, never
both (one pool geometry a runtime), and in at least one.  Such
a layer's pool is laid out for the paged kernel
(`ops.attention.paged_pool_heads`): a head of 64 lies two kv heads to a
128-lane row, ``[pages, layers, page_len, kv_heads / 2, 128]``, the same
bytes in the same order (`_pool_rows` / `_head_rows_of`), so the step
attends in place as at 128 and no lane of a row is padding.  ``'conv'``
is the gated short convolution (shortconv.py): its whole state is the
last ``taps - 1`` rows of its own input, the ``conv`` array of the
state dict with NO ``ssm`` beside it
(`CacheConfig.recurrent` ``(None, tail)``); such a model is `recurrent`
like one with ``'kda'`` layers (it holds state through ``'kda'`` OR
``'conv'`` layers, never both: one state geometry a runtime).  Its
feed-forward may be an expert layer with NO shared expert, and the
WHOLE layer (``ranks: 1``; experts.py).  The standing programs are
untouched by all of it (tests/test_generation_lfm2.py pins this kind's
two launches as the other files pin theirs).
"""
import threading
from collections.abc import Mapping

import numpy as np

from ... import observability as _obs
from ...core import compile_cache as _cc
from ...ops.attention import (cached_attention, latent_attention_eligible,
                              paged_attention, paged_attention_eligible,
                              paged_attention_rows, paged_pool_heads)
from ...ops.sampling import sample_logits, sample_tokens_at, token_key
from . import experts as _experts
from . import kda as _kda
from . import latent as _latent
from . import shortconv as _shortconv
from . import ssm as _ssm
from .kv_cache import (CacheConfig, PagePool, PrefixCache, SlotAllocator,
                       init_state)

__all__ = ['DecodeRuntime', 'dense_reference', 'weight_names',
           'weight_shapes', 'random_weights']

_WEIGHT_SLOTS = ('att_q_w', 'att_k_w', 'att_v_w', 'att_o_w', 'att_norm',
                 'ffn_norm', 'ffn_fc1_w', 'ffn_fc2_w', 'ffn_fc3_w')


_BLOCKS = ('dense', 'falcon_h1', 'latent_moe')


def _block(cfg):
    """The model's block kind: ``'dense'`` (the default: GQA and one
    SwiGLU), ``'falcon_h1'`` (the same beside a Mamba-2 mixer, ssm.py) or
    ``'latent_moe'`` (latent attention, latent.py, and per layer the
    feed-forward ``cfg['ffn']`` names, experts.py)."""
    block = cfg.get('block', 'dense')
    if block not in _BLOCKS:
        raise ValueError('block must be one of %s, got %r'
                         % (', '.join(map(repr, _BLOCKS)), block))
    return block


def _recurrent(cfg):
    """Whether the model carries recurrent state: a ``'falcon_h1'``
    block does in every layer (ssm.py), a ``'latent_moe'`` model in its
    ``'kda'`` or ``'conv'`` layers, if it has any (`_mixer_kinds`,
    kda.py, shortconv.py)."""
    return _block(cfg) == 'falcon_h1' or (
        _latent_moe(cfg) and _state_mixer(cfg) is not None)


def _latent_moe(cfg):
    """Whether the model's block is the ``'latent_moe'`` kind."""
    return _block(cfg) == 'latent_moe'


def _ffn_kinds(cfg):
    """A ``latent_moe`` model's feed-forward kind per layer: ``cfg['ffn']``,
    ``'dense'`` or ``'experts'`` for each of ``n_layer``."""
    kinds = tuple(cfg['ffn'])
    if len(kinds) != int(cfg['n_layer']) \
            or any(k not in ('dense', 'experts') for k in kinds):
        raise ValueError("ffn must name 'dense' or 'experts' for each of "
                         'the %d layers, got %r' % (int(cfg['n_layer']),
                                                    kinds))
    return kinds


# a `latent_moe` layer's mixer: the two that ATTEND (rows in the pool)
# and the two that hold STATE (the recurrent arrays)
_ATTENDING = ('latent', 'gqa')
_STATEFUL = ('kda', 'conv')


def _mixer_kinds(cfg):
    """A ``latent_moe`` model's mixer per layer: ``cfg['mixer']``,
    ``'latent'`` (latent.py), ``'gqa'`` (the dense block's attention),
    ``'kda'`` (kda.py) or ``'conv'`` (shortconv.py) for each of
    ``n_layer``; every layer ``'latent'`` for a model without the key.
    One pool geometry and one state geometry a runtime: a model attends
    through ``'latent'`` or ``'gqa'`` layers and holds state through
    ``'kda'`` or ``'conv'`` layers, never both of a pair.  `_ffn_kinds`'
    sibling, read by no other block kind."""
    L = int(cfg['n_layer'])
    kinds = tuple(cfg.get('mixer', ('latent',) * L))
    if len(kinds) != L or any(k not in _ATTENDING + _STATEFUL
                              for k in kinds) \
            or not any(k in _ATTENDING for k in kinds):
        raise ValueError("mixer must name 'latent', 'gqa', 'kda' or 'conv' "
                         'for each of the %d layers, and attend in at least '
                         'one, got %r' % (L, kinds))
    for pair, what in ((_ATTENDING, 'attends through'),
                       (_STATEFUL, 'holds state through')):
        if all(k in kinds for k in pair):
            raise ValueError('a model %s %r or %r layers, never both, got '
                             '%r' % ((what,) + pair + (kinds,)))
    return kinds


def _attending_mixer(cfg):
    """The mixer kind a ``latent_moe`` model attends through:
    ``'latent'`` or ``'gqa'``."""
    kinds = _mixer_kinds(cfg)
    return next(k for k in _ATTENDING if k in kinds)


def _state_mixer(cfg):
    """The mixer kind a ``latent_moe`` model holds state through
    (``'kda'`` or ``'conv'``), or None for one that holds none."""
    kinds = _mixer_kinds(cfg)
    return next((k for k in _STATEFUL if k in kinds), None)


def _layer_axes(cfg):
    """A ``latent_moe`` model's layers on their own axes: layer i's
    index among the layers of ITS mixer kind, which is its index on the
    pool's layer axis (``'latent'``, ``'gqa'``) or the recurrent state's
    (``'kda'``, ``'conv'``)."""
    seen, out = {}, []
    for kind in _mixer_kinds(cfg):
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return out


def _head_dim(cfg):
    return int(cfg.get('head_dim', int(cfg['d_model']) // int(cfg['n_head'])))


def weight_names(cfg):
    """The decode-side parameter names — the same names a trained llama
    program leaves in its scope (models/llama.py layout); a
    ``falcon_h1`` block adds its mixer's (`ssm.SLOTS`).  A ``latent_moe``
    block has its own: per layer the two norms, its mixer's (latent
    attention's, `latent.slots`, `kda.SLOTS`, `shortconv.SLOTS` or the
    dense block's four projections, `_gqa_shapes`) and, by the layer's
    feed-forward kind, the dense SwiGLU's or the expert layer's
    (`experts.weight_shapes`)."""
    if _latent_moe(cfg):
        return list(_latent_moe_shapes(cfg))
    slots = _WEIGHT_SLOTS + (_ssm.SLOTS if _recurrent(cfg) else ())
    names = ['tok_emb', 'final_norm', 'lm_proj_w']
    for i in range(int(cfg['n_layer'])):
        names.extend('layer_%d_%s' % (i, s) for s in slots)
    return names


def weight_shapes(cfg):
    """{name: shape} of every weight under `weight_names(cfg)`, in the
    public layout (a projection is ``[in, out]``)."""
    if _latent_moe(cfg):
        return _latent_moe_shapes(cfg)
    d, v, h = int(cfg['d_model']), int(cfg['vocab']), int(cfg['n_head'])
    hkv, f = int(cfg['n_kv_head']), int(cfg['d_ffn'])
    dh = _head_dim(cfg)
    mixer = _ssm.weight_shapes(d, cfg['ssm']) if _recurrent(cfg) else {}
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i in range(int(cfg['n_layer'])):
        p = 'layer_%d_' % i
        shapes.update({p + 'att_q_w': (d, h * dh),
                       p + 'att_k_w': (d, hkv * dh),
                       p + 'att_v_w': (d, hkv * dh),
                       p + 'att_o_w': (h * dh, d),
                       p + 'att_norm': (d,), p + 'ffn_norm': (d,),
                       p + 'ffn_fc1_w': (d, f), p + 'ffn_fc3_w': (d, f),
                       p + 'ffn_fc2_w': (f, d)})
        shapes.update((p + k, s) for k, s in mixer.items())
    return shapes


def _gqa_shapes(cfg):
    """{slot: shape} of a ``'gqa'`` mixer's weights: the dense block's
    four projections, and with ``cfg['qk_norm']`` the two head norms'
    scales (over a head's ``head_dim`` values, in the public order)."""
    d, dh = int(cfg['d_model']), _head_dim(cfg)
    h, hkv = int(cfg['n_head']), int(cfg['n_kv_head'])
    shapes = {'att_q_w': (d, h * dh), 'att_k_w': (d, hkv * dh),
              'att_v_w': (d, hkv * dh), 'att_o_w': (h * dh, d)}
    if cfg.get('qk_norm'):
        shapes.update({'att_q_norm': (dh,), 'att_k_norm': (dh,)})
    return shapes


def _latent_moe_shapes(cfg):
    """`weight_shapes` of a ``latent_moe`` model, in `weight_names`'
    order."""
    d, v = int(cfg['d_model']), int(cfg['vocab'])
    mixers = _mixer_kinds(cfg)
    mixer = {}
    if 'latent' in mixers:
        mixer['latent'] = _latent.weight_shapes(d, int(cfg['n_head']),
                                                cfg['latent'])
    if 'gqa' in mixers:
        mixer['gqa'] = _gqa_shapes(cfg)
    if 'kda' in mixers:
        mixer['kda'] = _kda.weight_shapes(d, cfg['kda'])
    if 'conv' in mixers:
        mixer['conv'] = _shortconv.weight_shapes(d, cfg['conv'])
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i, kind in enumerate(_ffn_kinds(cfg)):
        p = 'layer_%d_' % i
        shapes.update({p + 'att_norm': (d,), p + 'ffn_norm': (d,)})
        shapes.update((p + k, s) for k, s in mixer[mixers[i]].items())
        if kind == 'dense':
            f = int(cfg['d_ffn'])
            shapes.update({p + 'ffn_fc1_w': (d, f), p + 'ffn_fc3_w': (d, f),
                           p + 'ffn_fc2_w': (f, d)})
        else:
            shapes.update((p + k, s) for k, s in
                          _experts.weight_shapes(d, cfg['moe']).items())
    return shapes


def random_weights(cfg, seed=0, scale=0.08):
    """Random-init weight dict under `weight_names(cfg)` (tests/soaks
    that exercise the runtime without training a model first): a name
    ending in ``norm`` is ones, the rest normal at ``scale``."""
    rng = np.random.RandomState(seed)
    out = {}
    for n, s in weight_shapes(cfg).items():
        if n.endswith('norm'):
            out[n] = np.ones(s, np.float32)
        else:
            out[n] = (scale * rng.randn(*s)).astype(np.float32)
    return out


# ---------------------------------------- weights as the launches read them

# public slot -> the name its prepared form goes by among the executables'
# parameters: another name, so that a raw weight handed to `_qkv` is a
# KeyError and never a silently wrong product (q's matrix is square)
_PREPARED = {'att_q_w': 'att_q_wt', 'att_k_w': 'att_k_wt',
             'att_v_w': 'att_v_wt'}


def _head_rows(wt, dh, halves):
    """wt [heads * dh, D]: every head's rows from interleaved pairs
    (0, 1, 2, ...) to rotated halves (0, 2, ..., 1, 3, ...) with
    ``halves``, back without.  Data movement: each is the other's exact
    inverse."""
    n, d = wt.shape
    inner = (dh // 2, 2) if halves else (2, dh // 2)
    return wt.reshape((n // dh,) + inner + (d,)).transpose(
        0, 2, 1, 3).reshape(n, d)


def _prepare_qkv(q, k, v, dh):
    """The public ``[D, N]`` projections of one layer -> what `_qkv`
    contracts: ``[N, D]`` each, q's and k's heads in rotated-half order
    (v's columns keep theirs: nothing rotates v)."""
    return (_head_rows(q.T, dh, True), _head_rows(k.T, dh, True), v.T)


def _public_weight(slot, wt, dh):
    """`_prepare_qkv` undone for ONE prepared array: bitwise the public
    weight it was made from."""
    return (wt if slot == 'att_v_w' else _head_rows(wt, dh, False)).T


def _public_rows(k, dh):
    """K rows [..., dh] as the pool holds them (rotated halves) -> the
    public interleaved order (numpy; `DecodeRuntime.cache_row`)."""
    return k.reshape(k.shape[:-1] + (2, dh // 2)).swapaxes(-1, -2).reshape(
        k.shape)


def _qkv_layers(cfg):
    """The layers whose q, k and v are held as `_prepare_qkv` makes them:
    every layer of the dense and ``falcon_h1`` kinds, a ``latent_moe``
    model's ``'gqa'`` layers."""
    if _latent_moe(cfg):
        return [i for i, kind in enumerate(_mixer_kinds(cfg))
                if kind == 'gqa']
    return list(range(int(cfg['n_layer'])))


def _prepared_names(cfg):
    """{public name: (slot, the executables' name for its prepared
    form)} of the weights the runtime keeps prepared.  A ``latent_moe``
    model's are latent attention's (`latent.prepared`), in the layers
    that have it: a public weight there has one or two prepared parts,
    and the second entry is the tuple of their names; in its ``'gqa'``
    layers q, k and v as the dense block keeps them."""
    if _latent_moe(cfg) and _attending_mixer(cfg) == 'latent':
        return {'layer_%d_%s' % (i, slot):
                (slot, tuple('layer_%d_%s' % (i, t) for t in stored))
                for i, kind in enumerate(_mixer_kinds(cfg))
                if kind == 'latent'
                for slot, stored in _latent.prepared(cfg['latent']).items()}
    return {'layer_%d_%s' % (i, slot): (slot, 'layer_%d_%s' % (i, stored))
            for i in _qkv_layers(cfg) for slot, stored in _PREPARED.items()}


def _prepared_arrays(params, cfg):
    """Every prepared array of ``params``, a flat list."""
    out = []
    for _slot, stored in _prepared_names(cfg).values():
        out.extend(params[n] for n in
                   (stored if isinstance(stored, tuple) else (stored,)))
    return out


def _latent_dims(cfg):
    lat = cfg['latent']
    return (int(cfg['n_head']), int(lat['nope']), int(lat['rope']),
            int(lat['v']))


def _params_from(weights, cfg):
    """``weights`` under `weight_names(cfg)` -> the parameters the
    executables take: q, k and v of every layer prepared (one jitted
    call a layer, one compilation per layer geometry), under their
    `_PREPARED` names; every other weight as it is.  A layer's raw q, k
    and v are held only while that layer is prepared."""
    import jax
    import jax.numpy as jnp
    prepared = _prepared_names(cfg)
    params = {n: jnp.asarray(weights[n]) for n in weight_names(cfg)
              if n not in prepared}
    if _latent_moe(cfg) and _attending_mixer(cfg) == 'latent':
        # latent attention's: W_qb, W_kva, W_kvb -> `latent.PREPARED`
        prepare = jax.jit(_latent.prepare, static_argnums=(3, 4, 5, 6))
        slots = _latent.prepared(cfg['latent'])
        stored = [t for parts in slots.values() for t in parts]
        for i, kind in enumerate(_mixer_kinds(cfg)):
            if kind != 'latent':
                continue
            p = 'layer_%d_' % i
            made = prepare(*(jnp.asarray(weights[p + s]) for s in slots),
                           *_latent_dims(cfg))
            params.update(zip((p + t for t in stored), made))
        return params
    dh = _head_dim(cfg)
    prepare = jax.jit(_prepare_qkv, static_argnums=3)
    for i in _qkv_layers(cfg):
        p = 'layer_%d_' % i
        made = prepare(*(jnp.asarray(weights[p + s]) for s in _PREPARED), dh)
        params.update(zip((p + t for t in _PREPARED.values()), made))
    return params


class _PublicWeights(Mapping):
    """`DecodeRuntime.w`: the weights under `weight_names(cfg)`, in the
    public shapes and with the values that were passed in, bit for bit.
    The runtime keeps q, k and v ONCE, in the prepared form
    (`DecodeRuntime.params`); reading one of them here undoes the
    preparation into a fresh array, every other name is the array the
    executables read."""

    def __init__(self, params, cfg):
        import jax
        self._params, self._names = params, weight_names(cfg)
        self._prepared = _prepared_names(cfg)
        if _latent_moe(cfg) and _attending_mixer(cfg) == 'latent':
            self._dims = _latent_dims(cfg)
            self._undo = jax.jit(_latent.public,
                                 static_argnums=(0, 2, 3, 4, 5))
        else:
            self._dh = _head_dim(cfg)
            self._undo = jax.jit(_public_weight, static_argnums=(0, 2))

    def __getitem__(self, name):
        if name not in self._prepared:
            return self._params[name]
        slot, stored = self._prepared[name]
        if isinstance(stored, tuple):
            return self._undo(slot, tuple(self._params[t] for t in stored),
                              *self._dims)
        return self._undo(slot, self._params[stored], self._dh)

    def __iter__(self):
        return iter(self._names)

    def __len__(self):
        return len(self._names)


# ------------------------------------------------------- forward pieces

def _rms(x, scale, eps=1e-6):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _eps(cfg):
    return float(cfg.get('rms_eps', 1e-6))


def _scaled(cfg, x, name):
    """x times the model's multiplier ``name``; x itself for a model
    without ``multipliers`` (the dense decoder)."""
    mu = cfg.get('multipliers')
    return x * mu[name] if mu else x


def _rope_angles(pos, dh, theta):
    """(cos, sin) [B, 1, T, dh/2] of ``pos * theta^(-2i/dh)``: pair i's
    angle at each of pos [B, T]."""
    import jax.numpy as jnp
    freqs = theta ** (-jnp.arange(0, dh // 2) * 2.0 / dh)
    ang = pos[:, None, :, None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def _rope_at(x, pos, theta):
    """x: [B, h, T, dh] in ROTATED-HALF order (`_prepare_qkv`: a head's
    even columns, then its odd ones); pos: [B, T] absolute positions
    (per-row — decode slots all sit at different lengths).  Pair i of a
    head is ``(x[i], x[dh/2 + i])`` and turns by ``pos * theta^(-2i/dh)``:
    the interleaved rotation of the public order, with no stride along
    the lanes."""
    import jax.numpy as jnp
    dh = x.shape[-1]
    cos, sin = _rope_angles(pos, dh, theta)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _qkv(w, cfg, h, i, wide=None):
    """h: [B, T, D] -> q [B, H, T, dh], k/v [B, Hkv, T, dh] (pre-rope),
    from the PREPARED projections (`_prepare_qkv`): each stored
    ``[N, D]`` and contracted on its second axis, q's and k's heads in
    rotated-half order.  ``wide`` (a dtype; a ``latent_moe`` layer's
    float32 stream) takes ``h`` in the weights' dtype and hands the
    products back in ``wide``; without it they are what the operands'
    dtypes make them."""
    import jax.numpy as jnp
    B, T = h.shape[0], h.shape[1]
    H, Hkv = int(cfg['n_head']), int(cfg['n_kv_head'])
    dh = _head_dim(cfg)
    p = 'layer_%d_' % i
    h = _scaled(cfg, h, 'attention_in')
    if wide is not None:
        h = h.astype(w[p + _PREPARED['att_q_w']].dtype)

    def heads(slot, n):
        out = jnp.einsum('btd,nd->btn', h, w[p + _PREPARED[slot]],
                         preferred_element_type=wide)
        return out.reshape(B, T, n, dh).transpose(0, 2, 1, 3)

    q, k, v = heads('att_q_w', H), heads('att_k_w', Hkv), heads('att_v_w',
                                                                Hkv)
    return q, _scaled(cfg, k, 'key'), v


def _ffn(w, cfg, x, i):
    import jax
    p = 'layer_%d_' % i
    hh = _rms(x, w[p + 'ffn_norm'], _eps(cfg))
    gate = jax.nn.silu(_scaled(cfg, hh @ w[p + 'ffn_fc1_w'], 'mlp_gate'))
    out = (gate * (hh @ w[p + 'ffn_fc3_w'])) @ w[p + 'ffn_fc2_w']
    return x + _scaled(cfg, out, 'mlp_down')


def _embed(w, cfg, tokens):
    return _scaled(cfg, w['tok_emb'][tokens], 'embedding')


def _head(w, cfg, x):
    """x [..., D], normalised -> logits [..., V]."""
    return _scaled(cfg, x @ w['lm_proj_w'], 'lm_head')


def _attn_out(w, cfg, att, i):
    return _scaled(cfg, att @ w['layer_%d_att_o_w' % i], 'attention_out')


# -------------------------------------------------- paged read / write

def _quantize_rows(x):
    """x [..., dh] f32 -> (int8 rows, f32 per-row scale).  amax/127
    scaling, eps-clamped so an all-zero row round-trips to zeros."""
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def _write_rows(st, i, pg, rw, k_new, v_new, quant):
    """Scatter per-token K/V rows into layer ``i`` of the pools.

    pg/rw: [N] page ids and in-page rows; k_new/v_new: [N, Hkv, dh]
    float32.  Rows routed to page 0 (masked/invalid targets) are
    write-only garbage — never attended.  Returns the new state dict.
    """
    st = dict(st)
    if quant:
        qk, sk = _quantize_rows(k_new)
        qv, sv = _quantize_rows(v_new)
        st['k'] = st['k'].at[pg, i, rw].set(qk)
        st['v'] = st['v'].at[pg, i, rw].set(qv)
        st['k_scale'] = st['k_scale'].at[pg, i, rw].set(sk)
        st['v_scale'] = st['v_scale'].at[pg, i, rw].set(sv)
    else:
        st['k'] = st['k'].at[pg, i, rw].set(k_new.astype(st['k'].dtype))
        st['v'] = st['v'].at[pg, i, rw].set(v_new.astype(st['v'].dtype))
    return st


def _logical_rows(st, bt, i, cache):
    """Gather layer ``i``'s logical dense rows through the block table.

    bt: [B, max_pages] -> (k, v) each [B, Hkv, max_len, dh].  Unmapped
    entries (0) pull the garbage page — those positions sit at or past
    every live length, so the positional mask already hides them.  int8
    pools are dequantized here; attention math stays float32.
    """
    import jax.numpy as jnp
    B, M = bt.shape
    Hkv, PL, dh = cache.kv_heads, cache.page_len, cache.head_dim

    def assemble(pool, scale):
        rows = pool[bt, i]                     # [B, M, PL, Hkv, dh]
        rows = rows.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, M * PL, dh)
        if scale is None:
            return rows
        sc = scale[bt, i]                      # [B, M, PL, Hkv]
        sc = sc.transpose(0, 3, 1, 2).reshape(B, Hkv, M * PL)
        return rows.astype(jnp.float32) * sc[..., None]

    if cache.quant == 'int8':
        return (assemble(st['k'], st['k_scale']),
                assemble(st['v'], st['v_scale']))
    return assemble(st['k'], None), assemble(st['v'], None)


def _gathered_rows(cache, st, bt):
    """Rows of K (or of V) per layer that one COMPOSED step gathers for
    a window executable built over the structs ``st`` and ``bt``: batch
    x positions of what `_logical_rows` returns for them (shapes only,
    nothing runs); a narrower table changes this count with its
    executable.  The paged step gathers nothing and counts what its
    kernel fetches (`DecodeRuntime._window_rows_read`)."""
    import jax
    k, _v = jax.eval_shape(lambda s, b: _logical_rows(s, b, 0, cache),
                           st, bt)
    return int(k.shape[0]) * int(k.shape[2])


# what a `latent_moe` launch counts on the device and hands back beside
# its tokens: `experts.STATS` summed over its expert layers (and a
# window's steps), then the latent rows it read
_LAUNCH_STATS = _experts.STATS + ('latent_rows_read',)
# (0 for a model that attends through `gqa` layers: what its kernel reads
# is counted on the host, `DecodeRuntime._window_rows_read`)
# and, of a model with `kda` layers, behind them: slot-layers whose matrix
# state a window's steps read and wrote (`DecodeRuntime._count_stats`
# turns them into bytes), tokens a chunk's scan took, slot-layers whose
# convolution tails the steps read and wrote (bytes likewise)
_KDA_STATS = ('kda_state_bytes', 'kda_chunk_tokens', 'kda_tail_bytes')
_KDA_BYTES = {'kda_state_bytes': _kda.state_bytes,
              'kda_tail_bytes': _kda.tail_bytes}


def _launch_stats(cfg):
    """The names of what a ``latent_moe`` launch of this model counts, in
    the order of the array it hands back."""
    return _LAUNCH_STATS + (_KDA_STATS if _state_mixer(cfg) == 'kda' else ())


def _latent_moe_ffn(w, cfg, x, i, valid, experts_kernel):
    """The feed-forward half of layer ``i`` of a ``latent_moe`` block: x
    [T, D] float32 -> (x + the layer's feed-forward, `experts.STATS`);
    ``valid`` [T] marks the tokens that route, ``experts_kernel`` is
    `DecodeRuntime.experts_kernel` (experts.py)."""
    p = 'layer_%d_' % i
    h = _latent.rms(x, w[p + 'ffn_norm'], _eps(cfg))
    if _ffn_kinds(cfg)[i] == 'dense':
        y, stats = _experts.dense_layer(w, p, h)
    else:
        y, stats = _experts.expert_layer(w, p, cfg, h, valid,
                                         experts_kernel)
    return x + y, stats


def _gqa_qkv(w, cfg, h, i, pos, dtype):
    """A ``'gqa'`` layer's q [B, H, T, dh], k and v [B, Hkv, T, dh] from
    its normalised input h [B, T, D] float32 at positions pos [B, T]:
    the dense block's projections (`_qkv`, products in float32), with
    ``cfg['qk_norm']`` an RMS norm over every query and key head
    (``att_q_norm`` / ``att_k_norm``, public order -> the heads'
    rotated-half order), then the rotation (`_rope_at`).  q comes back in
    ``dtype``, the pool's: what `paged_attention` and `cached_attention`
    multiply the rows by; k and v in float32, as `_write_rows` takes
    them."""
    import jax
    import jax.numpy as jnp
    theta, dh = float(cfg['theta']), _head_dim(cfg)
    with jax.named_scope('attn.qkv'):
        q, k, v = _qkv(w, cfg, h, i, wide=jnp.float32)
        if cfg.get('qk_norm'):
            with jax.named_scope('attention.qk_norm'):
                def halves(scale):
                    return _head_rows(scale[:, None], dh, True)[:, 0]
                p = 'layer_%d_' % i
                q = _latent.rms(q, halves(w[p + 'att_q_norm']), _eps(cfg))
                k = _latent.rms(k, halves(w[p + 'att_k_norm']), _eps(cfg))
        q, k = _rope_at(q, pos, theta), _rope_at(k, pos, theta)
    return q.astype(dtype), k, v


def _pool_rows(x, cache):
    """Rows [N, Hkv, dh] of a ``'gqa'`` layer as the pool holds them, [N,
    cache.kv_heads, cache.head_dim]: `paged_pool_heads`' layout, kv heads
    side by side; the same values in the same order."""
    return x.reshape(x.shape[0], cache.kv_heads, cache.head_dim)


def _head_rows_of(rows, dh):
    """`_pool_rows` undone on gathered rows [B, cache.kv_heads, T,
    cache.head_dim] -> [B, Hkv, T, dh] (jax or numpy; the rows
    themselves where the pool's head is the model's)."""
    B, hp, T, wide = rows.shape
    if wide == dh:
        return rows
    return rows.reshape(B, hp, T, wide // dh, dh).transpose(
        0, 1, 3, 2, 4).reshape(B, hp * (wide // dh), T, dh)


def _gqa_prefill(w, cfg, cache, h, i, j, pos, st, pg, rw, bt_row):
    """A ``'gqa'`` layer of a prefill chunk: h [C, D] float32 normalised
    -> (the mixer's output [C, D] float32, the state dict with the
    chunk's rows written into layer ``j`` of the pools): the dense
    block's write, gather and `cached_attention`."""
    import jax
    scope = jax.named_scope
    q, k, v = _gqa_qkv(w, cfg, h[None], i, pos, st['k'].dtype)
    with scope('kv.write'):
        st = _write_rows(st, j, pg, rw,
                         _pool_rows(k[0].transpose(1, 0, 2), cache),
                         _pool_rows(v[0].transpose(1, 0, 2), cache), False)
    with scope('kv.gather'):
        kl, vl = (_head_rows_of(rows, q.shape[-1]) for rows in
                  _logical_rows(st, bt_row[None], j, cache))
    with scope('attn.scores'):
        att = cached_attention(q, kl, vl, pos)            # [1, H, C, dh]
        att = att[0].transpose(1, 0, 2).reshape(h.shape[0], -1)
        return _latent.dot(att, w['layer_%d_att_o_w' % i]), st


def _gqa_step(w, cfg, cache, h, i, j, pos, st, pg, rw, bt, n_attend, paged):
    """A ``'gqa'`` layer of a decode step: h [S, D] float32 normalised ->
    (the mixer's output [S, D] float32, the state dict with every slot's
    row written): in place over the pool with ``paged``
    (`ops.attention.paged_attention`), else the composed gather."""
    import jax
    scope = jax.named_scope
    S = h.shape[0]
    q, k, v = _gqa_qkv(w, cfg, h[:, None], i, pos[:, None], st['k'].dtype)
    with scope('kv.write'):
        st = _write_rows(st, j, pg, rw, _pool_rows(k[:, :, 0, :], cache),
                         _pool_rows(v[:, :, 0, :], cache), False)
    if not paged:
        with scope('kv.gather'):
            kl, vl = (_head_rows_of(rows, q.shape[-1]) for rows in
                      _logical_rows(st, bt, j, cache))
    with scope('attn.scores'):
        if paged:
            att = paged_attention(q[:, :, 0, :], st['k'], st['v'], bt,
                                  n_attend, j)
        else:
            att = cached_attention(q, kl, vl, pos[:, None]).transpose(
                0, 2, 1, 3)
        return _latent.dot(att.reshape(S, -1),
                           w['layer_%d_att_o_w' % i]), st


def _prefill_fn(cfg, cache, chunk, ring_mesh=None, latent_kernel=False,
                experts_kernel=False):
    """Build the one-chunk (or one-shot ring) prefill function.

    Scatters the chunk's K/V rows into the pages ``bt_row`` maps at the
    chunk's absolute positions (invalid tail rows of a short final
    chunk go to the garbage page), attends the chunk queries against
    the gathered logical row, SETS lengths[slot] = offset + true_count,
    samples the would-be next token at its absolute position, and
    stores it in tok[slot].  Only the final chunk's draw (the request's
    FIRST token, the TTFT token) survives.

    A ``latent_moe`` model's layers take their own branch (the layer's
    mixer, `latent.prefill` over the latent pool unless ``cfg['mixer']``
    names another, then the layer's feed-forward kind), carry the
    residual stream in float32, and the function returns a fourth value,
    the chunk's `_LAUNCH_STATS`.  ``latent_kernel``
    (`DecodeRuntime.prefill_kernel`) keeps that attention's scores on
    chip (`ops.attention.latent_prefill`); otherwise its block loop is
    composed of XLA operations.  ``experts_kernel``
    (`DecodeRuntime.experts_kernel`) is `experts.routed`'s.
    """
    import jax.numpy as jnp
    L = int(cfg['n_layer'])
    theta = float(cfg['theta'])
    M, PL = cache.max_pages, cache.page_len
    quant = cache.quant == 'int8'
    recurrent = _recurrent(cfg)
    latent_moe = _latent_moe(cfg)
    dh = _head_dim(cfg)
    if latent_moe:
        mixers, axis = _mixer_kinds(cfg), _layer_axes(cfg)
        n_latent = mixers.count('latent')
        with_kda = 'kda' in mixers

    if ring_mesh is not None:
        if recurrent or latent_moe:
            raise ValueError('ring prefill carries neither recurrent state '
                             'nor a latent pool')
        from ...parallel.ring_attention import ring_attention

    def prefill(w, st, bt_row, tokens, slot, offset, true_count,
                seed, temperature, top_k):
        import jax
        scope = jax.named_scope
        pos = (offset + jnp.arange(chunk))[None]          # [1, C]
        p_abs = offset + jnp.arange(chunk)                # [C]
        valid = jnp.arange(chunk) < true_count
        pg = jnp.where(valid,
                       bt_row[jnp.clip(p_abs // PL, 0, M - 1)], 0)
        rw = p_abs % PL
        with scope('embed'):
            x = _embed(w, cfg, tokens)[None]              # [1, C, D]
        if latent_moe:
            x = x[0].astype(jnp.float32)                  # [C, D]
            stats = jnp.zeros((len(_experts.STATS),), jnp.int32)
        for i in range(L):
            # ONE scope name for every layer: an operation's op_name
            # says which part of the block it is, whatever its index
            with scope('layer'):
                if latent_moe:
                    h = _latent.rms(x, w['layer_%d_att_norm' % i], _eps(cfg))
                    j = axis[i]
                    if mixers[i] == 'kda':
                        # the slot's state as the last chunk left it; a
                        # prompt's first chunk starts from zeros
                        carried = offset > 0
                        att, S, tail = _kda.prefill_mixer(
                            w, 'layer_%d_' % i, cfg, h,
                            jnp.where(carried, st['ssm'][slot, j], 0.0),
                            jnp.where(carried, st['conv'][slot, j], 0.0),
                            true_count)
                        st = dict(st, ssm=st['ssm'].at[slot, j].set(S),
                                  conv=st['conv'].at[slot, j].set(tail))
                    elif mixers[i] == 'conv':
                        # likewise the slot's tail, all the state it has
                        att, tail = _shortconv.prefill_mixer(
                            w, 'layer_%d_' % i, cfg, h,
                            jnp.where(offset > 0, st['conv'][slot, j], 0.0),
                            true_count)
                        st = dict(st, conv=st['conv'].at[slot, j].set(tail))
                    elif mixers[i] == 'gqa':
                        att, st = _gqa_prefill(w, cfg, cache, h, i, j, pos,
                                               st, pg, rw, bt_row)
                    else:
                        att, pool = _latent.prefill(
                            w, 'layer_%d_' % i, cfg, h, p_abs,
                            offset + true_count, st['k'], j, pg, rw, bt_row,
                            latent_kernel)
                        st = dict(st, k=pool)
                    with scope('ffn'):
                        x, counted = _latent_moe_ffn(
                            w, cfg, x + att, i, valid, experts_kernel)
                    stats = stats + counted
                    continue
                with scope('attn.qkv'):
                    h = _rms(x, w['layer_%d_att_norm' % i], _eps(cfg))
                    q, k, v = _qkv(w, cfg, h, i)
                    q = _rope_at(q, pos, theta)
                    k = _rope_at(k, pos, theta)
                with scope('kv.write'):
                    st = _write_rows(st, i, pg, rw, k[0].transpose(1, 0, 2),
                                     v[0].transpose(1, 0, 2), quant)
                if ring_mesh is None:
                    with scope('kv.gather'):
                        kl, vl = _logical_rows(st, bt_row[None], i, cache)
                with scope('attn.scores'):
                    if ring_mesh is not None:
                        # one-shot long-context prefill (offset == 0):
                        # the exact ppermute ring over the whole prompt
                        att = ring_attention(q, k, v, ring_mesh, causal=True)
                    else:
                        att = cached_attention(q, kl, vl, pos)
                    B, H, T = att.shape[0], att.shape[1], att.shape[2]
                    att = att.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
                    x = x + _attn_out(w, cfg, att, i)
                if recurrent:
                    # the slot's state as the last chunk left it; a
                    # prompt's first chunk starts from zeros, whoever
                    # held the slot before
                    carried = offset > 0
                    mix, S, tail = _ssm.prefill_mixer(
                        w, 'layer_%d_' % i, cfg, h[0],
                        jnp.where(carried, st['ssm'][slot, i], 0.0),
                        jnp.where(carried, st['conv'][slot, i], 0.0),
                        true_count)
                    st = dict(st, ssm=st['ssm'].at[slot, i].set(S),
                              conv=st['conv'].at[slot, i].set(tail))
                    x = x + _scaled(cfg, mix[None], 'ssm_out')
                with scope('ffn'):
                    x = _ffn(w, cfg, x, i)
        with scope('lm_head'):
            if latent_moe:
                last = jax.lax.dynamic_slice_in_dim(x, true_count - 1, 1)[0]
                logits = _latent.dot(
                    _latent.rms(last, w['final_norm'], _eps(cfg)),
                    w['lm_proj_w'])                       # [V] float32
            else:
                x = _rms(x, w['final_norm'], _eps(cfg))
                last = jax.lax.dynamic_slice_in_dim(x[0], true_count - 1,
                                                    1)[0]
                logits = _head(w, cfg, last)              # [V]
        new_len = offset + true_count
        with scope('sample'):
            nxt = sample_logits(logits, token_key(seed, new_len),
                                temperature, top_k)
        st = dict(st)
        st['lengths'] = st['lengths'].at[slot].set(new_len)
        st['tok'] = st['tok'].at[slot].set(nxt)
        if latent_moe:
            # the blocks of cached rows the chunk visited, in every layer
            # that attends
            rows = n_latent * _latent.prefill_rows(new_len, M * PL)
            handed = [stats, rows.astype(jnp.int32).reshape(1)]
            if with_kda:
                handed.append(jnp.stack([jnp.int32(0),
                                         true_count.astype(jnp.int32),
                                         jnp.int32(0)]))
            return st, nxt, logits, jnp.concatenate(handed)
        return st, nxt, logits

    return prefill


def _step_fn(cfg, cache, paged, state_kernel, experts_kernel=False):
    """One fused decode/verify step over ALL slots: write the fed token's
    K/V through the block table, attend, sample each slot's next token
    with the position-keyed stream, advance ACTIVE slots only.  Inactive
    slots compute masked garbage routed to page 0.

    ``paged`` (`DecodeRuntime.paged`) attends over the pool in place
    (`ops.attention.paged_attention`): an active slot reads the pages
    its length covers, an inactive one nothing.  Otherwise the composed
    path gathers every slot's logical row first (`_logical_rows` +
    `cached_attention`).

    ``state_kernel`` (`DecodeRuntime.state_kernel`) advances a recurrent
    model's scan state in place over the live slots (`ssm.ssm_step`);
    otherwise every slot's steps and the dead ones' is masked.

    A ``latent_moe`` model's layers take their own branch: `latent.step`
    (absorbed; with ``paged`` over the latent pool in place through
    `ops.attention.latent_attention`), then the layer's feed-forward
    kind, where a slot that rides along routes nowhere; a ``'kda'``
    layer advances the live slots' matrix state and convolution tails
    (`kda.step_mixer`: both in place through `kda.kda_step` with
    ``state_kernel``, else every slot steps and a dead one's are kept), a
    ``'conv'`` layer their
    tails (`shortconv.step_mixer`), a ``'gqa'`` layer attends as the
    dense block does (`_gqa_step`).  Its step returns a third
    value, the step's `_launch_stats`; ``experts_kernel``
    (`DecodeRuntime.experts_kernel`) is `experts.routed`'s."""
    import jax.numpy as jnp
    L = int(cfg['n_layer'])
    theta = float(cfg['theta'])
    M, PL = cache.max_pages, cache.page_len
    quant = cache.quant == 'int8'
    recurrent = _recurrent(cfg)
    latent_moe = _latent_moe(cfg)
    if latent_moe:
        mixers, axis = _mixer_kinds(cfg), _layer_axes(cfg)
        n_latent, n_kda = mixers.count('latent'), mixers.count('kda')

    def step(w, st, bt, fed, active, seeds, temps, topks):
        import jax
        scope = jax.named_scope
        S = bt.shape[0]
        pos = st['lengths']                               # [S] write pos
        pg = bt[jnp.arange(S), jnp.clip(pos // PL, 0, M - 1)]
        pg = jnp.where(active, pg, 0)
        rw = pos % PL
        n_attend = jnp.where(active, pos + 1, 0)          # [S]
        with scope('embed'):
            x = _embed(w, cfg, fed)[:, None, :]           # [S, 1, D]
        if latent_moe:
            x = x[:, 0].astype(jnp.float32)               # [S, D]
            stats = jnp.zeros((len(_experts.STATS),), jnp.int32)
        for i in range(L):
            with scope('layer'):     # one name for every layer (prefill)
                if latent_moe:
                    h = _latent.rms(x, w['layer_%d_att_norm' % i], _eps(cfg))
                    j = axis[i]
                    if mixers[i] == 'kda':
                        # an inactive slot keeps both kinds of state
                        att, matrices, tails = _kda.step_mixer(
                            w, 'layer_%d_' % i, cfg, h, st['ssm'], j,
                            st['conv'], active, state_kernel)
                        st = dict(st, ssm=matrices, conv=tails)
                    elif mixers[i] == 'conv':
                        att, tail = _shortconv.step_mixer(
                            w, 'layer_%d_' % i, cfg, h, st['conv'][:, j],
                            active)
                        st = dict(st, conv=st['conv'].at[:, j].set(tail))
                    elif mixers[i] == 'gqa':
                        att, st = _gqa_step(w, cfg, cache, h, i, j, pos, st,
                                            pg, rw, bt, n_attend, paged)
                    else:
                        att, pool = _latent.step(
                            w, 'layer_%d_' % i, cfg, h, pos, st['k'], j, pg,
                            rw, bt, n_attend, paged)
                        st = dict(st, k=pool)
                    with scope('ffn'):
                        x, counted = _latent_moe_ffn(
                            w, cfg, x + att, i, active, experts_kernel)
                    stats = stats + counted
                    continue
                with scope('attn.qkv'):
                    h = _rms(x, w['layer_%d_att_norm' % i], _eps(cfg))
                    q, k, v = _qkv(w, cfg, h, i)
                    q = _rope_at(q, pos[:, None], theta)
                    k = _rope_at(k, pos[:, None], theta)
                with scope('kv.write'):
                    st = _write_rows(st, i, pg, rw, k[:, :, 0, :],
                                     v[:, :, 0, :], quant)
                if not paged:
                    with scope('kv.gather'):
                        kl, vl = _logical_rows(st, bt, i, cache)
                with scope('attn.scores'):
                    if paged:
                        att = paged_attention(q[:, :, 0, :], st['k'],
                                              st['v'], bt, n_attend, i)
                    else:
                        att = cached_attention(q, kl, vl, pos[:, None])
                        att = att.transpose(0, 2, 1, 3)
                    x = x + _attn_out(w, cfg, att.reshape(S, 1, -1), i)
                if recurrent:
                    # an inactive slot keeps both kinds of state
                    mix, scan_state, tail = _ssm.step_mixer(
                        w, 'layer_%d_' % i, cfg, h[:, 0], st['ssm'], i,
                        st['conv'][:, i], active, state_kernel)
                    st = dict(
                        st, ssm=scan_state,
                        conv=st['conv'].at[:, i].set(jnp.where(
                            active[:, None, None], tail, st['conv'][:, i])))
                    x = x + _scaled(cfg, mix[:, None], 'ssm_out')
                with scope('ffn'):
                    x = _ffn(w, cfg, x, i)
        with scope('lm_head'):
            if latent_moe:
                logits = _latent.dot(
                    _latent.rms(x, w['final_norm'], _eps(cfg)),
                    w['lm_proj_w'])                       # [S, V] float32
            else:
                x = _rms(x, w['final_norm'], _eps(cfg))
                logits = _head(w, cfg, x[:, 0])           # [S, V]
        with scope('sample'):
            nxt = sample_tokens_at(logits, seeds, pos + 1, temps, topks)
        st = dict(st)
        st['tok'] = jnp.where(active, nxt, st['tok'])
        st['lengths'] = jnp.where(active, pos + 1, pos)
        if latent_moe:
            # rows a layer reads: in place, the whole pages a live slot's
            # positions cover (`paged_attention_rows`); gathered, every
            # slot's ``max_len``
            rows = jnp.sum(-(-n_attend // PL) * PL) if paged else S * M * PL
            handed = [stats,
                      jnp.asarray(n_latent * rows, jnp.int32).reshape(1)]
            if n_kda:
                # the kernel moves the live slots' state and tail in every
                # `kda` layer, the composed step every slot's (kda.py)
                moved = jnp.asarray(n_kda * (
                    jnp.sum(active, dtype=jnp.int32) if state_kernel
                    else S), jnp.int32)
                handed.append(jnp.stack([moved, jnp.int32(0), moved]))
            return st, nxt, jnp.concatenate(handed)
        return st, nxt

    return step


def _counted_window(step_body, st, xs, steps):
    """`lax.scan` of a window whose step counts: ``step_body(carry, x)``
    returns (state, tokens [S], `_LAUNCH_STATS`).  Returns (state, tokens
    [S, K], the counts summed over the window)."""
    import jax

    def body(carry, x):
        carry, nxt, stats = step_body(carry, x)
        return carry, (nxt, stats)

    st, (toks, stats) = jax.lax.scan(body, st, xs, length=steps)
    return st, toks.T, stats.sum(axis=0)


def _decode_fn(cfg, cache, steps, paged, state_kernel, experts_kernel=False):
    """K-step fused decode window: each step feeds every slot's own
    carry token.  One `lax.scan`; the state dict is donated carry; the
    block table is closed-over DATA (an ordinary traced argument)."""
    import jax

    step = _step_fn(cfg, cache, paged, state_kernel, experts_kernel)

    if _latent_moe(cfg):
        def window(w, st, bt, active, seeds, temps, topks):
            return _counted_window(
                lambda carry, _: step(w, carry, bt, carry['tok'], active,
                                      seeds, temps, topks), st, None, steps)
        return window

    def window(w, st, bt, active, seeds, temps, topks):
        def body(carry, _):
            carry, nxt = step(w, carry, bt, carry['tok'], active, seeds,
                              temps, topks)
            return carry, nxt
        st, toks = jax.lax.scan(body, st, None, length=steps)
        return st, toks.T                                 # [S, K]

    return window


def _verify_fn(cfg, cache, steps, paged, state_kernel, experts_kernel=False):
    """K-step speculative VERIFY window: identical step body, but step j
    feeds ``fed[j]`` (host-built: last emitted token, then the draft's
    proposals) and the returned samples are the target model's verdicts
    g_j at each position.  Same `(seed, position)` sampling as decode —
    an accepted prefix is bitwise the sequential stream."""
    import jax

    step = _step_fn(cfg, cache, paged, state_kernel, experts_kernel)

    if _latent_moe(cfg):
        def window(w, st, bt, fed, active, seeds, temps, topks):
            return _counted_window(
                lambda carry, fed_t: step(w, carry, bt, fed_t, active, seeds,
                                          temps, topks), st, fed, steps)
        return window

    def window(w, st, bt, fed, active, seeds, temps, topks):
        def body(carry, fed_t):
            carry, nxt = step(w, carry, bt, fed_t, active, seeds, temps,
                              topks)
            return carry, nxt
        st, toks = jax.lax.scan(body, st, fed)            # fed: [K, S]
        return st, toks.T                                 # [S, K]

    return window


def _interleaved_rope(x, pos, theta):
    """The rotation in the PUBLIC order, as the model defines it: pairs
    ``(x[2i], x[2i + 1])`` of x [B, h, T, dh] turn by
    ``pos * theta^(-2i/dh)`` (`dense_reference` only; the executables
    rotate halves, `_rope_at`)."""
    import jax.numpy as jnp
    cos, sin = _rope_angles(pos, x.shape[-1], theta)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def dense_reference(weights, cfg, prompt):
    """Independent prefill reference: ordinary dense causal attention
    over the whole prompt — no cache pages, no positional masking, no
    chunking (an intentionally different code path from
    `cached_attention`), and the RAW weights in the public order under
    the interleaved rotation: nothing of `_prepare_qkv`, which it
    thereby checks.  Returns (k [L, Hkv, P, dh], v, last-position
    logits [V]) for the parity tests."""
    import jax
    import jax.numpy as jnp
    w = {n: jnp.asarray(weights[n]) for n in weight_names(cfg)}
    L = int(cfg['n_layer'])
    theta = float(cfg['theta'])
    H, Hkv, dh = int(cfg['n_head']), int(cfg['n_kv_head']), _head_dim(cfg)
    P = int(np.asarray(prompt).shape[-1])
    pos = jnp.arange(P)[None]
    x = w['tok_emb'][jnp.asarray(prompt, jnp.int32).reshape(1, P)]
    ks, vs = [], []
    for i in range(L):
        h = _rms(x, w['layer_%d_att_norm' % i], _eps(cfg))

        def heads(slot, n):
            out = h @ w['layer_%d_%s' % (i, slot)]
            return out.reshape(1, P, n, dh).transpose(0, 2, 1, 3)

        q = _interleaved_rope(heads('att_q_w', H), pos, theta)
        k = _interleaved_rope(heads('att_k_w', Hkv), pos, theta)
        v = heads('att_v_w', Hkv)
        ks.append(k[0])
        vs.append(v[0])
        qg = q.reshape(1, Hkv, H // Hkv, P, dh)
        s = jnp.einsum('bhgqd,bhkd->bhgqk', qg, k,
                       preferred_element_type=jnp.float32) * (dh ** -0.5)
        mask = jnp.tril(jnp.ones((P, P), bool))
        s = jnp.where(mask[None, None, None], s, -1e30)
        att = jnp.einsum('bhgqk,bhkd->bhgqd', jax.nn.softmax(s, axis=-1), v,
                         preferred_element_type=jnp.float32)
        att = att.reshape(1, H, P, dh).transpose(0, 2, 1, 3)
        x = x + att.reshape(1, P, H * dh) @ w['layer_%d_att_o_w' % i]
        x = _ffn(w, cfg, x, i)
    x = _rms(x, w['final_norm'], _eps(cfg))
    logits = x[0, P - 1] @ w['lm_proj_w']
    return (np.asarray(jnp.stack(ks)), np.asarray(jnp.stack(vs)),
            np.asarray(logits))


class _Pending(np.lib.mixins.NDArrayOperatorsMixin):
    """What a launch returned, still on the device.  It stands in for
    the array: ``[i]``, ``int()``, ``len()``, iteration, `np.asarray`,
    arithmetic, comparisons and any ndarray attribute read it; the
    first of them waits for the launch, and the runtime times that wait
    as the launch's fetch (`DecodeRuntime._pending`).  Never read, it
    costs no transfer.  `landed` says, without waiting, whether the
    launch had ended before anyone came to read it."""
    __slots__ = ('_land', '_host', '_dev', '_landed')

    def __init__(self, land, dev=None):
        self._land, self._host, self._dev, self._landed = land, None, dev, None

    def read(self):
        if self._land is not None:
            self._host, self._land, self._dev = self._land(), None, None
        return self._host

    def landed(self):
        """Whether the result was on hand already when this was first
        asked: one `is_ready()` of the device array, before the read,
        and the answer kept (asked after the read: yes)."""
        if self._landed is None:
            self._landed = self._dev is None or bool(self._dev.is_ready())
        return self._landed

    def __array__(self, dtype=None, copy=None):
        out = self.read()
        return out if dtype is None else out.astype(dtype)

    def __getitem__(self, i):
        return self.read()[i]

    def __int__(self):
        return int(self.read())

    __index__ = __int__

    def __len__(self):
        return len(self.read())

    def __iter__(self):
        return iter(self.read())

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        # arithmetic and comparisons (NDArrayOperatorsMixin), on the host
        inputs = [x.read() if isinstance(x, _Pending) else x for x in inputs]
        return getattr(ufunc, method)(*inputs, **kwargs)

    def __getattr__(self, name):
        return getattr(self.read(), name)


class DecodeRuntime(object):
    """The device half of the streaming decode server: the paged KV
    pool + block tables + AOT prefill/decode/verify executables over one
    weight set.

    ``weights`` maps llama parameter names to arrays (a trained scope
    via models.llama.generation_weights, or `random_weights` for tests);
    ``cfg`` is the model config dict.  ``mesh`` (optional, with a >1
    ``seq`` axis) enables one-shot ring prefill for prompts of at least
    ``ring_min_len`` tokens.

    Paging knobs: ``page_len`` (default: largest divisor of max_len
    <= 8), ``pages`` (pool depth incl. the garbage page; default =
    dense-equivalent capacity), ``kv_quant`` ('none'/'int8'),
    ``prefix_cache`` (default on).  A slot is a batch row; PAGES are the memory: admission goes
    through `try_begin` (prefix-cache match + all-or-nothing page
    claim) and per-window `ensure_capacity`, both of which report
    shortage as a clean False/None the scheduler turns into
    backpressure or a terminal ``kv_oom``.

    A model that carries recurrent state (``block: 'falcon_h1'``, or a
    ``latent_moe`` model with ``'kda'`` or ``'conv'`` layers;
    `recurrent`) runs
    WITHOUT the prefix cache whatever
    ``prefix_cache`` says (a hit would skip tokens the scan state never
    saw), and refuses speculative windows and ring prefill.  A
    ``latent_moe`` model (`latent_moe`) keeps the prefix cache (its
    pages hold latent rows, shared like any other) and speculative
    windows (unless it is recurrent), and refuses ring prefill and
    ``kv_quant='int8'``.
    """

    def __init__(self, weights, cfg, slots=4, prefill_chunk=8,
                 cache_dtype='float32', mesh=None, ring_min_len=None,
                 page_len=None, pages=None, kv_quant='none',
                 prefix_cache=True):
        # weights adopted, pool and recurrent state allocated, the
        # composed path's rows read off the shapes: one phase of set-up
        with _obs.span('decode.init', cat='build', slots=int(slots),
                       counter='generation.init_s') as init:
            import jax
            self.cfg = dict(cfg)
            # what the executables read (q, k and v prepared, once: a
            # jitted call a layer, from JAX's cache on a warm start), and
            # the public face over the same arrays
            _cc.ensure_xla_cache_backstop()
            self.params = _params_from(weights, cfg)
            self.w = _PublicWeights(self.params, cfg)
            made = jax.block_until_ready(_prepared_arrays(self.params, cfg))
            made_bytes = sum(int(a.nbytes) for a in made)
            init.args.update(prepared=len(made), prepared_bytes=made_bytes)
            _obs.metrics.gauge('generation.prepared_weight_bytes').set(
                made_bytes)
            self.recurrent = _recurrent(cfg)
            self.latent_moe = _latent_moe(cfg)
            if self.latent_moe:
                _ffn_kinds(cfg)
                # the pool's layers are those that attend, the state's
                # those that hold one (`_mixer_kinds`)
                mixers = _mixer_kinds(cfg)
                attend = sum(k in _ATTENDING for k in mixers)
                if _attending_mixer(cfg) == 'gqa':
                    # the dense block's K and V pools, a narrow head's kv
                    # heads side by side in a row (`paged_pool_heads`)
                    heads, width = paged_pool_heads(int(cfg['n_kv_head']),
                                                    _head_dim(cfg))
                    geometry = dict(kv_heads=heads, head_dim=width,
                                    layers=attend)
                else:
                    # the second pool geometry: one row a token a layer
                    lat = cfg['latent']
                    geometry = dict(kv_heads=1,
                                    head_dim=_latent.stored_width(lat),
                                    latent=int(lat['kv_rank']),
                                    layers=attend)
                if self.recurrent:
                    geometry.update(
                        recurrent=(
                            _kda.state_shapes(cfg['kda'])
                            if _state_mixer(cfg) == 'kda' else
                            _shortconv.state_shapes(int(cfg['d_model']),
                                                    cfg['conv'])),
                        recurrent_layers=int(cfg['n_layer']) - attend)
            else:
                geometry = dict(kv_heads=int(cfg['n_kv_head']),
                                head_dim=_head_dim(cfg),
                                layers=int(cfg['n_layer']),
                                recurrent=(_ssm.state_shapes(cfg['ssm'])
                                           if self.recurrent else None))
            self.cache = CacheConfig(
                slots=slots, max_len=int(cfg['max_len']), dtype=cache_dtype,
                page_len=page_len, pages=pages, quant=kv_quant, **geometry)
            self.allocator = SlotAllocator(self.cache.slots)
            self.pool = PagePool(self.cache)
            # recurrent state cannot be shared between prompts: no prefix
            # cache, and every begin that forgoes one is counted
            self._prefix_refused = bool(prefix_cache) and self.recurrent
            self.prefix = (PrefixCache(self.pool, self.cache.page_len)
                           if prefix_cache and not self.recurrent else None)
            S = self.cache.slots
            self.block_tables = np.zeros((S, self.cache.max_pages), np.int32)
            self.owned = [[] for _ in range(S)]
            self.host_len = np.zeros(S, np.int32)
            self.host_tok = np.zeros(S, np.int32)
            self.state = init_state(self.cache)
            self.prefill_chunk = int(prefill_chunk)
            if not 0 < self.prefill_chunk <= self.cache.max_len:
                raise ValueError('prefill_chunk must be in (0, max_len]')
            self.mesh = mesh
            self.ring_min_len = (int(ring_min_len) if ring_min_len is not None
                                 else 2 * self.prefill_chunk)
            # the decode step attends over the pool in place where the
            # kernel can run (a floating pool, one device); an int8 pool and
            # a mesh of several devices keep the composed gather
            if self.cache.latent is not None:
                self.paged = latent_attention_eligible(
                    self.cache.pool_shape, self.cache.store_dtype,
                    self.cache.latent, mesh)
            else:
                self.paged = paged_attention_eligible(
                    self.cache.pool_shape, self.cache.store_dtype, mesh)
            # likewise the scan state (the matrix state) of a recurrent
            # model: in place over the live slots where its kernel can run
            # (float32, one device)
            # (a model whose state is convolution tails alone has none)
            self.state_kernel = 'ssm' in self.state and (
                _kda.kda_step_eligible(
                    self.state['ssm'].shape, self.state['conv'].shape,
                    self.state['ssm'].dtype, mesh) if self.latent_moe
                else _ssm.ssm_step_eligible(
                    self.state['ssm'].shape, self.state['ssm'].dtype, mesh))
            # and a latent chunk's scores: on chip where that kernel
            # can run, else through HBM a block at a time
            self.prefill_kernel = self.cache.latent is not None \
                and _latent.prefill_kernel(
                    cfg, self.cache, self.prefill_chunk, mesh)
            # and the grouped route of its routed experts: only the
            # matrices of the experts with rows, by a kernel, else by
            # `ragged_dot`
            self.experts_kernel = self.latent_moe and 'moe' in cfg \
                and _experts.gmm_eligible(_experts.weight_shapes(
                    int(cfg['d_model']), cfg['moe'])['moe_fc1_w'], mesh)
            self._execs = {}
            # `latent_moe` launches' `_LAUNCH_STATS`, still on the device,
            # oldest first, and how many launches' were already moved into
            # the counters: that happens behind the next read of a launch
            # that came after them (`_count_stats`)
            self._stats, self._counted = [], 0
            self._stat_names = _launch_stats(cfg) if self.latent_moe else ()
            # arguments uploaded ahead of their launch: {'prefill' | 'window':
            # [(host copy, device array), ...]} (`stage_prefill`, `stage_window`)
            self._staged = {}
            # where the latest launch's `dispatch` span ended, on that
            # span's clock (None with telemetry off): the scheduler ends
            # its boundary there without reading the clock again
            self.dispatched_at = None
            # rows of K (or V) per layer one COMPOSED step gathers
            self._gathered = (
                None if self.paged
                else self.cache.slots * self.cache.max_len if self.latent_moe
                else _gathered_rows(self.cache, self._state_structs(),
                                    self._bt_struct(self.cache.slots)))
            self._lock = threading.Lock()
            _obs.metrics.gauge('generation.kv_cache_bytes').set(
                self.cache.bytes())
            _obs.metrics.gauge('generation.recurrent_state_bytes').set(
                self.cache.recurrent_bytes())

    # ------------------------------------------------------- geometry
    @property
    def slots(self):
        return self.cache.slots

    @property
    def max_len(self):
        return self.cache.max_len

    def free_slots(self):
        return self.allocator.free_count()

    def alloc_slot(self):
        return self.allocator.alloc()

    def free_slot(self, slot):
        """Retire a slot: release every page its block table maps (a
        shared prefix page survives in the cache / other streams) and
        unmap the row.  Pages are never zeroed — positional masking
        keeps stale rows unreachable — and neither is recurrent state:
        the next prompt's first chunk starts from zeros."""
        slot = int(slot)
        pages, self.owned[slot] = self.owned[slot], []
        if pages:
            self.pool.release(pages)
        self.block_tables[slot] = 0
        self.host_len[slot] = 0
        self.host_tok[slot] = 0
        self.allocator.free(slot)

    def reset(self):
        """Fresh state + allocators (the weights and warm executables
        stay)."""
        for s in range(self.cache.slots):
            self.owned[s] = []
        if self.prefix is not None:
            self.prefix.reset()
        self.allocator.reset()
        self.pool.reset()
        self.block_tables[:] = 0
        self.host_len[:] = 0
        self.host_tok[:] = 0
        self._staged.clear()
        self._count_stats(self._counted + len(self._stats))
        self.state = init_state(self.cache)

    # ------------------------------------------------ page accounting
    def never_fits(self, prompt_len, max_new):
        """True when prompt+max_new could not run even on an idle pool —
        the admission-time terminal ``kv_oom``."""
        span = min(int(prompt_len) + int(max_new), self.cache.max_len)
        return self.cache.pages_for(span) > self.pool.capacity

    def try_begin(self, slot, prompt, window):
        """Claim pages for ``prompt`` plus one decode window on
        ``slot``: longest shared-prefix match first (those pages are
        mapped read-only — full by construction, so the request's own
        writes start in its first fresh page), then an all-or-nothing
        claim of the remainder.  Returns the PREFILL START OFFSET
        (matched tokens are skipped), or None on page shortage with
        nothing leaked — the scheduler's backpressure signal."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        span = min(prompt.size + max(1, int(window)), self.cache.max_len)
        need = self.cache.pages_for(span)
        if self._prefix_refused:
            _obs.metrics.counter('generation.prefix_refused_recurrent').inc()
        matched = self.prefix.match(prompt) if self.prefix is not None else []
        evict = self.prefix.evict_one if self.prefix is not None else None
        fresh = self.pool.alloc(max(0, need - len(matched)), evict=evict)
        if fresh is None:
            if matched:
                self.pool.release(matched)
            return None
        pages = list(matched) + list(fresh)
        self.owned[slot] = pages
        self.block_tables[slot] = 0
        self.block_tables[slot, :len(pages)] = pages
        self.host_len[slot] = 0
        self.host_tok[slot] = 0
        return len(matched) * self.cache.page_len

    def ensure_capacity(self, slot, target_len):
        """Grow ``slot``'s block table to cover ``target_len`` tokens.
        True when already covered or grown; False on pool exhaustion
        (mid-stream ``kv_oom`` — the caller retires the stream with a
        terminal reply, never truncates silently)."""
        slot = int(slot)
        need = self.cache.pages_for(min(int(target_len),
                                        self.cache.max_len))
        have = len(self.owned[slot])
        if need <= have:
            return True
        evict = self.prefix.evict_one if self.prefix is not None else None
        fresh = self.pool.alloc(need - have, evict=evict)
        if fresh is None:
            return False
        self.owned[slot].extend(fresh)
        self.block_tables[slot, have:need] = fresh
        return True

    def promote_prefix(self, slot, prompt):
        """Publish a freshly-prefilled prompt's full pages into the
        prefix cache (no-op when prefix caching is off)."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        full = prompt.size // self.cache.page_len
        return self.prefix.insert(prompt, self.owned[int(slot)][:full])

    def pool_snapshot(self):
        """Host-side pool gauges (flight-dump payload on kv_oom/breaker
        trips)."""
        return {'pages_capacity': self.pool.capacity,
                'pages_in_use': self.pool.in_use(),
                'page_bytes': self.pool.page_bytes,
                'bytes_reserved': self.cache.bytes(),
                'recurrent_state_bytes': self.cache.recurrent_bytes(),
                'bytes_live': self.pool.in_use() * self.pool.page_bytes,
                'prefix_entries': (len(self.prefix)
                                   if self.prefix is not None else 0),
                'slots_in_use': self.allocator.in_use()}

    # ---------------------------------------------------------- AOT
    def _param_specs(self):
        return {n: (tuple(a.shape), str(a.dtype))
                for n, a in self.params.items()}

    def _param_structs(self):
        """Arg structs of the executables' first argument."""
        return {n: self._sds(a.shape, a.dtype)
                for n, a in self.params.items()}

    def _compiled(self, key, build):
        """One executable per (kind, shape) key: AOT-lowered, donated
        state, persisted through the compile-cache disk tier so a fresh
        process warm-starts the decode loop without compiling.  A key
        not yet in `_execs` is the phase ``decode.compile``, whose
        children (fingerprint, disk load, trace + compile, store) each
        move a seconds counter."""
        with self._lock:
            call = self._execs.get(key)
        if call is not None:
            return call
        obs_on = _obs.enabled()
        with _obs.span('decode.compile', cat='compile', fn=key[0],
                       shape=list(key[1:])) as comp:
            with _obs.span('compile_cache.fingerprint', cat='compile',
                           counter='compile_cache.fingerprint_s'):
                _cc.ensure_xla_cache_backstop()
                spec = {'fn': key[0], 'shape': list(key[1:]),
                        'cfg': self.cfg, 'cache': self.cache.spec(),
                        'mesh': _cc._mesh_blob(self.mesh)
                        if key[0].endswith('ring') else None}
                fp = _cc.callable_fingerprint(
                    'generation', spec, param_specs=self._param_specs())
            call = None
            if _cc.disk_enabled():
                with _obs.span('decode.aot_load', cat='compile') as load:
                    call, _tier = _cc.disk_cache().load(fp)
            if obs_on:
                hit = call is not None
                comp.args['verdict'] = 'disk_hit' if hit else 'compiled'
                if _cc.disk_enabled():
                    _obs.metrics.counter(
                        'compile_cache.disk_hits' if hit
                        else 'compile_cache.disk_misses').inc()
                if hit:
                    _obs.metrics.counter('compile_cache.load_s').inc(
                        load.seconds)
            if call is None:
                with _obs.span('decode.trace_compile', cat='compile',
                               counter='generation.compile_s'):
                    jitted, args = build()
                    lowered = jitted.lower(*args)
                    call = lowered.compile()
                if obs_on:
                    _obs.metrics.counter('generation.compiles').inc()
                if _cc.disk_enabled():
                    with _obs.span('compile_cache.store', cat='compile',
                                   counter='compile_cache.store_s'):
                        _cc.disk_cache().store(
                            fp, compiled=call, lowered=lowered,
                            meta={'kind': 'generation', 'fn': key[0]})
            with self._lock:
                self._execs[key] = call
        return call

    def _sds(self, shape, dtype):
        """Arg struct for AOT lowering.  With a mesh every executable is
        compiled for REPLICATED NamedSharding state, so the ring-prefill
        and decode executables hand the donated cache back and forth
        without a resharding mismatch."""
        import jax
        if self.mesh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(self.mesh,
                                                 PartitionSpec()))

    def _state_structs(self):
        return {n: self._sds(a.shape, a.dtype)
                for n, a in self.state.items()}

    def _bt_struct(self, rows):
        import jax
        return self._sds((rows, self.cache.max_pages), jax.numpy.int32)

    def _prefill_exec(self, chunk, ring=False):
        import jax

        def build():
            fn = _prefill_fn(self.cfg, self.cache, chunk,
                             ring_mesh=self.mesh if ring else None,
                             latent_kernel=self.prefill_kernel,
                             experts_kernel=self.experts_kernel)
            jitted = jax.jit(fn, donate_argnums=(1,))
            i32 = self._sds((), jax.numpy.int32)
            f32 = self._sds((), jax.numpy.float32)
            toks = self._sds((chunk,), jax.numpy.int32)
            bt_row = self._sds((self.cache.max_pages,), jax.numpy.int32)
            args = [self._param_structs(), self._state_structs(), bt_row,
                    toks, i32, i32, i32, i32, f32, i32]
            return jitted, args

        return self._compiled(('prefill_ring' if ring else 'prefill',
                               chunk), build)

    def _window_exec(self, kind, steps):
        import jax
        if kind == 'verify' and self.recurrent:
            raise ValueError(
                'speculative decode rolls lengths back; recurrent state '
                'cannot be rolled back')

        def build():
            make = _verify_fn if kind == 'verify' else _decode_fn
            fn = make(self.cfg, self.cache, steps, self.paged,
                      self.state_kernel, self.experts_kernel)
            jitted = jax.jit(fn, donate_argnums=(1,))
            S = self.cache.slots
            vec = lambda dt: self._sds((S,), dt)  # noqa: E731
            args = [self._param_structs(), self._state_structs(),
                    self._bt_struct(S)]
            if kind == 'verify':
                args.append(self._sds((steps, S), jax.numpy.int32))
            args += [vec(jax.numpy.bool_), vec(jax.numpy.int32),
                     vec(jax.numpy.float32), vec(jax.numpy.int32)]
            return jitted, args

        return self._compiled((kind, steps), build)

    def _window_rows_read(self, steps, act):
        """Rows of K (or of V) per layer that one ``steps``-step window
        over the active slots ``act`` reads (`generation.kv_rows_read`).
        Paged: what the kernel fetches, step j of an active slot the
        whole pages its len + j + 1 positions cover, an inactive slot
        nothing (`paged_attention_rows`).  Composed: every slot's
        ``max_len`` rows a step whoever is live, taken from the shapes
        the executable was built over (`_gathered_rows`)."""
        if self.paged:
            lens = self.host_len[act].astype(np.int64)[:, None]
            return paged_attention_rows(lens + np.arange(1, steps + 1),
                                        self.cache.page_len)
        return steps * self._gathered

    def _decode_exec(self, steps):
        return self._window_exec('decode', steps)

    def _verify_exec(self, steps):
        return self._window_exec('verify', steps)

    def warmup(self, steps=None, speculative=False):
        """Compile (or disk-load) the steady-state executables up front
        so the first request pays no compile latency.  With
        ``speculative`` the verify window is warmed too."""
        with _obs.span('decode.warmup', cat='compile',
                       counter='generation.warmup_s'):
            self._prefill_exec(self.prefill_chunk)
            if steps:
                self._decode_exec(int(steps))
                if speculative:
                    self._verify_exec(int(steps))

    # ------------------------------------------------------ launching
    # A launch is upload -> dispatch, and returns what the executable
    # returned, still on the device (`_Pending`): whoever reads it pays
    # the wait, which is timed here as that launch's fetch.  `stage_*`
    # makes the upload ahead of the launch, while another one runs.
    def _put(self, values):
        """[(host copy, device array)] of numpy ``values``.  The device
        array is made from a copy nobody else holds: the caller's array
        (a row of `block_tables`, say) changes while the launch is in
        flight, and on the CPU `jax.device_put` may alias its memory."""
        import jax
        hosts = [np.array(v, order='C') for v in values]
        return list(zip(hosts, jax.device_put(hosts)))

    def _uploaded(self, kind, values):
        """The device copies of one launch's arguments: what `stage_*`
        left for this ``kind`` of launch wherever its bytes are these
        ``values``' (the block table among them), a fresh upload of the
        others.  Whatever was staged is spent either way."""
        staged = self._staged.pop(kind, None)
        if staged is None or len(staged) != len(values):
            staged = [None] * len(values)
        args = [held[1] if held is not None and np.array_equal(held[0], v)
                else None for held, v in zip(staged, values)]
        fresh = [i for i, a in enumerate(args) if a is None]
        for i, (_, dev) in zip(fresh, self._put([values[i] for i in fresh])):
            args[i] = dev
        if _obs.enabled():
            _obs.metrics.counter('generation.launches').inc()
            if not fresh:
                _obs.metrics.counter('generation.launches_staged').inc()
        return args

    def _stage(self, kind, values):
        with _obs.span('decode.%s.upload' % kind, cat='decode') as sp:
            self._staged[kind] = self._put(values)
        if _obs.enabled():
            # the upload's time belongs to the launch it is made for
            _obs.metrics.counter('generation.%s_s' % kind).inc(sp.seconds)

    def _pending(self, kind, dev, then=None):
        """``dev`` as a `_Pending` whose read is its launch's fetch: a
        `decode.<kind>.fetch` span whose seconds go to
        `generation.<kind>_s` and `generation.<kind>_fetch_s` wherever
        the read happens; ``then(host array)`` follows it."""
        # the launches made so far: theirs have landed when this one has
        upto = self._counted + len(self._stats)

        def land():
            with _obs.span('decode.%s.fetch' % kind, cat='decode') as fetch:
                out = np.asarray(dev)
            if _obs.enabled():
                counter = _obs.metrics.counter
                counter('generation.%s_s' % kind).inc(fetch.seconds)
                counter('generation.%s_fetch_s' % kind).inc(fetch.seconds)
            if upto > self._counted:
                self._count_stats(upto)
            if then is not None:
                then(out)
            return out
        return _Pending(land, dev)

    def _count_stats(self, upto):
        """Move the `_launch_stats` of the first ``upto`` launches of this
        runtime (those made up to the one whose result was just read: the
        device runs launches in order, so theirs have landed with it)
        into ``generation.<stat>``, and a decode window's also into
        ``generation.window_<stat>`` (what a step's roofline needs apart
        from the chunks').  No wait and no launch: the few bytes came
        over beside the tokens (`copy_to_host_async`)."""
        n = upto - self._counted
        mine, self._stats = self._stats[:n], self._stats[n:]
        self._counted = upto
        if not _obs.enabled():
            return
        for kind, stats in mine:
            for name, n in zip(self._stat_names, np.asarray(stats)):
                if name in _KDA_BYTES:
                    # counted in slot-layers: each read once, written once
                    n = int(n) * 2 * _KDA_BYTES[name](self.cfg['kda'])
                _obs.metrics.counter('generation.' + name).inc(int(n))
                if kind == 'window':
                    _obs.metrics.counter(
                        'generation.window_' + name).inc(int(n))

    def _launched_stats(self, kind, stats):
        """Keep a launch's `_LAUNCH_STATS` (on the device) until a read
        behind it moves them into the counters."""
        stats.copy_to_host_async()
        self._stats.append((kind, stats))

    # -------------------------------------------------------- prefill
    def _chunk(self, tokens, offset):
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if not 0 < n <= self.prefill_chunk:
            raise ValueError('chunk of %d tokens does not fit the %d-wide '
                             'prefill executable' % (n, self.prefill_chunk))
        if offset + n > self.cache.max_len:
            raise ValueError('prefill past max_len=%d' % self.cache.max_len)
        return tokens

    def _prefill_values(self, width, slot, tokens, offset, params):
        """What a prefill launch uploads, in the executable's order:
        the slot's block-table row, the chunk padded to ``width``, and
        six scalars."""
        buf = np.zeros(width, np.int32)
        buf[:tokens.shape[0]] = tokens
        return [self.block_tables[slot], buf, np.int32(slot),
                np.int32(offset), np.int32(tokens.shape[0]),
                np.int32(params.seed), np.float32(params.temperature),
                np.int32(params.top_k)]

    def stage_prefill(self, slot, tokens, offset, params):
        """Upload now what `prefill` with these arguments will launch
        with, so that the launch itself is the dispatch alone.  The
        staged copies are used only if the arguments and the slot's
        block-table row still read the same at the launch."""
        self._stage('prefill', self._prefill_values(
            self.prefill_chunk, slot, self._chunk(tokens, offset), offset,
            params))

    def prefill(self, slot, tokens, offset, params):
        """Launch ONE prefill chunk for ``slot``: tokens[offset:offset+C]
        of the prompt (the final chunk may be short — it is padded to
        the chunk width and masked by ``true_count``).  Returns
        (next_token, logits) — meaningful only on the final chunk, and
        still on the device (`_Pending`): reading one waits for the
        chunk.  ``params`` is a SamplingParams.  The slot's block table
        must already cover the chunk (`try_begin`/`ensure_capacity`)."""
        return self._launch_prefill(self._prefill_exec(self.prefill_chunk),
                                    self.prefill_chunk, slot,
                                    self._chunk(tokens, offset), offset,
                                    params, ring=False)

    def _launch_prefill(self, call, width, slot, tokens, offset, params,
                        ring):
        """Pad ``tokens`` to the executable's ``width``, upload (or take
        what was staged), launch: one `decode.prefill` span with
        `upload` / `dispatch` children, and the prefill counters (time,
        real and padding tokens).  `host_len` moves here, `host_tok`
        when the sample is read."""
        n = tokens.shape[0]
        with _obs.span('decode.prefill', cat='decode', slot=int(slot),
                       tokens=int(n), ring=ring) as sp:
            with _obs.span('decode.prefill.upload', cat='decode'):
                args = self._uploaded('prefill', self._prefill_values(
                    width, slot, tokens, offset, params))
            with _obs.span('decode.prefill.dispatch', cat='decode') as sent:
                st, nxt, logits, *stats = call(self.params, self.state,
                                               *args)
                self.state = st
                nxt.copy_to_host_async()
                if stats:
                    self._launched_stats('prefill', stats[0])
            self.dispatched_at = sent.t1
        self.host_len[slot] = offset + n
        if _obs.enabled():
            counter = _obs.metrics.counter
            counter('generation.prefill_s').inc(sp.seconds)
            counter('generation.prefill_tokens').inc(n)
            counter('generation.prefill_pad_tokens').inc(width - n)
            if self.recurrent and offset == 0:
                counter('generation.state_resets').inc()

        def sampled(out):
            self.host_tok[slot] = out

        return (self._pending('prefill', nxt, sampled),
                self._pending('prefill', logits))

    def ring_pad(self, n):
        """Padded one-shot ring prefill width for an n-token prompt:
        the next multiple of prefill_chunk (also a multiple of the ring
        size when prefill_chunk is)."""
        c = self.prefill_chunk
        return min(((int(n) + c - 1) // c) * c, self.cache.max_len)

    def prefill_ring(self, slot, prompt, params):
        """One-shot long-context prefill through ring attention: the
        whole (padded) prompt in a single launch, read at once.
        Requires ``mesh``."""
        if self.mesh is None:
            raise ValueError('ring prefill needs a mesh with a seq axis')
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = prompt.shape[0]
        width = self.ring_pad(n)
        if n > width:
            raise ValueError('prompt of %d exceeds max_len=%d'
                             % (n, self.cache.max_len))
        nxt, logits = self._launch_prefill(
            self._prefill_exec(width, ring=True), width, slot, prompt, 0,
            params, ring=True)
        return int(nxt), logits.read()

    # --------------------------------------------------------- decode
    def _window_values(self, fed, active, seeds, temps, topks):
        """What a window launch uploads, in the executable's order: the
        block table, a verify window's fed rows, the per-slot vectors."""
        S = self.cache.slots
        return ([self.block_tables] + ([] if fed is None else [fed.T])
                + [np.asarray(active, bool).reshape(S),
                   np.asarray(seeds, np.int32).reshape(S),
                   np.asarray(temps, np.float32).reshape(S),
                   np.asarray(topks, np.int32).reshape(S)])

    def stage_window(self, active, seeds, temps, topks):
        """Upload now what `decode_window` with these vectors will
        launch with.  Each staged copy is used only if it still reads
        the same at the launch: a changed ``active`` (a stream ended
        that nobody foresaw) is uploaded again, alone."""
        self._stage('window', self._window_values(None, active, seeds,
                                                  temps, topks))

    def _launch_window(self, kind, steps, fed, active, seeds, temps, topks):
        """Upload the per-slot vectors (or take what was staged), launch
        one K-step window executable: one `decode.window` span with
        `upload` / `dispatch` children, and the window counters (time,
        slot-steps run and live, KV positions live streams attended and
        rows read).  Returns (active slots, the [slots, steps] samples
        on the device)."""
        steps = int(steps)
        call = self._window_exec(kind, steps)
        values = self._window_values(fed, active, seeds, temps, topks)
        act = values[-4].copy()
        with _obs.span('decode.window', cat='decode', kind=kind,
                       steps=steps) as sp:
            with _obs.span('decode.window.upload', cat='decode'):
                args = self._uploaded('window', values)
            with _obs.span('decode.window.dispatch', cat='decode') as sent:
                st, toks, *stats = call(self.params, self.state, *args)
                self.state = st
                toks.copy_to_host_async()
                if stats:
                    self._launched_stats('window', stats[0])
            self.dispatched_at = sent.t1
        if _obs.enabled():
            live = int(act.sum())
            counter = _obs.metrics.counter
            counter('generation.window_s').inc(sp.seconds)
            counter('generation.decode_slot_steps').inc(
                self.cache.slots * steps)
            counter('generation.decode_live_slot_steps').inc(live * steps)
            # step j of a live stream attends its len + j + 1 positions
            counter('generation.kv_tokens_live').inc(
                steps * int(self.host_len[act].sum(dtype=np.int64))
                + live * steps * (steps + 1) // 2)
            counter('generation.kv_rows_read').inc(
                self._window_rows_read(steps, act))
            if self.recurrent:
                # slot-steps whose scan state the window touched: the
                # kernel's live slots; the composed step reads and
                # writes every slot's state
                counter('generation.state_slot_steps').inc(
                    (live if self.state_kernel else self.cache.slots)
                    * steps)
                counter('generation.state_live_slot_steps').inc(live * steps)
        return act, toks

    def decode_window(self, steps, active, seeds, temps, topks):
        """Launch one fused window that advances every ACTIVE slot
        ``steps`` tokens.  active/seeds/temps/topks are per-slot vectors
        (plain data — they never retrace); so is the block table.
        Returns the [slots, steps] token matrix, still on the device
        (`_Pending`: reading it waits for the window); inactive rows are
        garbage by contract.  `host_len` advances here, where the new
        lengths are already known; `host_tok` when the tokens are read."""
        act, toks = self._launch_window('decode', steps, None, active, seeds,
                                        temps, topks)
        self.host_len[act] = np.minimum(
            self.host_len[act] + int(steps), np.iinfo(np.int32).max)

        def last(out):
            self.host_tok[act] = out[act, -1]

        return self._pending('window', toks, last)

    def verify_window(self, steps, fed, active, seeds, temps, topks):
        """Speculative verify: feed ``fed`` [slots, steps] (host-built
        per-slot rows: last emitted token then draft proposals) through
        the fused window; returns the [slots, steps] TARGET samples
        g_0..g_{K-1}, read at once (the draft needs them).  Device
        lengths advance K for active slots — the caller MUST follow
        with `commit_speculation` (the host-side rollback) before any
        other launch."""
        fed = np.asarray(fed, np.int32).reshape(self.cache.slots,
                                                int(steps))
        toks = self._launch_window('verify', steps, fed, active, seeds,
                                   temps, topks)[1]
        return self._pending('window', toks).read()

    def commit_speculation(self, accepted):
        """Roll the post-verify state back to the accepted prefix.

        ``accepted`` maps slot -> (m, last_token): m tokens of the
        window were emitted (1 <= m <= K) and ``last_token`` (g_{m-1})
        is the next token to feed.  Every ACTIVE slot of the verify
        window must appear.  Rejected positions' K/V rows stay in the
        pool but sit at/past the committed length — unreachable under
        the positional mask and overwritten by the next window (pages
        are never shared at write positions).  Pure host-side metadata:
        the [slots] lengths/tok vectors are re-uploaded, no executable
        runs, nothing retraces."""
        import jax.numpy as jnp
        for slot, (m, last_tok) in accepted.items():
            self.host_len[int(slot)] += int(m)
            self.host_tok[int(slot)] = int(last_tok)
        st = dict(self.state)
        st['lengths'] = jnp.asarray(self.host_len.astype(np.int32))
        st['tok'] = jnp.asarray(self.host_tok.astype(np.int32))
        self.state = st

    # ----------------------------------------------- test conveniences
    def cache_row(self, slot):
        """Host copies (k [L, Hkv, Tmax, dh], v, length) of one slot's
        LOGICAL row, reassembled (and dequantized) through its block
        table, K in the public (interleaved) order of the head
        dimension: the pages hold it in rotated halves."""
        st = self.state
        bt = self.block_tables[int(slot)]
        L, Hkv = self.cache.layers, self.cache.kv_heads
        Tmax, dh = self.cache.max_len, self.cache.head_dim
        if self.cache.latent is not None:
            # the one row a token has: k [L, 1, Tmax, kv_rank + rope] in
            # the public order (`latent.public_rows`), no v
            rows = np.asarray(st['k'])[bt]         # [M, L, PL, W]
            rows = rows.transpose(1, 0, 2, 3).reshape(L, 1, Tmax, dh)
            return (_latent.public_rows(rows, self.cfg['latent']), None,
                    int(np.asarray(st['lengths'][int(slot)])))

        def assemble(pool, scale):
            rows = np.asarray(pool)[bt]        # [M, L, PL, Hkv, dh]
            rows = rows.transpose(1, 3, 0, 2, 4).reshape(L, Hkv, Tmax, dh)
            if scale is None:
                return rows
            sc = np.asarray(scale)[bt]         # [M, L, PL, Hkv]
            sc = sc.transpose(1, 3, 0, 2).reshape(L, Hkv, Tmax)
            return rows.astype(np.float32) * sc[..., None]

        if self.cache.quant == 'int8':
            k = assemble(st['k'], st['k_scale'])
            v = assemble(st['v'], st['v_scale'])
        else:
            k, v = assemble(st['k'], None), assemble(st['v'], None)
        if self.latent_moe:
            # a `gqa` mixer's pool may hold kv heads side by side
            dh = _head_dim(self.cfg)
            k, v = _head_rows_of(k, dh), _head_rows_of(v, dh)
        return (_public_rows(k, dh), v,
                int(np.asarray(st['lengths'][int(slot)])))

    def generate(self, prompt, max_new, params=None, steps_per_window=4,
                 use_ring=False, speculative=False):
        """Single-request convenience decode (tests, parity references):
        prefill the prompt, then advance in fused windows; returns the
        generated ids (list, length max_new).  steps_per_window=1 IS the
        sequential single-token reference path.  ``speculative`` runs
        draft-propose + fused-verify windows instead of plain decode
        (greedy streams are bitwise identical either way)."""
        from .sampling import SamplingParams, draft_ngram
        params = params or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + int(max_new) > self.cache.max_len:
            raise ValueError(
                'prompt of %d + max_new=%d exceeds max_len=%d — requests '
                'are never truncated; shorten the prompt or lower max_new'
                % (prompt.size, max_new, self.cache.max_len))
        slot = self.alloc_slot()
        if slot is None:
            raise RuntimeError('no free kv slot')
        started = False
        try:
            start = self.try_begin(slot, prompt, int(max_new))
            if start is None:
                raise RuntimeError(
                    'kv_oom: pool of %d pages cannot hold prompt of %d + '
                    'max_new=%d' % (self.pool.capacity, prompt.size,
                                    max_new))
            started = True
            first = None
            if use_ring:
                first, _ = self.prefill_ring(slot, prompt, params)
            else:
                for off in range(start, prompt.size, self.prefill_chunk):
                    chunk = prompt[off:off + self.prefill_chunk]
                    first, _ = self.prefill(slot, chunk, off, params)
            self.promote_prefix(slot, prompt)
            out = [int(first)]
            S = self.cache.slots
            active = np.zeros(S, bool)
            active[slot] = True
            seeds = np.zeros(S, np.int32)
            temps = np.zeros(S, np.float32)
            topks = np.zeros(S, np.int32)
            seeds[slot] = params.seed
            temps[slot] = params.temperature
            topks[slot] = params.top_k
            K = int(steps_per_window)
            while len(out) < int(max_new):
                if not self.ensure_capacity(
                        slot, self.host_len[slot] + K):
                    raise RuntimeError('kv_oom: pool exhausted mid-stream')
                if speculative:
                    ctx = np.concatenate([prompt, np.asarray(out,
                                                             np.int32)])
                    fed = np.zeros((S, K), np.int32)
                    fed[slot, 0] = out[-1]
                    fed[slot, 1:] = draft_ngram(ctx, K - 1)
                    g = self.verify_window(K, fed, active, seeds, temps,
                                           topks)[slot]
                    m = 1
                    while m < K and fed[slot, m] == g[m - 1]:
                        m += 1
                    _obs.metrics.counter(
                        'generation.spec_proposed').inc(K - 1)
                    _obs.metrics.counter(
                        'generation.spec_accepted').inc(m - 1)
                    self.commit_speculation({slot: (m, int(g[m - 1]))})
                    out.extend(int(t) for t in g[:m])
                else:
                    toks = self.decode_window(K, active, seeds, temps,
                                              topks)
                    out.extend(int(t) for t in toks[slot])
            return out[:int(max_new)]
        finally:
            if started:
                self.free_slot(slot)
            else:
                self.allocator.free(slot)
