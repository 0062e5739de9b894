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

WHAT THE MODEL IS is asked in one place.  `_layers` reads the model
dict (``block``, ``n_layer``, ``mixer``, ``ffn``) into one record a
layer: its mixers' kinds, its feed-forward, its index on the pool's
layer axis and on the recurrent arrays', and the model's stream
convention; it alone refuses a wrong dict.  What follows from a mixer's
KIND is its entry's in `_MIXERS` (mixer.py says what an entry answers;
``'gqa'``'s is below, the others' in ssm.py, latent.py, kda.py and
shortconv.py): its weights, which are kept prepared and how that is
undone, the pool geometry or recurrent shapes it fills, whether its
kernels may run here, what its launches count, and its halves of a
prefill chunk and of a decode step.  Everything else here walks the
layers and calls the table: a new mixer is one module with one entry.

Without a ``block`` key the model is the dense decoder above (RMSNorm,
GQA, RoPE, SwiGLU; ``head_dim`` and ``rms_eps`` default to ``d_model //
n_head`` and 1e-6).  ``block: 'falcon_h1'`` puts a Mamba-2 mixer beside
the attention, on the same normalised input, under the model's
``multipliers``.  ``block: 'latent_moe'`` gives a mixer a layer as data
(``cfg['mixer']``, latent attention without the key) and per layer
(``cfg['ffn']``) a dense SwiGLU or routed experts as ONE expert-parallel
rank holds them (experts.py).  A runtime has one pool geometry and one
state geometry (`CacheConfig`), so a model attends through one kind of
mixer, in at least one layer, and holds state through at most one; the
pool's layer axis counts the layers that attend, the recurrent arrays'
those that hold state.  A model that holds state (`recurrent`) starts a
chunk from the slot's state (zeros at offset 0), advances the live
slots' in a step and keeps a dead slot's bit for bit; that state can
neither be shared nor rolled back, so such a runtime takes no prefix-cache
hit (`generation.prefix_refused_recurrent` counts the begins), no
speculative window and no ring prefill.

TWO STREAM CONVENTIONS stand, on purpose (ROADMAP D1a).  The dense and
``falcon_h1`` kinds carry the residual stream ``[B, T, D]`` in the
model's dtype (`_rms`, `_qkv`, `_ffn`, `_head`); ``latent_moe`` carries
``[T, D]`` float32 and rounds at each product (`latent.rms`,
`latent.dot`), takes no ring prefill and no int8 rows, and its launches
hand back, beside their tokens, a few counts (`_launch_stats`: routing's
and each mixer's own) that move into ``generation.*`` counters behind
the next read of a result (`DecodeRuntime._count_stats`): no launch and
no wait of their own.  The layer loops choose their body by that one
field (`_Layer.wide`) and ask the model nothing else.  Which kernels run
is one record (`DecodeRuntime.kernels`, mixer.py) every launch is built
with.  No standing program moves when a kind is added: tests/
test_generation_pipeline.py, test_generation_kda.py and
test_generation_lfm2.py pin every kind's lowered text.
"""
import collections
import threading
from collections.abc import Mapping

import numpy as np

from ... import observability as _obs
from ...core import compile_cache as _cc
from ...ops.attention import (cached_attention, paged_attention,
                              paged_attention_eligible,
                              paged_attention_rows, paged_pool_heads)
from ...ops.sampling import sample_logits, sample_tokens_at, token_key
from . import experts as _experts
from . import kda as _kda
from . import latent as _latent
from . import shortconv as _shortconv
from . import ssm as _ssm
from .kv_cache import (CacheConfig, PagePool, PrefixCache, SlotAllocator,
                       init_state)
from .mixer import Chunk, Kernels, Mixer, Step

__all__ = ['DecodeRuntime', 'dense_reference', 'weight_names',
           'weight_shapes', 'random_weights']

# the dense layer's slots in the order a trained llama program names them
# (models/llama.py): `weight_names` keeps it for the narrow stream
_WEIGHT_SLOTS = ('att_q_w', 'att_k_w', 'att_v_w', 'att_o_w', 'att_norm',
                 'ffn_norm', 'ffn_fc1_w', 'ffn_fc2_w', 'ffn_fc3_w')


# one layer of a model: ``index`` i (its weights are ``layer_<i>_*``), the
# kinds of its ``mixers`` in order (keys of `_MIXERS`), its feed-forward
# ``ffn`` (``'dense'`` | ``'experts'``), its index ``pool`` on the page
# pool's layer axis and ``state`` on the recurrent arrays' (None where it
# stores none of that sort), and the MODEL's stream convention ``wide``:
# the residual stream is ``[T, D]`` float32, rounded at each product,
# where without it it is ``[B, T, D]`` in the model's dtype
_Layer = collections.namedtuple(
    '_Layer', ('index', 'mixers', 'ffn', 'pool', 'state', 'wide'))


def _layers(cfg):
    """The model's layers, one `_Layer` each: THE place that reads
    ``cfg['block']`` and refuses a wrong model dict.  ``'dense'`` (the
    default) is GQA and one SwiGLU a layer; ``'falcon_h1'`` the same with
    a Mamba-2 mixer beside the attention; ``'latent_moe'`` a mixer a
    layer as ``cfg['mixer']`` names it (latent attention without the key)
    and the feed-forward ``cfg['ffn']`` names, under the wide stream.  A
    model fills ONE pool geometry and ONE state geometry, and attends."""
    blocks = ('dense', 'falcon_h1', 'latent_moe')
    block = cfg.get('block', 'dense')
    if block not in blocks:
        raise ValueError('block must be one of %s, got %r'
                         % (', '.join(map(repr, blocks)), block))
    L, wide = int(cfg['n_layer']), block == 'latent_moe'
    if wide:
        ffn = tuple(cfg['ffn'])
        if len(ffn) != L or any(k not in ('dense', 'experts') for k in ffn):
            raise ValueError("ffn must name 'dense' or 'experts' for each "
                             'of the %d layers, got %r' % (L, ffn))
        kinds = tuple(cfg.get('mixer', ('latent',) * L))
        named = [k for k, m in _MIXERS.items() if m.wide]
        if len(kinds) != L or any(k not in named for k in kinds) \
                or not any(_MIXERS[k].pool for k in kinds):
            raise ValueError(
                'mixer must name %s or %r for each of the %d layers, and '
                'attend in at least one, got %r'
                % (', '.join(map(repr, named[:-1])), named[-1], L, kinds))
        mixers = tuple((k,) for k in kinds)
    else:
        ffn = ('dense',) * L
        mixers = (('gqa', 'ssm') if block == 'falcon_h1' else ('gqa',),) * L
    for stores, what in (('pool', 'attends through'),
                         ('recurrent', 'holds state through')):
        both = [k for k in _MIXERS if getattr(_MIXERS[k], stores)
                and any(k in m for m in mixers)]
        if len(both) > 1:
            raise ValueError('a model %s %r or %r layers, never both, got %r'
                             % (what, both[0], both[1],
                                tuple(k for m in mixers for k in m)))
    out, n_pool, n_state = [], 0, 0
    for i in range(L):
        pool = any(_MIXERS[k].pool for k in mixers[i])
        state = any(_MIXERS[k].recurrent for k in mixers[i])
        out.append(_Layer(i, mixers[i], ffn[i], n_pool if pool else None,
                          n_state if state else None, wide))
        n_pool, n_state = n_pool + pool, n_state + state
    return tuple(out)


def _kinds(lays):
    """[(mixer kind, the layers that have it)] of a model, in the table's
    order."""
    counts = {k: sum(k in lay.mixers for lay in lays) for k in _MIXERS}
    return [(k, n) for k, n in counts.items() if n]


def _head_dim(cfg):
    return int(cfg.get('head_dim', int(cfg['d_model']) // int(cfg['n_head'])))


def _layer_shapes(cfg, lay):
    """{slot: shape} of one layer's weights, in `weight_shapes`' order:
    the norms, the mixer's and the feed-forward's under the wide stream;
    the first mixer's, the norms, the feed-forward's and any other
    mixer's under the narrow one."""
    d = int(cfg['d_model'])
    norms = {'att_norm': (d,), 'ffn_norm': (d,)}
    first, *rest = (_MIXERS[k].weight_shapes(cfg) for k in lay.mixers)
    if lay.ffn == 'dense':
        f = int(cfg['d_ffn'])
        ffn = {'ffn_fc1_w': (d, f), 'ffn_fc3_w': (d, f), 'ffn_fc2_w': (f, d)}
    else:
        ffn = _experts.weight_shapes(d, cfg['moe'])
    shapes = {}
    for part in ((norms, first) if lay.wide else (first, norms)) \
            + (ffn,) + tuple(rest):
        shapes.update(part)
    return shapes


def weight_names(cfg):
    """The decode-side parameter names: the embedding, the last norm, the
    head, and per layer the two norms, its mixers' (`Mixer.weight_shapes`)
    and its feed-forward's (the dense SwiGLU's or
    `experts.weight_shapes`).  A dense layer's are the names a trained
    llama program leaves in its scope, in its order (models/llama.py
    layout)."""
    names = ['tok_emb', 'final_norm', 'lm_proj_w']
    for lay in _layers(cfg):
        slots = list(_layer_shapes(cfg, lay))
        if not lay.wide:
            # stable: a second mixer's stay behind, in their order
            slots.sort(key=lambda s: _WEIGHT_SLOTS.index(s)
                       if s in _WEIGHT_SLOTS else len(_WEIGHT_SLOTS))
        names.extend('layer_%d_%s' % (lay.index, s) for s in slots)
    return names


def weight_shapes(cfg):
    """{name: shape} of every weight under `weight_names(cfg)`, in the
    public layout (a projection is ``[in, out]``)."""
    d, v = int(cfg['d_model']), int(cfg['vocab'])
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for lay in _layers(cfg):
        shapes.update(('layer_%d_%s' % (lay.index, k), s)
                      for k, s in _layer_shapes(cfg, lay).items())
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


def _public_weight(slot, parts, dh):
    """`_prepare_qkv` undone for ONE public slot from its prepared array:
    bitwise the public weight it was made from."""
    wt, = parts
    return (wt if slot == 'att_v_w' else _head_rows(wt, dh, False)).T


def _public_rows(k, dh):
    """K rows [..., dh] as the pool holds them (rotated halves) -> the
    public interleaved order (numpy; `DecodeRuntime.cache_row`)."""
    return k.reshape(k.shape[:-1] + (2, dh // 2)).swapaxes(-1, -2).reshape(
        k.shape)


def _prepared_names(cfg):
    """{public name: (mixer kind, slot, the executables' names for its
    prepared parts)} of the weights the runtime keeps prepared
    (`Mixer.prepared`): q, k and v of a ``'gqa'`` layer, a latent layer's
    three projections in their one or two parts."""
    return {'layer_%d_%s' % (lay.index, slot):
            (kind, slot, tuple('layer_%d_%s' % (lay.index, t) for t in parts))
            for lay in _layers(cfg) for kind in lay.mixers
            for slot, parts in _MIXERS[kind].prepared(cfg).items()}


def _params_from(weights, cfg):
    """``weights`` under `weight_names(cfg)` -> the parameters the
    executables take: the weights a mixer keeps prepared made so
    (`Mixer.prepare`: one jitted call a layer, one compilation per layer
    geometry), under their prepared names; every other weight as it is.
    A layer's raw weights are held only while that layer is prepared."""
    import jax
    import jax.numpy as jnp
    prepared = _prepared_names(cfg)
    params = {n: jnp.asarray(weights[n]) for n in weight_names(cfg)
              if n not in prepared}
    prepare = {}
    for lay in _layers(cfg):
        for kind in lay.mixers:
            slots, p = _MIXERS[kind].prepared(cfg), 'layer_%d_' % lay.index
            if not slots:
                continue
            dims = _MIXERS[kind].dims(cfg)
            if kind not in prepare:
                prepare[kind] = jax.jit(_MIXERS[kind].prepare,
                                        static_argnames=tuple(dims))
            made = prepare[kind](*(jnp.asarray(weights[p + s])
                                   for s in slots), **dims)
            params.update(zip((p + t for parts in slots.values()
                               for t in parts), made))
    return params


class _PublicWeights(Mapping):
    """`DecodeRuntime.w`: the weights under `weight_names(cfg)`, in the
    public shapes and with the values that were passed in, bit for bit.
    The runtime keeps a prepared weight ONCE, in the prepared form
    (`DecodeRuntime.params`); reading one of them here undoes the
    preparation into a fresh array (`Mixer.public`), every other name is
    the array the executables read."""

    def __init__(self, params, cfg):
        import jax
        self._params, self._names = params, weight_names(cfg)
        self._prepared = _prepared_names(cfg)
        self._dims = {kind: _MIXERS[kind].dims(cfg) for kind in
                      {kind for kind, _, _ in self._prepared.values()}}
        self._undo = {kind: jax.jit(_MIXERS[kind].public, static_argnums=0,
                                    static_argnames=tuple(dims))
                      for kind, dims in self._dims.items()}

    def __getitem__(self, name):
        if name not in self._prepared:
            return self._params[name]
        kind, slot, stored = self._prepared[name]
        return self._undo[kind](slot, tuple(self._params[t] for t in stored),
                                **self._dims[kind])

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


def _launch_stats(cfg):
    """{counter after ``generation.``: what one counted unit adds to it}
    of what a wide launch of this model counts on the device and hands
    back beside its tokens, in the order of that array: `experts.STATS`
    summed over its expert layers (and a window's steps), then each
    mixer kind's own (`Mixer.stats`).  Empty for the narrow stream, whose
    launches count nothing."""
    lays = _layers(cfg)
    if not lays[0].wide:
        return {}
    stats = dict.fromkeys(_experts.STATS, 1)
    for kind, _n in _kinds(lays):
        stats.update(_MIXERS[kind].stats(cfg))
    return stats


def _counted(lays, cache, stats, half, *where):
    """A wide launch's whole array of counts: the expert layers' summed
    ``stats``, then what each mixer kind counts for its layers
    (`Mixer.counted`, ``half`` 0 for a chunk and 1 for a step)."""
    import jax.numpy as jnp
    handed = [stats]
    for kind, n in _kinds(lays):
        if _MIXERS[kind].counted is not None:
            handed.extend(_MIXERS[kind].counted[half](n, cache, *where))
    return jnp.concatenate(handed)


def _wide_ffn(w, cfg, lay, x, valid, kernels):
    """The feed-forward half of a layer under the wide stream: x [T, D]
    float32 -> (x + the layer's feed-forward, `experts.STATS`); ``valid``
    [T] marks the tokens that route (experts.py)."""
    p = 'layer_%d_' % lay.index
    h = _latent.rms(x, w[p + 'ffn_norm'], _eps(cfg))
    if lay.ffn == 'dense':
        y, stats = _experts.dense_layer(w, p, h)
    else:
        y, stats = _experts.expert_layer(w, p, cfg, h, valid,
                                         kernels.experts)
    return x + y, stats


# ------------------------------------------------- the ``'gqa'`` mixer
# The one kind that serves under both streams, so its entry holds both
# pairs of halves until the conventions are one (ROADMAP D1a).

def _gqa_weights(cfg):
    """{slot: shape} of a ``'gqa'`` mixer's weights: the four
    projections, and with ``cfg['qk_norm']`` the two head norms' scales
    (over a head's ``head_dim`` values, in the public order)."""
    d, dh = int(cfg['d_model']), _head_dim(cfg)
    h, hkv = int(cfg['n_head']), int(cfg['n_kv_head'])
    shapes = {'att_q_w': (d, h * dh), 'att_k_w': (d, hkv * dh),
              'att_v_w': (d, hkv * dh), 'att_o_w': (h * dh, d)}
    if cfg.get('qk_norm'):
        shapes.update({'att_q_norm': (dh,), 'att_k_norm': (dh,)})
    return shapes


def _gqa_pool(cfg, wide):
    """The K and V pools' geometry: a head a row; under the wide stream a
    narrow head's kv heads side by side in a row (`paged_pool_heads`)."""
    heads, width = int(cfg['n_kv_head']), _head_dim(cfg)
    if wide:
        heads, width = paged_pool_heads(heads, width)
    return dict(kv_heads=heads, head_dim=width)


def _gqa_public_rows(cfg, k, v):
    """`cache_row`'s K and V from the pool's rows: a packed pool's heads
    apart again, K in the public (interleaved) order."""
    dh = _head_dim(cfg)
    return _public_rows(_head_rows_of(k, dh), dh), _head_rows_of(v, dh)


def _gqa_prefill_narrow(w, cfg, cache, kernels, lay, h, st, at):
    """A dense layer's attention over a prefill chunk: h [1, C, D]
    normalised -> (what it adds to the stream, the state dict with the
    chunk's rows written): write, gather and `cached_attention`, or the
    exact ring over the whole prompt (``at.ring``, offset 0)."""
    import jax
    scope = jax.named_scope
    i, theta = lay.index, float(cfg['theta'])
    with scope('attn.qkv'):
        q, k, v = _qkv(w, cfg, h, i)
        q = _rope_at(q, at.pos, theta)
        k = _rope_at(k, at.pos, theta)
    with scope('kv.write'):
        st = _write_rows(st, lay.pool, at.pg, at.rw, k[0].transpose(1, 0, 2),
                         v[0].transpose(1, 0, 2), cache.quant == 'int8')
    if at.ring is None:
        with scope('kv.gather'):
            kl, vl = _logical_rows(st, at.bt_row[None], lay.pool, cache)
    with scope('attn.scores'):
        if at.ring is not None:
            from ...parallel.ring_attention import ring_attention
            att = ring_attention(q, k, v, at.ring, causal=True)
        else:
            att = cached_attention(q, kl, vl, at.pos)
        B, H, T = att.shape[0], att.shape[1], att.shape[2]
        att = att.transpose(0, 2, 1, 3).reshape(B, T, H * _head_dim(cfg))
        return _attn_out(w, cfg, att, i), st


def _gqa_step_narrow(w, cfg, cache, kernels, lay, h, st, at):
    """A dense layer's attention over a decode step: h [S, 1, D]
    normalised -> (what it adds to the stream, the state dict with every
    slot's row written): over the pool in place with ``kernels.paged``
    (`ops.attention.paged_attention`: an active slot reads the pages its
    length covers, an inactive one nothing), else the composed gather
    (`_logical_rows` + `cached_attention`)."""
    import jax
    scope = jax.named_scope
    i, theta, pos = lay.index, float(cfg['theta']), at.pos
    with scope('attn.qkv'):
        q, k, v = _qkv(w, cfg, h, i)
        q = _rope_at(q, pos[:, None], theta)
        k = _rope_at(k, pos[:, None], theta)
    with scope('kv.write'):
        st = _write_rows(st, lay.pool, at.pg, at.rw, k[:, :, 0, :],
                         v[:, :, 0, :], cache.quant == 'int8')
    if not kernels.paged:
        with scope('kv.gather'):
            kl, vl = _logical_rows(st, at.bt, lay.pool, cache)
    with scope('attn.scores'):
        if kernels.paged:
            att = paged_attention(q[:, :, 0, :], st['k'], st['v'], at.bt,
                                  at.n_attend, lay.pool)
        else:
            att = cached_attention(q, kl, vl, pos[:, None])
            att = att.transpose(0, 2, 1, 3)
        return _attn_out(w, cfg, att.reshape(h.shape[0], 1, -1), i), st


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


def _gqa_prefill(w, cfg, cache, kernels, lay, h, st, at):
    """A ``'gqa'`` layer of a prefill chunk under the wide stream: h [C,
    D] float32 normalised -> (the mixer's output [C, D] float32, the
    state dict with the chunk's rows written into layer ``lay.pool`` of
    the pools): the dense block's write, gather and `cached_attention`."""
    import jax
    scope = jax.named_scope
    i, j = lay.index, lay.pool
    q, k, v = _gqa_qkv(w, cfg, h[None], i, at.pos, st['k'].dtype)
    with scope('kv.write'):
        st = _write_rows(st, j, at.pg, at.rw,
                         _pool_rows(k[0].transpose(1, 0, 2), cache),
                         _pool_rows(v[0].transpose(1, 0, 2), cache), False)
    with scope('kv.gather'):
        kl, vl = (_head_rows_of(rows, q.shape[-1]) for rows in
                  _logical_rows(st, at.bt_row[None], j, cache))
    with scope('attn.scores'):
        att = cached_attention(q, kl, vl, at.pos)         # [1, H, C, dh]
        att = att[0].transpose(1, 0, 2).reshape(h.shape[0], -1)
        return _latent.dot(att, w['layer_%d_att_o_w' % i]), st


def _gqa_step(w, cfg, cache, kernels, lay, h, st, at):
    """A ``'gqa'`` layer of a decode step under the wide stream: h [S, D]
    float32 normalised -> (the mixer's output [S, D] float32, the state
    dict with every slot's row written): in place over the pool with
    ``kernels.paged`` (`ops.attention.paged_attention`), else the
    composed gather."""
    import jax
    scope = jax.named_scope
    i, j, pos, S = lay.index, lay.pool, at.pos, h.shape[0]
    q, k, v = _gqa_qkv(w, cfg, h[:, None], i, pos[:, None], st['k'].dtype)
    with scope('kv.write'):
        st = _write_rows(st, j, at.pg, at.rw,
                         _pool_rows(k[:, :, 0, :], cache),
                         _pool_rows(v[:, :, 0, :], cache), False)
    if not kernels.paged:
        with scope('kv.gather'):
            kl, vl = (_head_rows_of(rows, q.shape[-1]) for rows in
                      _logical_rows(st, at.bt, j, cache))
    with scope('attn.scores'):
        if kernels.paged:
            att = paged_attention(q[:, :, 0, :], st['k'], st['v'], at.bt,
                                  at.n_attend, j)
        else:
            att = cached_attention(q, kl, vl, pos[:, None]).transpose(
                0, 2, 1, 3)
        return _latent.dot(att.reshape(S, -1),
                           w['layer_%d_att_o_w' % i]), st


def _of_no_layer(counted):
    return lambda n, *where: counted(0, *where)


# every kind of mixer a layer may have (mixer.py), in the order a wide
# launch's counts follow.  ``'gqa'``'s wide launches carry latent
# attention's count of rows, as zero (its kernel's reads are counted on
# the host, `DecodeRuntime._window_rows_read`): the slot stood in every
# wide launch's array before a model attended through anything else
_MIXERS = {
    'latent': _latent.MIXER,
    'gqa': Mixer(
        weight_shapes=_gqa_weights, pool=_gqa_pool,
        prepared=lambda cfg: {s: (t,) for s, t in _PREPARED.items()},
        dims=lambda cfg: {'dh': _head_dim(cfg)},
        prepare=_prepare_qkv, public=_public_weight,
        public_rows=_gqa_public_rows,
        kernels=lambda cfg, cache, chunk, mesh: {
            'paged': paged_attention_eligible(
                cache.pool_shape, cache.store_dtype, mesh)},
        stats=_latent.MIXER.stats,
        counted=tuple(map(_of_no_layer, _latent.MIXER.counted)),
        narrow=(_gqa_prefill_narrow, _gqa_step_narrow),
        wide=(_gqa_prefill, _gqa_step)),
    'kda': _kda.MIXER,
    'conv': _shortconv.MIXER,
    'ssm': _ssm.MIXER,
}


def _through_layers(w, cfg, cache, kernels, lays, half, x, st, at, routes):
    """The embedded stream ``x`` through every layer of one launch:
    ``half`` is 0 for a prefill chunk and 1 for a decode step (which of a
    mixer's halves runs, mixer.py), ``at`` where the launch stands,
    ``routes`` [T] the tokens an expert layer routes.  A layer is its
    norm, its mixers' halves and its feed-forward; the body is chosen by
    the model's stream convention, the one thing the loop asks of the
    model.  Returns (x, the state dict, `experts.STATS` summed over the
    layers or None for the narrow stream)."""
    import jax
    import jax.numpy as jnp
    scope = jax.named_scope
    stats = jnp.zeros((len(_experts.STATS),), jnp.int32) \
        if lays[0].wide else None
    for lay in lays:
        norm = w['layer_%d_att_norm' % lay.index]
        # ONE scope name for every layer: an operation's op_name says
        # which part of the block it is, whatever its index
        with scope('layer'):
            if lay.wide:
                h = _latent.rms(x, norm, _eps(cfg))
                kind, = lay.mixers        # one a layer under this stream
                mixed, st = _MIXERS[kind].wide[half](
                    w, cfg, cache, kernels, lay, h, st, at)
                with scope('ffn'):
                    x, counted = _wide_ffn(w, cfg, lay, x + mixed, routes,
                                           kernels)
                stats = stats + counted
                continue
            with scope('attn.qkv'):
                h = _rms(x, norm, _eps(cfg))
            for kind in lay.mixers:
                mixed, st = _MIXERS[kind].narrow[half](
                    w, cfg, cache, kernels, lay, h, st, at)
                x = x + mixed
            with scope('ffn'):
                x = _ffn(w, cfg, x, lay.index)
    return x, st, stats


def _prefill_fn(cfg, cache, chunk, ring_mesh=None, kernels=Kernels()):
    """Build the one-chunk (or one-shot ring) prefill function.

    Scatters the chunk's K/V rows into the pages ``bt_row`` maps at the
    chunk's absolute positions (invalid tail rows of a short final
    chunk go to the garbage page), attends the chunk queries against
    the gathered logical row, SETS lengths[slot] = offset + true_count,
    samples the would-be next token at its absolute position, and
    stores it in tok[slot].  Only the final chunk's draw (the request's
    FIRST token, the TTFT token) survives.

    The layers are `_through_layers`'; each mixer reads its own field of
    ``kernels`` (`DecodeRuntime.kernels`).  Under the wide stream the
    function returns a fourth value, the chunk's `_launch_stats`.
    """
    import jax.numpy as jnp
    lays = _layers(cfg)
    wide = lays[0].wide
    M, PL = cache.max_pages, cache.page_len

    if ring_mesh is not None and any(
            lay.wide or lay.state is not None for lay in lays):
        raise ValueError('ring prefill carries neither recurrent state '
                         'nor a latent pool')

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
        at = Chunk(slot, offset, true_count, pos, p_abs, pg, rw, bt_row,
                   ring_mesh)
        with scope('embed'):
            x = _embed(w, cfg, tokens)[None]              # [1, C, D]
        if wide:
            x = x[0].astype(jnp.float32)                  # [C, D]
        x, st, stats = _through_layers(w, cfg, cache, kernels, lays, 0, x,
                                       st, at, valid)
        with scope('lm_head'):
            if wide:
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
        if wide:
            return st, nxt, logits, _counted(lays, cache, stats, 0, new_len,
                                             true_count)
        return st, nxt, logits

    return prefill


def _step_fn(cfg, cache, kernels):
    """One fused decode/verify step over ALL slots: write the fed token's
    K/V through the block table, attend, sample each slot's next token
    with the position-keyed stream, advance ACTIVE slots only.  Inactive
    slots compute masked garbage routed to page 0, and route nowhere in
    an expert layer.

    The layers are `_through_layers`'.  ``kernels``
    (`DecodeRuntime.kernels`) says which of their steps run in place:
    ``paged`` attends over the pool, an active slot reading the pages its
    length covers and an inactive one nothing, where the composed path
    gathers every slot's logical row first; ``state`` advances the live
    slots' recurrent state, where otherwise every slot steps and the dead
    ones' is masked.  Under the wide stream the step returns a third
    value, its `_launch_stats`."""
    import jax.numpy as jnp
    lays = _layers(cfg)
    wide = lays[0].wide
    M, PL = cache.max_pages, cache.page_len

    def step(w, st, bt, fed, active, seeds, temps, topks):
        import jax
        scope = jax.named_scope
        S = bt.shape[0]
        pos = st['lengths']                               # [S] write pos
        pg = bt[jnp.arange(S), jnp.clip(pos // PL, 0, M - 1)]
        pg = jnp.where(active, pg, 0)
        rw = pos % PL
        n_attend = jnp.where(active, pos + 1, 0)          # [S]
        at = Step(active, pos, pg, rw, bt, n_attend)
        with scope('embed'):
            x = _embed(w, cfg, fed)[:, None, :]           # [S, 1, D]
        if wide:
            x = x[:, 0].astype(jnp.float32)               # [S, D]
        x, st, stats = _through_layers(w, cfg, cache, kernels, lays, 1, x,
                                       st, at, active)
        with scope('lm_head'):
            if wide:
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
        if wide:
            return st, nxt, _counted(lays, cache, stats, 1, kernels, at)
        return st, nxt

    return step


def _window_fn(cfg, cache, steps, kernels, verify):
    """A K-step window over `_step_fn`: one `lax.scan`, the state dict
    its donated carry, the block table closed-over DATA (an ordinary
    traced argument).  Its arguments are the parameters, the state, the
    block table, with ``verify`` the rows to feed [K, S], then active,
    seeds, temps and topks [S].  Returns (state, tokens [S, K]) and,
    where the step counts (`_launch_stats`), the counts summed over the
    window."""
    import jax

    step = _step_fn(cfg, cache, kernels)
    counts = _layers(cfg)[0].wide

    def window(w, st, bt, *args):
        fed = args[0] if verify else None
        active, seeds, temps, topks = args[-4:]

        def body(carry, fed_t):
            carry, *out = step(w, carry, bt,
                               fed_t if verify else carry['tok'], active,
                               seeds, temps, topks)
            return carry, tuple(out)
        st, out = jax.lax.scan(body, st, fed, length=steps)
        if counts:
            return st, out[0].T, out[1].sum(axis=0)
        return st, out[0].T                               # [S, K]

    return window


def _decode_fn(cfg, cache, steps, kernels=Kernels()):
    """K-step fused decode window: each step feeds every slot's own
    carry token."""
    return _window_fn(cfg, cache, steps, kernels, False)


def _verify_fn(cfg, cache, steps, kernels=Kernels()):
    """K-step speculative VERIFY window: identical step body, but step j
    feeds ``fed[j]`` (host-built: last emitted token, then the draft's
    proposals) and the returned samples are the target model's verdicts
    g_j at each position.  Same `(seed, position)` sampling as decode —
    an accepted prefix is bitwise the sequential stream."""
    return _window_fn(cfg, cache, steps, kernels, True)


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

    A model that carries recurrent state in any layer (`recurrent`)
    runs WITHOUT the prefix cache whatever ``prefix_cache`` says (a hit
    would skip tokens the state never saw), and refuses speculative
    windows and ring prefill.  A model under the wide stream
    (`latent_moe`) refuses ring prefill, and a latent pool
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
            made = jax.block_until_ready(
                [self.params[t] for _kind, _slot, stored in
                 self.w._prepared.values() for t in stored])
            made_bytes = sum(int(a.nbytes) for a in made)
            init.args.update(prepared=len(made), prepared_bytes=made_bytes)
            _obs.metrics.gauge('generation.prepared_weight_bytes').set(
                made_bytes)
            # one pool geometry and one state geometry, each over the
            # layers that have it (`_layers`), as the mixers describe them
            self.layers = lays = _layers(cfg)
            # whether any layer carries recurrent state, and whether the
            # launches run the wide stream and hand back `_launch_stats`
            self.recurrent = any(lay.state is not None for lay in lays)
            self.latent_moe = lays[0].wide
            geometry = {}
            for kind, n in _kinds(lays):
                entry = _MIXERS[kind]
                if entry.pool:
                    geometry.update(entry.pool(cfg, lays[0].wide), layers=n)
                    self._attends = entry
                if entry.recurrent:
                    geometry.update(recurrent=entry.recurrent(cfg),
                                    recurrent_layers=n)
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
            # which kernels may run here (a floating pool, one device,
            # whole tiles), asked once of every mixer the model uses and
            # of its expert layers: ONE record, which every launch is
            # built with and a mixer reads its own field of (mixer.py)
            may = {}
            for kind, _n in _kinds(lays):
                may.update(_MIXERS[kind].kernels(
                    cfg, self.cache, self.prefill_chunk, mesh))
            if any(lay.ffn == 'experts' for lay in lays):
                may['experts'] = _experts.gmm_eligible(
                    _experts.weight_shapes(int(cfg['d_model']),
                                           cfg['moe'])['moe_fc1_w'], mesh)
            self.kernels = Kernels(**may)
            self._execs = {}
            # wide launches' `_launch_stats`, still on the device, oldest
            # first, and how many launches' were already moved into the
            # counters: that happens behind the next read of a launch
            # that came after them (`_count_stats`)
            self._stats, self._counted = [], 0
            self._stat_names = _launch_stats(cfg)
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
                else self.cache.slots * self.cache.max_len
                if self.cache.latent is not None
                else _gathered_rows(self.cache, self._state_structs(),
                                    self._bt_struct(self.cache.slots)))
            self._lock = threading.Lock()
            _obs.metrics.gauge('generation.kv_cache_bytes').set(
                self.cache.bytes())
            _obs.metrics.gauge('generation.recurrent_state_bytes').set(
                self.cache.recurrent_bytes())

    # ------------------------------------------------ what may run here
    # the fields of `kernels`, under the names they are read by
    paged = property(lambda self: self.kernels.paged)
    state_kernel = property(lambda self: self.kernels.state)
    prefill_kernel = property(lambda self: self.kernels.prefill)
    experts_kernel = property(lambda self: self.kernels.experts)

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
                             kernels=self.kernels)
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
            fn = make(self.cfg, self.cache, steps, self.kernels)
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

    def warmup(self, steps=None, speculative=False):
        """Compile (or disk-load) the steady-state executables up front
        so the first request pays no compile latency.  With
        ``speculative`` the verify window is warmed too."""
        with _obs.span('decode.warmup', cat='compile',
                       counter='generation.warmup_s'):
            self._prefill_exec(self.prefill_chunk)
            if steps:
                self._window_exec('decode', int(steps))
                if speculative:
                    self._window_exec('verify', int(steps))

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
            for (name, unit), n in zip(self._stat_names.items(),
                                       np.asarray(stats)):
                n = int(n) * unit
                _obs.metrics.counter('generation.' + name).inc(n)
                if kind == 'window':
                    _obs.metrics.counter('generation.window_' + name).inc(n)

    def _launched_stats(self, kind, stats):
        """Keep a launch's `_launch_stats` (on the device) until a read
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

        def assemble(pool, scale):
            # a latent pool's one row a token is its one head's
            rows = np.asarray(pool)[bt]        # [M, L, PL, (Hkv,) dh]
            rows = rows.reshape(rows.shape[:3] + (Hkv, dh))
            rows = rows.transpose(1, 3, 0, 2, 4).reshape(L, Hkv, Tmax, dh)
            if scale is None:
                return rows
            sc = np.asarray(scale)[bt]         # [M, L, PL, Hkv]
            sc = sc.transpose(1, 3, 0, 2).reshape(L, Hkv, Tmax)
            return rows.astype(np.float32) * sc[..., None]

        k, v = (assemble(st[n], st.get(n + '_scale')) if n in st else None
                for n in ('k', 'v'))
        return self._attends.public_rows(self.cfg, k, v) + (
            int(np.asarray(st['lengths'][int(slot)])),)

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
