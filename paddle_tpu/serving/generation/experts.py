"""The feed-forward of a ``latent_moe`` block (decode.py): a dense SwiGLU
in the layers that say ``'dense'``, routed experts beside a shared one
in those that say ``'experts'``, as ONE expert-parallel rank runs them.

For a layer's normalised input ``h`` ``[T, D]`` and ``moe = cfg['moe']``
(``n_routed``, ``top_k``, ``d_expert``, ``scale``, ``ranks``, ``rank``):

    g   = sigmoid(h W_g)                    [T, n_routed], float32
    E   = top_k(g)                          over ALL n_routed experts
    w_e = scale * g_e / sum_{e' in E} g_e'  over all top_k picks
    y   = sum_{e in E, e held} w_e FFN_e(h) + FFN_shared(h)

The layer is told WHICH experts it holds: rank ``r`` of ``ranks`` holds
the contiguous block ``[r * n_routed / ranks, (r + 1) * n_routed /
ranks)`` (`held`), and its weights ``moe_fc{1,3,2}_w`` carry that many
experts on their leading axis.  It routes over all ``n_routed``,
normalises over all ``top_k`` picks as published, and adds only what
its own experts give; what the absent ranks' experts would have added
is left out, and nothing stands in for them or for their exchange.  A
token that is padding, or a slot that rides along, routes nowhere.

`select` is the ONE place that turns scores into picks: plain top-k (the
reading of ``topk_method: 'none'``), or, for a model whose ``moe`` says
``bias``, the BIAS-CORRECTED choice ``top_k(g + b)`` with ``b`` the
layer's weight ``moe_router_bias`` ``[n_routed]``: ``b`` decides which
experts are picked and nothing else, the weights are the picks' own
scores ``g_e``.  A group-limited selection would change this function
alone.

`routed` does work proportional to the ASSIGNMENTS: the (token, pick)
pairs that fall on held experts are sorted by expert and the tokens
gathered in that order.  A decode step's handful of pairs runs through
three `jax.lax.ragged_dot`s, which read only the experts that have rows
(an expert no token picked is no group: its matrices are not read); a
chunk's pairs, some twenty an expert, run as one batch entry an expert
of three batched products (each group padded to 64 rows), every
expert's matrices read once at full width.  No token is dropped whatever
the routing: a chunk with a group that does not fit the batch takes
`ragged_dot` too, and `ragged_dot` runs over ``T`` sorted rows where the
held pairs fit them and over all ``T * top_k`` where they do not
(`jax.lax.cond`, every route compiled): its time follows the rows it is
handed, not the rows that are real (a decode step of 64 slots over 512
rows instead of 64: 13.4 ms against 9.5, PERF.md, Findings of PR 47).

Every call returns, beside its output, `STATS` int32 counts that the
launches sum and hand back beside their tokens (decode.py moves them
into ``generation.moe_*``).
"""
__all__ = ['SLOTS', 'STATS', 'weight_shapes', 'held',
           'select', 'route', 'routed', 'swiglu', 'expert_layer',
           'dense_layer']

# the expert layer's weights, after `layer_<i>_`
SLOTS = ('moe_router_w', 'moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w',
         'moe_shared_fc1_w', 'moe_shared_fc3_w', 'moe_shared_fc2_w')
# what one call counts: (token, pick) pairs computed here, tokens routed
# at all, held experts with at least one token, the busiest held
# expert's tokens
STATS = ('moe_assignments', 'moe_tokens', 'moe_experts_touched',
         'moe_busiest_expert_tokens')


def held(moe):
    """(first, count) of the experts this rank holds."""
    count = int(moe['n_routed']) // int(moe['ranks'])
    return int(moe['rank']) * count, count


def weight_shapes(d_model, moe):
    """{slot: shape} of one expert layer's weights: `SLOTS`, and where
    ``moe['bias']`` the choice bias ``moe_router_bias``."""
    f, n = int(moe['d_expert']), held(moe)[1]
    fs = f * int(moe.get('n_shared', 1))
    shapes = {'moe_router_w': (d_model, int(moe['n_routed'])),
              'moe_fc1_w': (n, d_model, f), 'moe_fc3_w': (n, d_model, f),
              'moe_fc2_w': (n, f, d_model),
              'moe_shared_fc1_w': (d_model, fs),
              'moe_shared_fc3_w': (d_model, fs),
              'moe_shared_fc2_w': (fs, d_model)}
    if moe.get('bias'):
        shapes['moe_router_bias'] = (int(moe['n_routed']),)
    return shapes


def select(scores, moe, bias=None):
    """scores [T, n_routed] float32 -> the picked experts [T, top_k]:
    top-k over all of them, of the scores themselves or, with ``bias``
    [n_routed], of ``scores + bias`` (the bias-corrected choice: the
    bias moves the choice alone, never a weight)."""
    import jax
    import jax.numpy as jnp
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    return jax.lax.top_k(scores, int(moe['top_k']))[1]


def route(h, router_w, moe, bias=None):
    """h [T, D] float32 normalised -> (picks [T, top_k] int32, their
    weights [T, top_k] float32).  Logits, scores and weights in float32
    at full precision, as the source computes them; ``bias`` is
    `select`'s."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    g = jax.nn.sigmoid(logits)
    picks = select(g, moe, bias)
    gp = jnp.take_along_axis(g, picks, axis=1)
    return picks.astype(jnp.int32), \
        gp / jnp.sum(gp, axis=1, keepdims=True) * float(moe['scale'])


def swiglu(h, w1, w3, w2):
    """``(silu(h w1) * (h w3)) w2``: inputs in the weights' dtype, float32
    accumulation and result."""
    import jax
    import jax.numpy as jnp
    hb = h.astype(w1.dtype)
    a = jnp.dot(hb, w1, preferred_element_type=jnp.float32)
    b = jnp.dot(hb, w3, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(a) * b).astype(w2.dtype), w2,
                   preferred_element_type=jnp.float32)


# rows an expert's group is padded to on the batched route of `routed`
_GROUP_ROWS = 64


def routed(h, w1, w3, w2, picks, wts, valid, moe):
    """The held experts' part of the layer for h [T, D]: picks / wts
    [T, top_k] (`route`), valid [T] bool (False: the token routes
    nowhere).  Returns (y [T, D] float32, stats [len(STATS)] int32).

    The held (token, pick) pairs are sorted by expert.  A chunk of many
    tokens whose every group fits ``_GROUP_ROWS`` rows (the usual case: a
    group averages ``T * top_k / n_routed``) runs each expert's rows as
    one batch entry of three batched products, every expert's matrices
    read once at full width; a decode step, and a chunk with a larger
    group, takes `jax.lax.ragged_dot`, which reads only the experts that
    have rows: over the first ``T`` sorted rows where the held pairs fit
    them (a decode step's do, unless its streams' held picks outnumber
    the slots), over all ``T * top_k`` where they do not.
    `jax.lax.cond` on the device, every route compiled; none drops a
    pair."""
    import jax
    import jax.numpy as jnp
    T, k = picks.shape
    first, G = held(moe)
    local = picks - first
    local = jnp.where((local >= 0) & (local < G) & valid[:, None], local, G)
    flat = local.reshape(T * k)
    order = jnp.argsort(flat, stable=True)       # held first, by expert
    sizes = jnp.sum(flat[:, None] == jnp.arange(G)[None], axis=0,
                    dtype=jnp.int32)             # [G]
    n_held, busiest = jnp.sum(sizes), jnp.max(sizes)
    hb = h.astype(w1.dtype)
    flat_w = wts.reshape(T * k)

    def back(tok, y):
        return jnp.zeros((T, h.shape[1]), jnp.float32).at[tok].add(y)

    def ragged(rows):
        sel = order[:rows]
        tok = sel // k
        x = hb[tok]                                            # [rows, D]
        a = jax.lax.ragged_dot(x, w1, sizes,
                               preferred_element_type=jnp.float32)
        b = jax.lax.ragged_dot(x, w3, sizes,
                               preferred_element_type=jnp.float32)
        y = jax.lax.ragged_dot((jax.nn.silu(a) * b).astype(w2.dtype), w2,
                               sizes, preferred_element_type=jnp.float32)
        # rows behind the last group are zero already; their weight is a
        # pick's that fell elsewhere
        return back(tok, y * jnp.where(jnp.arange(rows) < n_held,
                                       flat_w[sel], 0.0)[:, None])

    def unbatched():
        return jax.lax.cond(n_held <= T, lambda: ragged(T),
                            lambda: ragged(T * k))

    def batched():
        # entry (e, j): the j-th pair of expert e in the sorted order
        j = jnp.arange(_GROUP_ROWS)[None]
        real = j < sizes[:, None]                              # [G, R]
        sel = order[jnp.where(real, (jnp.cumsum(sizes) - sizes)[:, None] + j,
                              0)]
        tok = sel // k
        x = hb[tok]                                            # [G, R, D]
        a = jnp.einsum('grd,gdf->grf', x, w1,
                       preferred_element_type=jnp.float32)
        b = jnp.einsum('grd,gdf->grf', x, w3,
                       preferred_element_type=jnp.float32)
        y = jnp.einsum('grf,gfd->grd', (jax.nn.silu(a) * b).astype(w2.dtype),
                       w2, preferred_element_type=jnp.float32)
        y = y * jnp.where(real, flat_w[sel], 0.0)[..., None]
        return back(tok.reshape(-1), y.reshape(-1, h.shape[1]))

    if T > _GROUP_ROWS:
        y = jax.lax.cond(busiest <= _GROUP_ROWS, batched, unbatched)
    else:
        y = unbatched()
    stats = jnp.stack([n_held, jnp.sum(valid, dtype=jnp.int32),
                       jnp.sum(sizes > 0, dtype=jnp.int32), busiest])
    return y, stats


def expert_layer(w, p, cfg, h, valid):
    """h [T, D] float32 normalised -> (the layer's output [T, D]
    float32, stats): the held routed experts' part and the shared
    expert's."""
    import jax
    moe = cfg['moe']
    with jax.named_scope('moe.route'):
        chosen_by = (w[p + 'moe_router_bias'],) if moe.get('bias') else ()
        picks, wts = route(h, w[p + 'moe_router_w'], moe, *chosen_by)
    with jax.named_scope('moe.experts'):
        y, stats = routed(h, w[p + 'moe_fc1_w'], w[p + 'moe_fc3_w'],
                          w[p + 'moe_fc2_w'], picks, wts, valid, moe)
    with jax.named_scope('moe.shared'):
        y = y + swiglu(h, w[p + 'moe_shared_fc1_w'],
                       w[p + 'moe_shared_fc3_w'], w[p + 'moe_shared_fc2_w'])
    return y, stats


def dense_layer(w, p, h):
    """h [T, D] normalised -> (the dense SwiGLU's output [T, D] float32,
    stats that count nothing)."""
    import jax.numpy as jnp
    return (swiglu(h, w[p + 'ffn_fc1_w'], w[p + 'ffn_fc3_w'],
                   w[p + 'ffn_fc2_w']),
            jnp.zeros((len(STATS),), jnp.int32))
