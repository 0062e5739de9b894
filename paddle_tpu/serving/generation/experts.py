"""The feed-forward of a ``latent_moe`` block (decode.py): a dense SwiGLU
in the layers that say ``'dense'``, routed experts beside a shared one
in those that say ``'experts'``, as ONE expert-parallel rank runs them.

For a layer's normalised input ``h`` ``[T, D]`` and ``moe = cfg['moe']``
(``n_routed``, ``top_k``, ``d_expert``, ``scale``, ``ranks``, ``rank``;
``n_shared``, 1 unless given; ``norm_eps``, 0 unless given):

    g   = sigmoid(h W_g)                    [T, n_routed], float32
    E   = top_k(g)                          over ALL n_routed experts
    w_e = scale * g_e / (sum_{e' in E} g_e' + norm_eps)  over all picks
    y   = sum_{e in E, e held} w_e FFN_e(h) + FFN_shared(h)

``n_shared: 0`` is a layer WITHOUT a shared expert: it has no
``moe_shared_*`` weights and runs no product for one (not arrays of
width zero).  ``ranks: 1`` is the whole layer on this chip: every
routed expert is held and every pair of every token is computed here.

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
gathered in that order.  Which way the sorted rows are multiplied is
chosen ON THE DEVICE from the routing the call was handed, the held
pairs' count and the largest group (`jax.lax.switch`, every route
compiled), never from a model's name or a switch:

* a call of at most ``_GROUP_ROWS`` tokens (a decode step of few
  slots): three `jax.lax.ragged_dot`s, which read only the experts that
  have rows (an expert no token picked is no group: its matrices are
  not read), over ``T`` sorted rows where the held pairs fit them and
  over all ``T * top_k`` where they do not: `ragged_dot`'s time follows
  the rows it is handed, not the rows that are real (a decode step of
  64 slots over 512 rows instead of 64: 13.4 ms against 9.5, PERF.md,
  Findings of PR 47);
* a larger call with FEW held pairs, at most ``_GROUPED_ROWS`` a held
  expert (a decode step of many slots, a short last chunk): the grouped
  route over that static bucket of sorted rows, which likewise reads the
  experts with rows and no other, through `gmm` where that kernel can
  run (`gmm_eligible`) and through `ragged_dot` where not, and gathers
  and scatters the bucket's rows, not every expert's padded group;
* a larger call with many pairs whose every group fits ``_GROUP_ROWS``
  rows (a full chunk: some twenty pairs an expert, every expert
  touched): one batch entry an expert of three batched products, every
  expert's matrices read once at full width;
* a group that does not fit the batch: `ragged_dot` as in a small call.

No pair is dropped on any route.

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
# expert's tokens, calls that read only the experts with rows (every
# route of `routed` but the batched one, which reads each held expert)
STATS = ('moe_assignments', 'moe_tokens', 'moe_experts_touched',
         'moe_busiest_expert_tokens', 'moe_touched_only_calls')


def held(moe):
    """(first, count) of the experts this rank holds."""
    count = int(moe['n_routed']) // int(moe['ranks'])
    return int(moe['rank']) * count, count


def _n_shared(moe):
    return int(moe.get('n_shared', 1))


def weight_shapes(d_model, moe):
    """{slot: shape} of one expert layer's weights: `SLOTS` (without
    the shared expert's three where ``moe['n_shared']`` is 0), and where
    ``moe['bias']`` the choice bias ``moe_router_bias``."""
    f, n = int(moe['d_expert']), held(moe)[1]
    fs = f * _n_shared(moe)
    shapes = {'moe_router_w': (d_model, int(moe['n_routed'])),
              'moe_fc1_w': (n, d_model, f), 'moe_fc3_w': (n, d_model, f),
              'moe_fc2_w': (n, f, d_model)}
    if fs:
        shapes.update({'moe_shared_fc1_w': (d_model, fs),
                       'moe_shared_fc3_w': (d_model, fs),
                       'moe_shared_fc2_w': (fs, d_model)})
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
    `select`'s.  ``moe['norm_eps']`` (a source that renormalises over
    ``sum + 1e-6``) is added to the picks' sum where the model dict
    gives one."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    g = jax.nn.sigmoid(logits)
    picks = select(g, moe, bias)
    gp = jnp.take_along_axis(g, picks, axis=1)
    total = jnp.sum(gp, axis=1, keepdims=True)
    if moe.get('norm_eps'):
        total = total + float(moe['norm_eps'])
    return picks.astype(jnp.int32), gp / total * float(moe['scale'])


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
# (taken by any call of more than this many tokens whose held pairs are
# many and whose every group fits)
_GROUP_ROWS = 64
# the grouped route's bucket of sorted rows, for each held expert: a call
# of more than `_GROUP_ROWS` tokens with at most this many held pairs an
# expert held reads the experts that have rows and no other
_GROUPED_ROWS = 4


# `gmm`: rows a grid step multiplies (a whole number of bf16 sublane
# tiles; a group of a few rows wastes the rest of one tile of the MXU's
# rows, never a weight byte), and the VMEM asked of Mosaic: an expert's
# whole matrix is one block (4.7 MB at [2304, 1024] bf16), double
# buffered, beside the rows' and the result's tiles: more than the 16 MiB
# a kernel gets unasked, of a v5e's 128
_GMM_ROWS = 32
_GMM_VMEM = 48 << 20
# the largest weight block `gmm` takes whole: above it the result's
# columns are tiled
_GMM_BLOCK_BYTES = 6 << 20


def gmm_eligible(w_shape, mesh=None):
    """Static rule for `gmm` over weights ``[G, K, N]``: one device
    (`_pallas.single_device`); on an accelerator whole lane tiles both
    ways (K and N multiples of 128).  Otherwise `jax.lax.ragged_dot`."""
    from ...ops import _pallas
    _G, K, N = w_shape
    if not _pallas.single_device(mesh):
        return False
    return _pallas.interpret() or (K % 128 == 0 and N % 128 == 0)


def _gmm_visits(sizes, rows, tm):
    """The (group, row tile) pairs `gmm`'s grid walks, in order, for
    ``rows`` sorted rows in tiles of ``tm``: every group that has rows
    with every tile its rows lie in, and no other (a group without rows
    is no grid step and no DMA).  Returns (offsets [G + 1], group [V],
    tile [V], count): V = tiles + G - 1 bounds the pairs, ``count`` of
    them are real (at least 1: with no row at all, the last group on
    tile 0, which writes zeros)."""
    import jax.numpy as jnp
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    upto = jnp.cumsum(tiles)                       # visits up to group g's
    v = jnp.arange(rows // tm + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(upto, v, side='right'), G - 1) \
        .astype(jnp.int32)
    tile = starts[group] // tm + (v - (upto - tiles)[group])
    tile = jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               ends.astype(jnp.int32)])
    return offsets, group, tile, jnp.maximum(upto[-1], 1).astype(jnp.int32)


def gmm(x, w, sizes):
    """``x[rows of group g] @ w[g]`` for sorted rows: x [R, K] in the
    weights' dtype, w [G, K, N], sizes [G] int32 (group g owns the
    ``sizes[g]`` rows behind group g - 1's; their sum at most R).
    Returns [R, N] float32, zeros behind the last group: what
    `jax.lax.ragged_dot` returns, by a Pallas kernel that reads the
    matrices of the groups that HAVE rows and no other.

    The grid is (column tiles, `_gmm_visits`): its second extent is a
    traced value, and the weight block of a step is chosen by the
    scalar-prefetched table of the groups with rows, so a group without
    any is no step and no DMA.  A step multiplies one tile of
    ``_GMM_ROWS`` rows by its group's block and stores the group's rows of
    the product; a tile's first step zeroes the rest of it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ...ops import _pallas
    R, K = x.shape
    G, _, N = w.shape
    tm = _GMM_ROWS
    rows = -(-R // tm) * tm
    tn = N
    while K * tn * w.dtype.itemsize > _GMM_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    offsets, group, tile, count = _gmm_visits(sizes, rows, tm)

    def kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref):
        v = pl.program_id(1)
        g, t = group_ref[v], tile_ref[v]
        y = jnp.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        first = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)

        @pl.when(first)
        def _():
            o_ref[...] = jnp.where(mine, y, 0.0)

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] = jnp.where(mine, y, o_ref[...])

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, count),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, K, tn),
                             lambda n, v, o, g, t: (g[v], 0, n))],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, o, g, t: (t[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_GMM_VMEM),
        name='experts_gmm',
        interpret=_pallas.interpret(),
    )(offsets, group, tile, jnp.pad(x, ((0, rows - R), (0, 0))), w)
    # a tile behind the last group's was never visited
    return jnp.where((jnp.arange(R) < offsets[G])[:, None], out[:R], 0.0)


def _routes(h, w1, w3, w2, picks, wts, valid, moe, kernel=False):
    """The routing `routed` was handed, sorted, and every way it has of
    computing the held pairs: ((n_held, sizes [G], busiest), {'grouped',
    'batched', 'unbatched': () -> y [T, D] float32}).  Each computes
    every held pair wherever its precondition holds (`routed` chooses;
    chip_smoke.py times them one by one).  ``kernel`` (`gmm_eligible`,
    static): the grouped route's products are `gmm`'s."""
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

    def ragged_dot(x, w):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)

    def ragged(rows, dot=ragged_dot):
        # needs n_held <= rows
        sel = order[:rows]
        tok = sel // k
        x = hb[tok]                                            # [rows, D]
        a, b = dot(x, w1), dot(x, w3)
        y = dot((jax.nn.silu(a) * b).astype(w2.dtype), w2)
        # rows behind the last group are zero already; their weight is a
        # pick's that fell elsewhere
        return back(tok, y * jnp.where(jnp.arange(rows) < n_held,
                                       flat_w[sel], 0.0)[:, None])

    def unbatched():
        return jax.lax.cond(n_held <= T, lambda: ragged(T),
                            lambda: ragged(T * k))

    def batched():
        # needs busiest <= _GROUP_ROWS; entry (e, j): the j-th pair of
        # expert e in the sorted order
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

    return (n_held, sizes, busiest), {
        'grouped': lambda: ragged(
            min(_GROUPED_ROWS * G, T * k),
            (lambda x, w: gmm(x, w, sizes)) if kernel else ragged_dot),
        'batched': batched, 'unbatched': unbatched}


def routed(h, w1, w3, w2, picks, wts, valid, moe, kernel=False):
    """The held experts' part of the layer for h [T, D]: picks / wts
    [T, top_k] (`route`), valid [T] bool (False: the token routes
    nowhere).  Returns (y [T, D] float32, stats [len(STATS)] int32).

    The held (token, pick) pairs are sorted by expert.  A call of at
    most ``_GROUP_ROWS`` tokens (a decode step of few slots) takes
    `jax.lax.ragged_dot`, which reads only the experts that have rows:
    over the first ``T`` sorted rows where the held pairs fit them,
    over all ``T * top_k`` where they do not (``unbatched``).  A larger
    call is routed by the ROUTING it was handed, not by its size: few
    held pairs, at most ``_GROUPED_ROWS`` a held expert (a decode step
    of many slots, a short last chunk), run grouped over that static
    bucket of sorted rows and read the experts with rows alone; many
    pairs whose every group fits ``_GROUP_ROWS`` rows (a full chunk: a
    group averages ``T * top_k / n_routed``) run each expert's rows as
    one batch entry of three batched products, every expert's matrices
    read once at full width; a larger group takes ``unbatched``.
    `jax.lax.switch` on the device, every route compiled; none drops a
    pair."""
    import jax
    import jax.numpy as jnp
    T, k = picks.shape
    (n_held, sizes, busiest), routes = _routes(h, w1, w3, w2, picks, wts,
                                               valid, moe, kernel)
    if T > _GROUP_ROWS:
        bucket = min(_GROUPED_ROWS * sizes.shape[0], T * k)
        route = jnp.where(n_held <= bucket, 0,
                          jnp.where(busiest <= _GROUP_ROWS, 1, 2))
        y = jax.lax.switch(route, [routes['grouped'], routes['batched'],
                                   routes['unbatched']])
        touched_only = (route != 1).astype(jnp.int32)
    else:
        y = routes['unbatched']()
        touched_only = jnp.ones((), jnp.int32)
    stats = jnp.stack([n_held, jnp.sum(valid, dtype=jnp.int32),
                       jnp.sum(sizes > 0, dtype=jnp.int32), busiest,
                       touched_only])
    return y, stats


def expert_layer(w, p, cfg, h, valid, kernel=False):
    """h [T, D] float32 normalised -> (the layer's output [T, D]
    float32, stats): the held routed experts' part and, where the layer
    has one, the shared expert's.  ``kernel`` is `routed`'s."""
    import jax
    moe = cfg['moe']
    with jax.named_scope('moe.route'):
        chosen_by = (w[p + 'moe_router_bias'],) if moe.get('bias') else ()
        picks, wts = route(h, w[p + 'moe_router_w'], moe, *chosen_by)
    with jax.named_scope('moe.experts'):
        y, stats = routed(h, w[p + 'moe_fc1_w'], w[p + 'moe_fc3_w'],
                          w[p + 'moe_fc2_w'], picks, wts, valid, moe,
                          kernel)
    if not _n_shared(moe):
        return y, stats
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
