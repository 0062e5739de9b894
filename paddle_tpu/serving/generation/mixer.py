"""What a MIXER is to the decode runtime: one entry of `decode._MIXERS`.

A layer of a served model is a norm, one or more mixers that read the
normalised input side by side, and a feed-forward (`decode._layers`).
Everything that follows from a mixer's KIND is asked of its entry, a
`Mixer` that lives with the kind's module (``MIXER`` in ssm.py, latent.py,
kda.py, shortconv.py; ``'gqa'``'s in decode.py); decode.py walks the
layers and calls the table.  A new mixer is one module with one entry.

The traced halves share one call shape, ``half(w, cfg, cache, kernels,
lay, h, st, at) -> (mixed, st)``: the executables' parameters, the model
dict, the `CacheConfig`, the runtime's `Kernels` (a mixer reads its own
field), the layer's record (``lay.index`` names its weights, ``lay.pool``
and ``lay.state`` index the pool's layer axis and the recurrent arrays'),
the normalised input, the state dict, and where the launch stands
(`Chunk`, `Step`).  They return what the layer adds to the residual
stream and the state dict with the layer's rows or state written.
"""
import collections

__all__ = ['Mixer', 'Kernels', 'Chunk', 'Step']


def _nothing(*_args):
    return {}


Mixer = collections.namedtuple('Mixer', (
    # (cfg) -> {slot: shape} of a layer's weights after ``layer_<i>_``,
    # in the public layout (a projection is ``[in, out]``)
    'weight_shapes',
    # what a layer STORES, None for none of that sort: ``pool(cfg, wide)``
    # -> `CacheConfig`'s keywords of the page pool (kv_heads, head_dim,
    # latent); ``recurrent(cfg)`` -> the (scan state, tail) shapes of one
    # slot in one layer.  One of each a runtime: two kinds that both fill
    # one are refused (`decode._layers`)
    'pool', 'recurrent',
    # the weights the runtime keeps PREPARED: ``prepared(cfg)`` -> {public
    # slot: the names of its prepared parts}; ``dims(cfg)`` -> the static
    # keywords both functions take; ``prepare(*the public arrays in that
    # order, **dims)`` -> every part, in order; ``public(slot, parts,
    # **dims)`` -> bitwise the public weight (``rt.w``)
    'prepared', 'dims', 'prepare', 'public',
    # (cfg, k, v) -> `cache_row`'s (k, v) [layers, heads, max_len, width]
    # in the public order, from the pool's rows (numpy; v None for a pool
    # without one)
    'public_rows',
    # (cfg, cache, chunk, mesh) -> {field of `Kernels`: whether that
    # kernel of this mixer may run here}
    'kernels',
    # what a wide launch counts for this kind behind `experts.STATS`:
    # ``stats(cfg)`` -> {counter after ``generation.``: what one counted
    # unit adds to it}; ``counted`` the pair ``(n, cache, new_len,
    # true_count) -> [int32 arrays]`` of a chunk and ``(n, cache, kernels,
    # at) -> [...]`` of a step, for the model's ``n`` layers of the kind
    'stats', 'counted',
    # the halves (prefill, step) under the stream ``[B, T, D]`` in the
    # model's dtype and under ``[T, D]`` float32; None where the kind does
    # not serve under it
    'narrow', 'wide'),
    defaults=(None, None, _nothing, None, None, None, None, _nothing,
              _nothing, None, None, None))

# whether each kernel may run over this runtime's cache and mesh (a
# floating pool, one device, whole tiles), asked once: a step attends over
# the pool in place; it advances the live slots' recurrent state in place;
# a latent chunk's scores stay on chip; the grouped expert products run as
# `experts.gmm`
Kernels = collections.namedtuple(
    'Kernels', ('paged', 'state', 'prefill', 'experts'),
    defaults=(False,) * 4)

# a prefill chunk of ONE slot: scalars ``slot``, ``offset`` (positions
# already written) and ``true_count`` (real tokens); ``pos`` [1, C] and
# ``p_abs`` [C] the absolute positions, ``pg`` / ``rw`` [C] each one's
# page (0 for padding) and in-page row, ``bt_row`` [max_pages]; ``ring``
# the mesh of a one-shot ring prefill
Chunk = collections.namedtuple('Chunk', (
    'slot', 'offset', 'true_count', 'pos', 'p_abs', 'pg', 'rw', 'bt_row',
    'ring'))

# a decode step of EVERY slot: ``active`` [S], ``pos`` [S] the write
# positions, ``pg`` (0 for a slot that rides along) / ``rw`` [S], ``bt``
# [S, max_pages], ``n_attend`` [S] the positions each slot attends
Step = collections.namedtuple('Step', (
    'active', 'pos', 'pg', 'rw', 'bt', 'n_attend'))
