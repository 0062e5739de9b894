"""Paged KV cache: a shared page pool + per-slot block tables.

One preallocated pair of ``[pages, layers, page_len, kv_heads,
head_dim]`` pools holds every in-flight request's keys/values (token
major inside a page: one token's K for all kv heads is one contiguous
``[kv_heads, head_dim]`` slab, which is what a step writes, and one
page of one layer is a contiguous ``[page_len * kv_heads, head_dim]``
matrix, which is what the paged decode kernel copies); a
request owns a *slot* (its row in the fixed-width decode batch) and a
list of *pages* its block table maps, so its memory footprint is
``ceil(len / page_len)`` pages instead of a dense ``max_len`` strip.
Allocation is a host-side free list over pages (`PagePool`); device
state is the pool pair plus per-slot ``lengths`` / ``tok`` vectors,
threaded as DONATED carry through the fused decode loop (decode.py).
Block tables are plain per-launch DATA (int32 ``[slots, max_pages]``
arrays), never part of an executable signature.

Page 0 is the reserved GARBAGE page: unmapped block-table entries are
0, so an inactive slot's masked ride-along write lands there and is
never attended (the positional mask ``kpos <= qpos`` already makes any
row beyond a slot's live length unreachable).  Freed pages are never
zeroed — reuse is metadata-only, O(1), zero device work.

``quant='int8'`` stores the pools as int8 with one float32 scale per
written row (per token, per kv head): ``scale = amax/127`` on write,
dequantized inside the attention window (decode.py) with float32
accumulation.  Off by default (``DecodeRuntime(kv_quant='int8')``).

`PrefixCache` maps chain-hashed FULL prompt pages to refcounted page
ids so requests sharing a prompt prefix map the same read-only pages
instead of re-prefilling them.  Shared pages are full by construction,
so a request's own writes (its prompt tail and generated tokens)
always land in freshly allocated pages — copy-on-extend needs no copy.
On by default (``DecodeRuntime(prefix_cache=False)`` turns it off).

A model with RECURRENT layers (decode.py's ``falcon_h1`` block, ssm.py)
keeps a second kind of state beside the pool, ``CacheConfig.recurrent``:
per slot and layer a float32 scan state and the convolution's last
inputs, ``ssm`` ``[slots, layers, heads, head_dim, d_state]`` and
``conv`` ``[slots, layers, d_conv - 1, channels]`` in the same donated
state dict.  A slot IS its row there: nothing is allocated or freed, the
chunk at offset 0 starts from zeros, and such rows cannot be shared, so
a runtime over them runs without the prefix cache.

The pool and the recurrent state need not cover the same layers.  A
``latent_moe`` model with a mixer per layer (decode.py's
``cfg['mixer']``, kda.py) ATTENDS in some layers and holds a matrix
state in the others: ``CacheConfig.layers`` counts the layers that
attend (the pool's layer axis, `page_bytes`), ``recurrent_layers`` the
layers that hold state (the layer axis of ``ssm`` and ``conv``), and
each layer knows its index on its own axis.  Left out,
``recurrent_layers`` is ``layers``: the ``falcon_h1`` block, every
layer of which does both.  The layers that attend may as well be GQA
layers over the K and V pools of the first geometry (``cfg['mixer']``
``'gqa'``), and a recurrent layer's state may be a convolution tail
ALONE (shortconv.py: ``recurrent`` ``(None, tail shape)``): then there
is a ``conv`` array and no ``ssm``.

A model with LATENT attention (decode.py's ``latent_moe`` block,
latent.py) keeps the second pool geometry, ``CacheConfig.latent``: ONE
pool ``[pages, layers, page_len, width]`` whose row is everything a
token leaves in a layer, the normalised compressed key/value vector
and behind it the one rotated key all heads share (``width`` =
``kv_lora_rank + qk_rope_head_dim``).  There is no V pool and there are
no kv heads: the values are the row's first ``latent`` columns.  The
page geometry ``[pages, layers, page_len, ...]`` is the same, so
`PagePool`, block tables, refcounts and `PrefixCache` count pages as
before and the kv-bytes gauges count what such a page holds.
"""
import hashlib
import threading
from collections import OrderedDict

import numpy as np

from ... import observability as _obs
from ...testing import faults as _faults

__all__ = ['CacheConfig', 'SlotAllocator', 'PagePool', 'PrefixCache',
           'init_state', 'default_page_len']


def default_page_len(max_len, want=8):
    """Largest divisor of ``max_len`` that is <= ``want`` (page length
    must tile the context window exactly)."""
    max_len, want = int(max_len), int(want)
    for d in range(min(want, max_len), 0, -1):
        if max_len % d == 0:
            return d
    return 1


class CacheConfig(object):
    """Geometry of the paged KV pool.

    ``slots`` is the decode-batch width (rows of ``lengths``/``tok``
    and of the block table); ``pages`` the pool depth INCLUDING the
    reserved garbage page 0; ``page_len`` tokens per page (must divide
    ``max_len``); ``quant`` is ``'none'`` or ``'int8'``; ``recurrent``
    is None or the (scan state, convolution tail) shapes of one slot in
    one layer (`ssm.state_shapes`, `kda.state_shapes`), both float32;
    the scan state's may be None (`shortconv.state_shapes`: a mixer that
    keeps its tail and nothing else).

    ``layers`` is the pool's layer axis: the layers that ATTEND.
    ``recurrent_layers`` is the recurrent state's: the layers that hold
    state, ``layers`` unless given (a model with a mixer per layer has
    fewer of each than it has layers).

    Two pool geometries.  Without ``latent``: a K and a V pool, each
    ``[pages, layers, page_len, kv_heads, head_dim]``.  With ``latent``
    (an int, the leading columns of a row that serve as values): ONE
    pool ``[pages, layers, page_len, head_dim]``, a row a token a layer
    (``kv_heads`` is 1, ``head_dim`` the row's width), no V pool and no
    int8 form.
    """
    __slots__ = ('slots', 'layers', 'kv_heads', 'max_len', 'head_dim',
                 'dtype', 'page_len', 'pages', 'quant', 'recurrent',
                 'latent', 'recurrent_layers')

    def __init__(self, slots, layers, kv_heads, max_len, head_dim,
                 dtype='float32', page_len=None, pages=None, quant='none',
                 recurrent=None, latent=None, recurrent_layers=None):
        if int(slots) < 1:
            raise ValueError('kv cache needs >= 1 slot, got %r' % (slots,))
        self.slots = int(slots)
        self.layers = int(layers)
        self.kv_heads = int(kv_heads)
        self.max_len = int(max_len)
        self.head_dim = int(head_dim)
        self.dtype = str(dtype)
        self.page_len = (default_page_len(self.max_len) if page_len is None
                         else int(page_len))
        if self.page_len < 1 or self.max_len % self.page_len:
            raise ValueError('page_len=%r must divide max_len=%d'
                             % (page_len, self.max_len))
        # default pool: dense-equivalent capacity (every slot can grow
        # to max_len) + the garbage page — callers shrink it to create
        # real memory pressure
        self.pages = (self.slots * self.max_pages + 1 if pages is None
                      else int(pages))
        if self.pages < 2:
            raise ValueError('kv pool needs >= 2 pages (page 0 is the '
                             'reserved garbage page), got %r' % (pages,))
        self.quant = str(quant or 'none')
        if self.quant not in ('none', 'int8'):
            raise ValueError("quant must be 'none' or 'int8', got %r"
                             % (quant,))
        self.recurrent = None if recurrent is None else tuple(
            None if shape is None else tuple(int(n) for n in shape)
            for shape in recurrent)
        self.recurrent_layers = (self.layers if recurrent_layers is None
                                 else int(recurrent_layers))
        self.latent = None if latent is None else int(latent)
        if self.latent is not None:
            if self.kv_heads != 1 or not 0 < self.latent <= self.head_dim:
                raise ValueError(
                    'a latent pool holds one row a token: kv_heads=1 and '
                    '0 < latent <= head_dim, got kv_heads=%d latent=%d '
                    'head_dim=%d' % (self.kv_heads, self.latent,
                                     self.head_dim))
            if self.quant != 'none':
                raise ValueError('a latent pool has no int8 form')

    @property
    def max_pages(self):
        """Block-table width: pages needed for a max_len sequence."""
        return self.max_len // self.page_len

    @property
    def store_dtype(self):
        return 'int8' if self.quant == 'int8' else self.dtype

    @property
    def pool_shape(self):
        if self.latent is not None:
            return (self.pages, self.layers, self.page_len, self.head_dim)
        return (self.pages, self.layers, self.page_len, self.kv_heads,
                self.head_dim)

    @property
    def scale_shape(self):
        """Per-row dequant scales (int8 mode): one f32 per written
        (page, layer, row, kv head)."""
        return (self.pages, self.layers, self.page_len, self.kv_heads)

    @property
    def page_shape(self):
        """Back-compat alias: the K (or V) pool shape."""
        return self.pool_shape

    def pages_for(self, n_tokens):
        """Pages a sequence of ``n_tokens`` occupies."""
        return -(-max(0, int(n_tokens)) // self.page_len)

    def page_bytes(self):
        """Bytes ONE page costs across both pools (K+V, plus the scale
        rows when quantized; the one pool of a latent cache) over the
        layers that attend — the unit of the kv_bytes gauges."""
        per = int(np.dtype(self.store_dtype).itemsize)
        elems = self.layers * self.kv_heads * self.page_len * self.head_dim
        if self.latent is not None:
            return per * elems
        b = 2 * per * elems
        if self.quant == 'int8':
            b += 2 * 4 * self.layers * self.kv_heads * self.page_len
        return b

    def recurrent_shapes(self):
        """{state name: shape} of the recurrent arrays over every slot
        and every layer that holds state; empty for a model without
        recurrent layers."""
        if self.recurrent is None:
            return {}
        lead = (self.slots, self.recurrent_layers)
        return {name: lead + shape
                for name, shape in zip(('ssm', 'conv'), self.recurrent)
                if shape is not None}

    def recurrent_bytes(self):
        """Bytes of the recurrent state (float32), every slot's."""
        return 4 * sum(int(np.prod(s))
                       for s in self.recurrent_shapes().values())

    def bytes(self):
        """Total bytes reserved for streams' state: the K+V pools and
        the recurrent state (capacity-planning helper)."""
        return self.pages * self.page_bytes() + self.recurrent_bytes()

    def dense_slot_bytes(self):
        """What ONE slot would reserve under the dense PR-11 layout (a
        full float32 max_len strip) — the denominator of the density
        headline."""
        per = int(np.dtype(self.dtype).itemsize)
        return 2 * per * (self.layers * self.kv_heads * self.max_len *
                          self.head_dim)

    def spec(self):
        """Declarative blob for the AOT cache fingerprint."""
        spec = {'slots': self.slots, 'layers': self.layers,
                'kv_heads': self.kv_heads, 'max_len': self.max_len,
                'head_dim': self.head_dim, 'dtype': self.dtype,
                'page_len': self.page_len, 'pages': self.pages,
                'quant': self.quant}
        if self.recurrent is not None:
            spec['recurrent'] = self.recurrent
            if self.recurrent_layers != self.layers:
                spec['recurrent_layers'] = self.recurrent_layers
        if self.latent is not None:
            spec['latent'] = self.latent
        return spec


def init_state(cache_cfg):
    """Fresh device-side decode state: the K/V page pools plus per-slot
    ``lengths`` (tokens written so far) and ``tok`` (the next token to
    feed — set by prefill, advanced by every decode step).  int8 mode
    adds the per-row dequant scale pools, recurrent layers their zeroed
    ``ssm`` and ``conv`` rows.  A latent cache has the one pool ``k``
    and no ``v``."""
    import jax.numpy as jnp
    k = jnp.zeros(cache_cfg.pool_shape, jnp.dtype(cache_cfg.store_dtype))
    st = {'k': k,
          'lengths': jnp.zeros((cache_cfg.slots,), jnp.int32),
          'tok': jnp.zeros((cache_cfg.slots,), jnp.int32)}
    if cache_cfg.latent is None:
        st['v'] = jnp.zeros_like(k)
    if cache_cfg.quant == 'int8':
        ks = jnp.zeros(cache_cfg.scale_shape, jnp.float32)
        st['k_scale'] = ks
        st['v_scale'] = jnp.zeros_like(ks)
    for name, shape in cache_cfg.recurrent_shapes().items():
        st[name] = jnp.zeros(shape, jnp.float32)
    return st


class SlotAllocator(object):
    """Free-list slot allocation.  Lowest-index-first for deterministic
    placement (the same admission order always lands on the same slots,
    which keeps soak runs reproducible).  Exports the live occupancy as
    the ``generation.kv_slots_in_use`` gauge.  Slots are cheap batch
    rows — the MEMORY gate is the PagePool."""

    def __init__(self, slots):
        self._capacity = int(slots)
        self._free = list(range(self._capacity))
        self._lock = threading.Lock()
        _obs.metrics.gauge('generation.kv_slots_in_use').set(0)

    @property
    def capacity(self):
        return self._capacity

    def free_count(self):
        with self._lock:
            return len(self._free)

    def in_use(self):
        return self._capacity - self.free_count()

    def alloc(self):
        """Claim the lowest free slot, or None when fully occupied."""
        with self._lock:
            if not self._free:
                return None
            slot = min(self._free)
            self._free.remove(slot)
            used = self._capacity - len(self._free)
        _obs.metrics.gauge('generation.kv_slots_in_use').set(used)
        return slot

    def free(self, slot):
        slot = int(slot)
        with self._lock:
            if not 0 <= slot < self._capacity:
                raise ValueError('slot %d out of range [0, %d)'
                                 % (slot, self._capacity))
            if slot in self._free:
                raise ValueError('double free of kv slot %d' % slot)
            self._free.append(slot)
            used = self._capacity - len(self._free)
        _obs.metrics.gauge('generation.kv_slots_in_use').set(used)

    def reset(self):
        with self._lock:
            self._free = list(range(self._capacity))
        _obs.metrics.gauge('generation.kv_slots_in_use').set(0)


class PagePool(object):
    """Refcounted free-list allocation over the KV page pool.

    Page 0 is reserved (the garbage page) and never handed out.
    ``alloc`` is all-or-nothing and lowest-index-first (deterministic
    placement); when short it asks the optional ``evict`` callback
    (the PrefixCache) to drop unreferenced cached pages, oldest first.
    Shared pages (prefix-cache hits) carry one refcount per holder and
    return to the free list only when the LAST holder releases.

    Exhaustion is a clean ``None`` — the scheduler turns it into
    admission backpressure (stay queued) or a terminal ``kv_oom``
    reply, never a truncation.  The ``kv_oom`` fault site forces the
    next allocation(s) to report exhaustion on demand.

    Gauges: ``generation.kv_pages_in_use``, ``generation.
    kv_bytes_reserved`` (fixed pool footprint) and ``generation.
    kv_bytes_live`` (pages in use x page_bytes).
    """

    def __init__(self, cache_cfg):
        self._cfg = cache_cfg
        self._page_bytes = cache_cfg.page_bytes()
        self._capacity = cache_cfg.pages - 1      # page 0 reserved
        self._free = list(range(1, cache_cfg.pages))
        self._refs = {}
        self._lock = threading.RLock()
        _obs.metrics.gauge('generation.kv_bytes_reserved').set(
            cache_cfg.bytes())
        self._set_gauges(0)

    def _set_gauges(self, used):
        _obs.metrics.gauge('generation.kv_pages_in_use').set(used)
        _obs.metrics.gauge('generation.kv_bytes_live').set(
            used * self._page_bytes)

    @property
    def capacity(self):
        """Allocatable pages (the garbage page excluded)."""
        return self._capacity

    @property
    def page_bytes(self):
        return self._page_bytes

    def free_count(self):
        with self._lock:
            return len(self._free)

    def in_use(self):
        return self._capacity - self.free_count()

    def alloc(self, n, evict=None):
        """Claim ``n`` pages (refcount 1 each) or None — all or
        nothing.  ``evict`` is called repeatedly (under the pool lock;
        it may re-enter release()) while the free list is short."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            if _faults.any_active() and _faults.fire('kv_oom'):
                return None
            while len(self._free) < n and evict is not None:
                if not evict():
                    break
            if len(self._free) < n:
                return None
            self._free.sort()
            got, self._free = self._free[:n], self._free[n:]
            for p in got:
                self._refs[p] = 1
            self._set_gauges(self._capacity - len(self._free))
        return got

    def retain(self, pages):
        """One more holder for already-allocated pages (shared prefix
        hits)."""
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError('retain of unallocated kv page %d'
                                     % int(p))
                self._refs[p] += 1

    def release(self, pages):
        """Drop one holder per page; pages reaching refcount 0 return
        to the free list (never zeroed — positional masking makes stale
        rows unreachable)."""
        with self._lock:
            for p in pages:
                p = int(p)
                c = self._refs.get(p)
                if c is None:
                    raise ValueError('release of free kv page %d' % p)
                if c > 1:
                    self._refs[p] = c - 1
                else:
                    del self._refs[p]
                    self._free.append(p)
            self._set_gauges(self._capacity - len(self._free))

    def refcount(self, page):
        with self._lock:
            return self._refs.get(int(page), 0)

    def reset(self):
        with self._lock:
            self._free = list(range(1, self._cfg.pages))
            self._refs.clear()
            self._set_gauges(0)


def _chain_digest(prev, tokens):
    h = hashlib.sha1(prev)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class PrefixCache(object):
    """Fingerprinted prefix -> pages map at FULL-page granularity.

    Keys are chained page digests: ``h_j = sha1(h_{j-1} || tokens of
    page j)``, so a depth-j entry certifies the whole prefix, not one
    page.  Each entry holds its own refcount (via PagePool.retain) on
    every page of its chain; `match` retains the matched pages again
    FOR THE CALLER, so a cached page is pinned while any stream maps
    it and survives (cached) after all streams retire.

    Matching is capped at ``(prompt_len - 1) // page_len`` pages so at
    least one suffix token always prefills — the final chunk's forward
    pass is what produces the request's first-token logits.  Shared
    pages hold bitwise-identical K/V to a cold prefill (position-
    absolute RoPE, deterministic per-row math), which is what makes
    hit-vs-cold streams bitwise equal (pinned in tests).

    Eviction is deterministic: `evict_one` drops the OLDEST entry (its
    retains; pages free only once unreferenced) — wired as PagePool's
    under-pressure callback.
    """

    def __init__(self, pool, page_len):
        self._pool = pool
        self._page_len = int(page_len)
        self._entries = OrderedDict()     # digest -> tuple(pages)
        self._lock = threading.Lock()

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def _keys_for(self, prompt, depth):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        keys, h = [], b'pt-prefix-v1'
        for j in range(depth):
            h = _chain_digest(
                h, prompt[j * self._page_len:(j + 1) * self._page_len])
            keys.append(h)
        return keys

    def match(self, prompt):
        """Longest cached full-page prefix of ``prompt``.  Returns the
        page list (retained for the caller — release them with the rest
        of the request's pages) — [] on a miss."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cap = max(0, (prompt.size - 1) // self._page_len)
        if cap == 0:
            return []
        keys = self._keys_for(prompt, cap)
        with self._lock:
            for j in range(cap, 0, -1):
                pages = self._entries.get(keys[j - 1])
                if pages is not None:
                    self._pool.retain(pages)
                    _obs.metrics.counter('generation.prefix_hits').inc()
                    _obs.metrics.counter(
                        'generation.prefix_pages_reused').inc(len(pages))
                    return list(pages)
        return []

    def insert(self, prompt, pages):
        """Publish a freshly-prefilled request's FULL pages (``pages``
        = its block-table prefix).  Every depth 1..full gets an entry
        so later prompts sharing a shorter prefix still hit; existing
        entries are kept (first writer wins — contents are bitwise
        identical by construction)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        full = prompt.size // self._page_len
        full = min(full, len(pages))
        if full == 0:
            return 0
        keys = self._keys_for(prompt, full)
        added = 0
        with self._lock:
            for j in range(1, full + 1):
                if keys[j - 1] in self._entries:
                    continue
                chain = tuple(int(p) for p in pages[:j])
                self._pool.retain(chain)
                self._entries[keys[j - 1]] = chain
                added += 1
        if added:
            _obs.metrics.counter('generation.prefix_inserts').inc(added)
        return added

    def evict_one(self):
        """Drop the oldest entry (deterministic).  Returns True when an
        entry was dropped — its pages free only if nothing else holds
        them, so PagePool.alloc keeps calling until satisfied or
        empty."""
        with self._lock:
            if not self._entries:
                return False
            _key, pages = self._entries.popitem(last=False)
        self._pool.release(pages)
        _obs.metrics.counter('generation.prefix_evictions').inc()
        return True

    def reset(self):
        while self.evict_one():
            pass
