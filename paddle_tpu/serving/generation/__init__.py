"""paddle_tpu.serving.generation — streaming autoregressive decode.

The generation subsystem turns the ServingEngine into a streaming
decode server (docs/generation.md):

  * `kv_cache` — PAGED KV storage: one shared page pool
    ``[pages, layers, kv_heads, page_len, head_dim]`` plus per-slot
    block tables, refcounted free-list page allocation (`PagePool`),
    optional int8 quantization (``kv_quant='int8'``) and a fingerprinted
    shared-prefix page cache (`PrefixCache`, ``prefix_cache=``) — a
    stream's footprint is ceil(len/page_len) pages, not max_len rows.
  * `decode` — the fused prefill/decode/verify executables: K decode
    tokens launch as ONE `lax.scan` with the page pools as donated
    carry (no host round-trips inside the window); block tables are
    per-launch DATA, so one warm executable serves every page
    assignment; chunked/ring prefill; speculative verify windows;
    AOT-compiled and persisted through the compile-cache disk tier.
  * `ssm` — the Mamba-2 mixer of the `falcon_h1` block kind (a model
    dict with ``block: 'falcon_h1'``): the chunked scan a prefill chunk
    runs from and into a slot's recurrent state, and the single step of
    a decode window; that state lives beside the page pool in the same
    donated state dict (`CacheConfig.recurrent`).
  * `latent`, `experts` — the `latent_moe` block kind (a model dict
    with ``block: 'latent_moe'``): multi-head latent attention over the
    second pool geometry, ONE row ``[c_kv ; k_r]`` a token a layer
    (`CacheConfig.latent`), expanded in a prefill chunk and absorbed in
    a decode step; and per layer (``cfg['ffn']``) a dense SwiGLU or
    routed experts beside a shared one, as one expert-parallel rank
    holds them.
  * `kda` — Kimi Delta Attention, the mixer of the layers a
    `latent_moe` model marks ``'kda'`` in ``cfg['mixer']`` (a mixer per
    layer as data): a gated delta rule over a float32 matrix state a
    head with a decay a channel, in the chunk form for a prefill chunk
    and a single step for a decode window; its state lives in the
    recurrent arrays, whose layer axis counts the layers that hold
    state while the pool's counts those that attend
    (`CacheConfig.recurrent_layers`).
  * `shortconv` — the gated short convolution, the mixer of the layers
    a `latent_moe` model marks ``'conv'``: a causal 3-tap depthwise
    filter over ``B * x`` under the gate ``C``, whose whole state is
    the last two rows of its own input (a tail and no scan state in the
    recurrent arrays).  Beside it a model may attend through ``'gqa'``
    layers, the dense block's attention over the K and V pools with a
    norm on every query and key head (``cfg['qk_norm']``).
  * `sampling` — greedy / temperature / top-k draws keyed by
    ``(request seed, absolute position)`` only, so fused and sequential
    decode sample bitwise-identical streams (ops/sampling.py).
  * `scheduler` — mixed prefill+decode continuous batching on the
    PR-8 engine: prompts prefill one chunk per round, interleaved with
    full-width decode (or speculative draft+verify) windows; page-pool
    shortage is admission BACKPRESSURE, never truncation, and a stream
    that cannot grow retires with a terminal ``kv_oom`` reply.
  * `streaming` — per-token delivery through the engine reply path
    with TTFT/ITL SLOs and EOS / max-token / cancel termination, all
    resolving the terminal-reply invariant exactly once.

    from paddle_tpu.serving import generation
    engine = generation.GenerationEngine(runtime).start()
    stream = engine.generate(prompt_ids, max_new=32, temperature=0.8,
                             top_k=40, seed=7)
    for tok in stream.tokens():
        ...
    result = stream.result()          # ServeResult, reason='eos'/...
"""
from .kv_cache import (CacheConfig, PagePool, PrefixCache,  # noqa
                       SlotAllocator, default_page_len, init_state)
from .decode import (DecodeRuntime, dense_reference,  # noqa
                     random_weights, weight_names, weight_shapes)
from . import experts, kda, latent, shortconv, ssm  # noqa
from .sampling import SamplingParams, draft_ngram  # noqa
from .streaming import TokenStream  # noqa
from .scheduler import GenerationConfig, GenerationEngine  # noqa

__all__ = ['CacheConfig', 'PagePool', 'PrefixCache', 'SlotAllocator',
           'default_page_len', 'init_state', 'DecodeRuntime',
           'dense_reference', 'random_weights', 'weight_names',
           'weight_shapes', 'experts', 'kda', 'latent', 'shortconv', 'ssm',
           'SamplingParams', 'draft_ngram', 'TokenStream',
           'GenerationConfig', 'GenerationEngine']
