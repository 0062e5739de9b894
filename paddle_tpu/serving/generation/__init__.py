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
  * `mixer` — what a layer's MIXER answers to the runtime, one entry a
    kind in `decode._MIXERS` (docs/generation.md, "A layer and its
    mixers"); a model dict names them per layer (`decode._layers`):

    kind      module      stores a layer            kernel of its step
    'gqa'     decode      K and V rows in the pool  `paged_attention`
    'latent'  latent      one row ``[c_kv ; k_r]``  `latent_attention`
    'ssm'     ssm         scan state + conv tail    `ssm.ssm_step`
    'kda'     kda         matrix state + conv tails `kda.kda_step`
    'conv'    shortconv   its input's last rows     none

  * `experts` — the feed-forward beside the dense SwiGLU: routed experts
    beside a shared one (or none), as ONE expert-parallel rank holds them.
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
