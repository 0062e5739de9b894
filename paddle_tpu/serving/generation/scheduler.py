"""GenerationEngine — prefill/decode continuous batching on the serving
engine.

The PR-8 ServingEngine coalesces same-signature one-shot requests into
superbatches; generation requests are long-lived instead, so this
subclass replaces the dispatch loop with a round-based scheduler over
the DecodeRuntime's KV slots:

  round := sweep (cancel / deadline / TTFT / ITL)
         → claim queued requests into free slots WITH pages
         → ONE prefill chunk for the oldest still-prefilling request
         → ONE fused decode (or speculative verify) window for ALL
           decoding slots

Memory admission is PAGED (kv_cache.PagePool): a queued request is
claimed only when a slot AND the pages for its prompt plus one decode
window are both available — pool shortage leaves it QUEUED
(``generation.kv_backpressure``), it is never truncated.  A request
whose prompt+max_new could not fit even an idle pool is refused at
admission with reason ``kv_oom``; a stream whose window cannot grow
mid-flight retires with a terminal ``error``/``kv_oom`` reply and a
flight dump carrying the pool gauge snapshot.  Prefix-cache hits skip
straight to their first unshared chunk (`DecodeRuntime.try_begin`) and
completed prompts are published for later requests (`promote_prefix`).

With ``GenerationConfig.speculative`` (default off) the decode window
becomes draft-propose + fused VERIFY: a host-side n-gram draft proposes K-1 tokens per stream, one
batched verify pass samples the target model at every position, and
each stream keeps the longest accepted prefix
(``generation.spec_proposed`` / ``spec_accepted``) — greedy streams
are bitwise identical to non-speculative decode.

Long prompts advance one bounded chunk per round, interleaved with
full-width decode windows — a prompt of any length never stalls token
delivery for running requests (``generation.mixed_dispatches`` counts
rounds that did both).  A request lives in one slot from prefill
through decode (migration is in place by construction) and every
admitted request keeps the PR-8 guarantee: exactly one terminal reply —
``ok`` (reason ``eos`` / ``max_tokens``), ``deadline_exceeded`` (queue
wait, overall deadline, TTFT or ITL budget), ``shed`` (cancel, drain),
``rejected`` (admission), or ``error`` (decode fault / mid-stream
``kv_oom``) — through drain, stop, and injected ``decode_step`` /
``kv_oom`` faults alike.

Token-level SLOs: ``serving.ttft_ms`` observes submit→first-token per
request, ``serving.itl_ms`` the amortized inter-token gap; both export
through telemetry_snapshot('serving') (docs/generation.md).

Measured from inside (docs/observability.md): every request's life is
three spans that tile its ``serving.request`` and share its trace id —
``serving.queue`` (generate() → slot granted), ``serving.prefill_phase``
(→ first token) and ``serving.decode_phase`` (→ terminal reply) — and
every scheduler round is a ``serving.round`` span with ``serving.admit``
/ ``serving.prefill`` / ``serving.decode_step`` / ``serving.emit``
children; waiting with nothing to do is ``serving.idle_wait``.  Each
boundary also feeds a ``generation.*`` time or work counter, taken on
the span's own clock.
"""
import time

import numpy as np

from ... import observability as _obs
from ...observability import flight as _flight
from ...observability import trace_context as _tc
from ...testing import faults as _faults
from ..engine import (DEADLINE_EXCEEDED, DRAINING, ERROR, OK, SHED,
                      ServingEngine, _Request)
from .sampling import SamplingParams
from .streaming import TokenStream

__all__ = ['GenerationConfig', 'GenerationEngine']

class GenerationConfig(object):
    """Generation-side knobs (the queue/rate/breaker knobs stay on
    ServingConfig).  ``decode_window`` is K, the tokens-per-launch of
    the fused decode scan; ``ttft_timeout_s`` / ``itl_timeout_s`` are
    the default per-token SLO budgets (overridable per request);
    ``speculative`` swaps the decode window for draft + fused verify
    (default off)."""

    def __init__(self, decode_window=4, eos_id=None, max_new_default=16,
                 ttft_timeout_s=None, itl_timeout_s=None,
                 speculative=False):
        if int(decode_window) < 1:
            raise ValueError('decode_window must be >= 1')
        self.decode_window = int(decode_window)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.max_new_default = int(max_new_default)
        self.ttft_timeout_s = ttft_timeout_s
        self.itl_timeout_s = itl_timeout_s
        self.speculative = bool(speculative)


class _GenRequest(_Request):
    __slots__ = ('prompt', 'max_new', 'params', 'ttft_timeout',
                 'itl_timeout', 'slot', 'offset', 'produced',
                 't_last_token', 't_grant', 't_first', 'round_grant',
                 'rounds_first', 'chunks', 'skipped', 'windows')

    def __init__(self, prompt, max_new, params, deadline, t_submit,
                 ttft_timeout=None, itl_timeout=None, trace=None,
                 t_pc=None):
        _Request.__init__(self, {'prompt': prompt}, 1,
                          ('generate',), deadline, t_submit,
                          trace=trace, t_pc=t_pc)
        self.future = TokenStream()   # streaming reply handle
        if trace is not None:
            self.future.traceparent = trace.to_traceparent()
        self.prompt = prompt
        self.max_new = int(max_new)
        self.params = params
        self.ttft_timeout = ttft_timeout
        self.itl_timeout = itl_timeout
        self.slot = None
        self.offset = 0          # prompt tokens prefilled so far
        self.produced = 0        # tokens streamed so far
        self.t_last_token = None
        # phase marks on the recorder's clock (perf_counter; None until
        # reached, and always None with PT_OBS=0) and what the phase
        # spans carry as args
        self.t_grant = None      # slot granted: queue -> prefill phase
        self.t_first = None      # first token: prefill -> decode phase
        self.round_grant = 0     # scheduler round of the grant
        self.rounds_first = 0    # rounds from the grant to the first token
        self.chunks = 0          # prefill chunks (or ring shots) run
        self.skipped = 0         # prompt tokens the prefix cache skipped
        self.windows = 0         # decode windows this stream rode


class GenerationEngine(ServingEngine):
    """Streaming decode server over one :class:`DecodeRuntime`.

        engine = GenerationEngine(runtime).start()
        stream = engine.generate([1, 2, 3], max_new=32, temperature=0.8,
                                 top_k=40, seed=7)
        for tok in stream.tokens():
            ...
        reply = stream.result()       # ServeResult, reason='eos'/...

    Admission (queue bound, overflow policy, rate limit, drain gate) is
    inherited; ``submit()`` is closed off — generation requests go
    through :meth:`generate`.
    """

    def __init__(self, runtime, config=None, gen_config=None,
                 clock=time.monotonic):
        ServingEngine.__init__(self, self._no_backend, bucketer=None,
                               config=config, clock=clock)
        self.runtime = runtime
        self._gen = gen_config or GenerationConfig()
        if self._gen.speculative and runtime.recurrent:
            raise ValueError(
                'speculative decode rolls lengths back; the recurrent '
                'state of this runtime\'s model cannot be rolled back')
        self._active = []        # slot-holding requests, admission order
        self._round_no = 0       # rounds with work so far

    @staticmethod
    def _no_backend(feed):
        raise TypeError('GenerationEngine has no one-shot backend; '
                        'requests go through generate()')

    def submit(self, feed, timeout_s=None):
        raise TypeError('GenerationEngine serves token streams — use '
                        'generate(prompt_ids, ...) instead of submit()')

    # ----------------------------------------------------- admission
    def _rejected_gen(self, t_submit, reason, message, trace, t_pc):
        # the base _rejected builds a plain ServeFuture; generation
        # refusals must still hand back an (already-closed) TokenStream
        from ..engine import REJECTED, ServeResult
        fut = TokenStream()
        if trace is not None:
            fut.traceparent = trace.to_traceparent()
        fut._resolve(ServeResult(REJECTED, error=message, reason=reason,
                                 latency_s=self._clock() - t_submit))
        _obs.metrics.counter('serving.rejected').inc()
        _obs.metrics.counter('serving.rejected.%s' % reason).inc()
        self._emit_root_span(trace, t_pc, REJECTED, reason=reason)
        return fut

    def generate(self, prompt_ids, max_new=None, temperature=0.0, top_k=0,
                 seed=0, timeout_s=None, ttft_timeout_s=None,
                 itl_timeout_s=None):
        """Admit one generation request; always returns a
        :class:`TokenStream` (refusals come back already terminal with a
        named reason, never an exception and never silence)."""
        t_submit = self._clock()
        obs_on = _obs.enabled()
        trace = _tc.TraceContext.new() if obs_on else None
        t_pc = time.perf_counter() if obs_on else None
        _obs.metrics.counter('serving.submitted').inc()
        try:
            prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
            params = SamplingParams(temperature=temperature, top_k=top_k,
                                    seed=seed)
        except Exception as e:  # noqa: BLE001 - refusal, not crash
            return self._rejected_gen(t_submit, 'bad_request',
                                      'unusable request: %r' % (e,),
                                      trace, t_pc)
        if prompt.size == 0:
            return self._rejected_gen(t_submit, 'bad_request',
                                      'empty prompt', trace, t_pc)
        if max_new is None:
            max_new = self._gen.max_new_default
        if int(max_new) < 1:
            return self._rejected_gen(t_submit, 'bad_request',
                                      'max_new must be >= 1, got %r'
                                      % (max_new,), trace, t_pc)
        limit = self.runtime.max_len
        if prompt.size + int(max_new) > limit:
            # the hard context ceiling: refuse with the arithmetic
            # spelled out — a prompt is NEVER silently truncated
            return self._rejected_gen(
                t_submit, 'too_long',
                'prompt of %d tokens + max_new=%d exceeds the runtime '
                'context window max_len=%d; shorten the prompt or lower '
                'max_new — nothing is silently truncated'
                % (prompt.size, int(max_new), limit), trace, t_pc)
        never_fits = getattr(self.runtime, 'never_fits', None)
        if never_fits is not None and never_fits(prompt.size, int(max_new)):
            # transient pool pressure means WAIT (backpressure), but a
            # request bigger than the whole pool can never run: refuse
            # with the arithmetic spelled out rather than deadlock it
            return self._rejected_gen(
                t_submit, 'kv_oom',
                'prompt of %d tokens + max_new=%d needs more KV pages '
                'than the entire pool holds (%d pages of %d tokens); '
                'nothing is silently truncated'
                % (prompt.size, int(max_new), self.runtime.pool.capacity,
                   self.runtime.cache.page_len), trace, t_pc)
        if timeout_s is None:
            timeout_s = self._cfg.default_timeout_s
        deadline = None
        if timeout_s is not None:
            if timeout_s <= 0:
                return self._rejected_gen(
                    t_submit, 'deadline',
                    'deadline already expired at admission '
                    '(timeout_s=%r)' % timeout_s, trace, t_pc)
            deadline = t_submit + float(timeout_s)
        if self._rate is not None and not self._rate.try_acquire():
            return self._rejected_gen(
                t_submit, 'rate', 'token-bucket rate limit exceeded '
                '(rate_qps=%r)' % self._cfg.rate_qps, trace, t_pc)
        req = _GenRequest(
            prompt, int(max_new), params, deadline, t_submit,
            ttft_timeout=(self._gen.ttft_timeout_s if ttft_timeout_s is None
                          else ttft_timeout_s),
            itl_timeout=(self._gen.itl_timeout_s if itl_timeout_s is None
                         else itl_timeout_s),
            trace=trace, t_pc=t_pc)
        fut = self._admit(req, t_submit)
        if trace is not None:
            t_now = time.perf_counter()
            _obs.tracing.recorder().add_complete(
                'serving.submit', t_pc, t_now, cat='serving',
                args=trace.span_args(prompt_tokens=int(prompt.size),
                                     max_new=int(max_new)))
            _obs.tracing.add_flow(trace.trace_id[:16], 's', t_pc,
                                  name='serving.link', cat='serving')
        return fut

    # ----------------------------------------------------- scheduling
    def _loop(self):
        try:
            while self._round():
                pass
        finally:
            # slot-holding requests get their terminal (shed) reply
            # BEFORE the base deadlock audit counts stragglers
            self._shed_active()
            self._finish_stop()

    def _round(self):
        """One scheduler round; False means the loop should exit."""
        with self._cond:
            if not self._queue and not self._active:
                # nobody asked: the chip is idle for want of requests
                alive = True
                idle = _obs.span('serving.idle_wait', cat='serving')
                with idle:
                    while not self._queue and not self._active:
                        if self._stopping or self._state == DRAINING:
                            alive = False
                            break
                        self._cond.wait(0.05)
                if _obs.enabled():
                    _obs.metrics.counter('generation.idle_wait_s').inc(
                        idle.seconds)
                if not alive:
                    return False
            if self._stopping:
                return False
        self._round_no += 1
        work = _obs.span('serving.round', cat='serving')
        with work:
            with _obs.span('serving.admit', cat='serving'):
                if not self._admit_round():
                    return False
                self._sweep_active()
            did_prefill = self._prefill_step()
            did_decode = self._decode_step()
        if _obs.enabled():
            _obs.metrics.counter('generation.round_s').inc(work.seconds)
        if did_prefill and did_decode:
            _obs.metrics.counter('generation.mixed_dispatches').inc()
        return True

    def _admit_round(self):
        """Drop expired/cancelled queued requests and claim queued ones
        into free slots WITH pages; False when the engine is stopping."""
        with self._cond:
            if self._stopping:
                return False
            now = self._clock()
            expired, dropped = [], []
            for r in list(self._queue):
                if r.deadline is not None and r.deadline <= now:
                    expired.append(r)
                elif r.future.cancelled:
                    dropped.append(r)
            if expired or dropped:
                gone = set(map(id, expired + dropped))
                self._queue = type(self._queue)(
                    r for r in self._queue if id(r) not in gone)
            while self._queue:
                nxt = self._queue[0]
                slot = self.runtime.alloc_slot()
                if slot is None:
                    break
                start = self.runtime.try_begin(slot, nxt.prompt,
                                               self._gen.decode_window)
                if start is None:
                    # pool shortage: the request STAYS QUEUED (admission
                    # backpressure) and the slot goes back — retiring
                    # streams free pages and the next round re-claims
                    self.runtime.free_slot(slot)
                    _obs.metrics.counter(
                        'generation.kv_backpressure').inc()
                    break
                r = self._queue.popleft()
                r.slot = slot
                r.offset = int(start)   # prefix-cache hits skip ahead
                self._active.append(r)
                if r.t_pc is not None:
                    # queue -> prefill phase, where the slot is granted
                    r.t_grant = time.perf_counter()
                    r.round_grant = self._round_no
                    r.skipped = int(start)
                    _obs.metrics.counter('generation.admitted').inc()
                    _obs.metrics.counter('generation.queue_wait_s').inc(
                        r.t_grant - r.t_pc)
            _obs.metrics.gauge('serving.queue_depth').set(len(self._queue))
            self._cond.notify_all()
        for r in expired:
            self._resolve(r, DEADLINE_EXCEEDED, reason='queue_wait',
                          error='deadline expired while queued; dropped '
                                'pre-dispatch (no compute was spent)')
        for r in dropped:
            _obs.metrics.counter('generation.cancelled').inc()
            self._resolve(r, SHED, reason='cancelled',
                          error='cancelled while queued')
        return True

    def _sweep_active(self):
        """Terminal conditions checked at every round boundary."""
        now = self._clock()
        for r in list(self._active):
            if r.future.cancelled:
                _obs.metrics.counter('generation.cancelled').inc()
                self._retire(r, SHED, reason='cancelled',
                             error='cancelled by the client mid-stream')
            elif r.deadline is not None and r.deadline <= now:
                self._retire(r, DEADLINE_EXCEEDED, reason='deadline',
                             error='overall deadline expired mid-stream')
            elif r.ttft_timeout is not None and r.produced == 0 \
                    and now - r.t_submit > r.ttft_timeout:
                self._retire(r, DEADLINE_EXCEEDED, reason='ttft',
                             error='no first token within the TTFT '
                                   'budget (%gs)' % r.ttft_timeout)
            elif r.itl_timeout is not None and r.produced > 0 \
                    and now - r.t_last_token > r.itl_timeout:
                self._retire(r, DEADLINE_EXCEEDED, reason='itl',
                             error='inter-token gap exceeded the ITL '
                                   'budget (%gs)' % r.itl_timeout)

    def _prefill_step(self):
        """Advance the OLDEST still-prefilling request by one chunk (or
        one ring shot).  Bounded work per round: long prompts cannot
        starve the decode batch."""
        rt = self.runtime
        pre = [r for r in self._active if r.offset < r.prompt.size]
        if not pre:
            return False
        r = min(pre, key=lambda x: x.t_submit)
        use_ring = (rt.mesh is not None and r.offset == 0
                    and r.prompt.size >= rt.ring_min_len)
        with _obs.span('serving.prefill', cat='serving') as sp:
            if _obs.enabled():
                sp.args.update(slot=int(r.slot), ring=bool(use_ring))
                if r.trace is not None:
                    sp.args.update(trace_id=r.trace.trace_id,
                                   parent_span_id=r.trace.span_id)
            try:
                if use_ring:
                    first, _logits = rt.prefill_ring(r.slot, r.prompt,
                                                     r.params)
                    r.offset = int(r.prompt.size)
                else:
                    chunk = r.prompt[r.offset:r.offset + rt.prefill_chunk]
                    first, _logits = rt.prefill(r.slot, chunk, r.offset,
                                                r.params)
                    r.offset += int(chunk.size)
            except BaseException as e:  # noqa: BLE001 - replied per request
                self.breaker.record_failure()
                _obs.metrics.counter('serving.batch_failures').inc()
                _flight.record('serving.prefill_failure',
                               error=repr(e)[:300])
                self._retire(r, ERROR, error=e, reason='prefill')
                _flight.maybe_dump('serving_prefill_failure')
                return True
            if _obs.enabled():
                sp.args['offset'] = int(r.offset)
        r.chunks += 1
        _obs.metrics.counter('generation.prefill_chunks').inc()
        if r.offset >= r.prompt.size:
            # prompt complete: publish its full pages for later
            # prefix-sharing requests, then emit the final chunk's
            # sample — the first token (TTFT)
            self.runtime.promote_prefix(r.slot, r.prompt)
            with _obs.span('serving.emit', cat='serving'):
                self._emit_tokens(r, [int(first)])
        return True

    def _decode_step(self):
        """One fused K-token window (plain decode or speculative
        verify) over every decoding slot."""
        rt = self.runtime
        dec = [r for r in self._active if r.offset >= r.prompt.size]
        if not dec:
            return False
        S, K = rt.slots, self._gen.decode_window
        # grow every stream's block table to cover this window FIRST: a
        # stream the pool cannot grow gets a terminal kv_oom reply (it
        # is never truncated and never silently stalled) and its freed
        # pages may rescue the streams after it
        for r in list(dec):
            if rt.ensure_capacity(r.slot, int(rt.host_len[r.slot]) + K):
                continue
            _obs.metrics.counter('generation.kv_oom').inc()
            snap = rt.pool_snapshot()
            _flight.record('serving.kv_oom', slot=int(r.slot),
                           produced=int(r.produced), **snap)
            dec.remove(r)
            self._retire(
                r, ERROR, reason='kv_oom',
                error='KV page pool exhausted mid-stream (%d/%d pages '
                      'live); partial output is in tokens_so_far()'
                      % (snap['pages_in_use'], snap['pages_capacity']))
            _flight.maybe_dump('kv_oom', extra={'kv_pool': snap})
        if not dec:
            return False
        active = np.zeros(S, bool)
        seeds = np.zeros(S, np.int32)
        temps = np.zeros(S, np.float32)
        topks = np.zeros(S, np.int32)
        for r in dec:
            active[r.slot] = True
            seeds[r.slot] = r.params.seed
            temps[r.slot] = r.params.temperature
            topks[r.slot] = r.params.top_k
        speculative = self._gen.speculative and K > 1
        with _obs.span('serving.decode_step', cat='serving') as sp:
            if _obs.enabled():
                sp.args.update(
                    steps=int(K), requests=len(dec),
                    speculative=bool(speculative),
                    links=[r.trace.trace_id for r in dec
                           if r.trace is not None])
            try:
                if _faults.any_active():
                    _faults.maybe_fail('decode_step')
                if speculative:
                    emitted = self._verify_step(dec, K, active, seeds,
                                                temps, topks)
                else:
                    toks = rt.decode_window(K, active, seeds, temps, topks)
                    emitted = {id(r): [int(t) for t in toks[r.slot]]
                               for r in dec}
            except BaseException as e:  # noqa: BLE001 - replied per request
                self.breaker.record_failure()
                _obs.metrics.counter('serving.batch_failures').inc()
                _flight.record('serving.decode_failure',
                               error=repr(e)[:300], requests=len(dec),
                               steps=int(K))
                for r in dec:
                    self._retire(r, ERROR, error=e, reason='decode_step')
                _flight.maybe_dump('serving_decode_failure')
                return False
        self.breaker.record_success(cold=False)
        _obs.metrics.counter('generation.decode_windows').inc()
        with _obs.span('serving.emit', cat='serving'):
            for r in list(dec):
                r.windows += 1
                self._emit_tokens(r, emitted[id(r)])
        return True

    def _verify_step(self, dec, K, active, seeds, temps, topks):
        """One speculative window: build each stream's fed row (last
        emitted token + n-gram draft), run the fused verify, keep the
        longest accepted prefix per stream, and roll the runtime back
        to the committed lengths.  Returns {id(request): tokens}."""
        from .sampling import draft_ngram
        rt = self.runtime
        S = rt.slots
        fed = np.zeros((S, K), np.int32)
        for r in dec:
            fed[r.slot, 0] = rt.host_tok[r.slot]
            ctx = np.concatenate([
                r.prompt, np.asarray(r.future.tokens_so_far(), np.int32)])
            fed[r.slot, 1:] = draft_ngram(ctx, K - 1)
        g = rt.verify_window(K, fed, active, seeds, temps, topks)
        emitted, accepted, kept = {}, {}, 0
        for r in dec:
            row = g[r.slot]
            m = 1
            while m < K and fed[r.slot, m] == row[m - 1]:
                m += 1
            accepted[r.slot] = (m, int(row[m - 1]))
            emitted[id(r)] = [int(t) for t in row[:m]]
            kept += m - 1
        _obs.metrics.counter('generation.spec_proposed').inc(
            (K - 1) * len(dec))
        _obs.metrics.counter('generation.spec_accepted').inc(kept)
        # commit BEFORE emitting: finishing streams retire (and free
        # their pages) with the runtime already consistent
        rt.commit_speculation(accepted)
        return emitted

    # ----------------------------------------------------- token path
    def _emit_tokens(self, r, toks):
        """Stream tokens to the client; finish on EOS / max_tokens."""
        now = self._clock()
        first = r.produced == 0
        if first:
            _obs.metrics.histogram('serving.ttft_ms').observe(
                max(0.0, (now - r.t_submit) * 1e3))
            if r.t_grant is not None:
                # prefill -> decode phase, where the first token leaves
                r.t_first = time.perf_counter()
                r.rounds_first = self._round_no - r.round_grant + 1
                _obs.metrics.counter('generation.first_tokens').inc()
                _obs.metrics.counter('generation.prefill_phase_s').inc(
                    r.t_first - r.t_grant)
                _obs.metrics.counter(
                    'generation.rounds_to_first_token').inc(r.rounds_first)
        elif toks:
            # the fused window delivers K tokens at once: observe the
            # amortized per-token gap K times so the ITL histogram
            # weighs every token, not every window
            gap_ms = max(0.0, (now - r.t_last_token) * 1e3) / len(toks)
            h = _obs.metrics.histogram('serving.itl_ms')
            for _ in range(min(len(toks), r.max_new - r.produced)):
                h.observe(gap_ms)
        finish = None
        for tok in toks:
            r.future._push(tok)
            r.produced += 1
            _obs.metrics.counter('generation.tokens').inc()
            if self._gen.eos_id is not None and tok == self._gen.eos_id:
                finish = 'eos'
                break
            if r.produced >= r.max_new:
                finish = 'max_tokens'
                break
        r.t_last_token = now
        if finish is not None:
            ids = np.asarray(r.future.tokens_so_far(), np.int64)
            self._retire(r, OK, outputs=[ids], reason=finish)

    def _emit_request_spans(self, req, status, reason):
        """The phases a request reached, then its root: `serving.queue`,
        `serving.prefill_phase` and `serving.decode_phase` share the
        root's trace id, name it as their parent and tile it exactly
        (the last phase and the root end on one clock reading)."""
        if req.trace is None or req.t_pc is None:
            return
        t_end = time.perf_counter()
        rec = _obs.tracing.recorder()
        ids = {'trace_id': req.trace.trace_id,
               'parent_span_id': req.trace.span_id}
        t_grant = req.t_grant
        rec.add_complete('serving.queue', req.t_pc,
                         t_end if t_grant is None else t_grant,
                         cat='serving', args=dict(ids))
        if t_grant is not None:
            t_first = req.t_first
            rec.add_complete(
                'serving.prefill_phase', t_grant,
                t_end if t_first is None else t_first, cat='serving',
                args=dict(ids, chunks=req.chunks, rounds=req.rounds_first,
                          prefix_tokens_skipped=req.skipped))
            if t_first is not None:
                rec.add_complete(
                    'serving.decode_phase', t_first, t_end, cat='serving',
                    args=dict(ids, tokens=req.produced,
                              windows=req.windows))
        self._emit_root_span(req.trace, req.t_pc, status, reason=reason,
                             rows=req.rows, t_end=t_end)

    def _retire(self, r, status, outputs=None, error=None, reason=None):
        """Terminal resolution for a slot-holding request: drop it from
        the round-robin, release the KV slot, resolve the stream."""
        if r in self._active:
            self._active.remove(r)
        if r.slot is not None:
            self.runtime.free_slot(r.slot)
            r.slot = None
        self._resolve(r, status, outputs=outputs, error=error,
                      reason=reason)

    def _shed_active(self):
        for r in list(self._active):
            self._retire(r, SHED, reason='shutdown',
                         error='engine stopped mid-stream; partial output '
                               'is in tokens_so_far()')
