"""GenerationEngine — prefill/decode continuous batching on the serving
engine.

The PR-8 ServingEngine coalesces same-signature one-shot requests into
superbatches; generation requests are long-lived instead, so this
subclass replaces the dispatch loop with a round-based scheduler over
the DecodeRuntime's KV slots:

  round := while the chip runs window N:
           claim queued requests into free slots WITH pages
         → sweep (cancel / deadline / TTFT / ITL)
         → plan ONE prefill chunk for the oldest still-prefilling
           request and ONE fused decode window N+1 for ALL the slots
           decoding then; grow their block tables; upload both
           launches' arguments (`DecodeRuntime.stage_*`)
         at the boundary:
           read N's tokens → ends nobody foresaw (EOS, cancel,
           deadline) → launch the chunk and window N+1 back to back
         while they run:
           emit N's tokens → read the chunk's sample, emit it

A launch does not block (decode.py): the runtime's calls return their
results still on the device, so the host work of a round lies UNDER a
running launch and only read → end check → dispatch stands between two.
The host knows who rides N+1 before N ends: a rider whose
``produced + K`` reaches ``max_new`` leaves, the stream whose last
chunk is picked joins (its first token is fed on the device).  Never
two windows queued: a new arrival would wait behind a committed window,
one more window on every TTFT; a request that arrives before N lands
still gets its first chunk directly behind N.  Two boundaries keep the
serial order — emit and retire, admit, grow or ``kv_oom``, upload,
launch — because they turn on pages and slots that N's leavers are
about to give back: a block table that could not grow under N, and a
queued request with no chunk to run while a rider of N leaves.  A
speculative window's draft needs the last tokens on the host, so with
``GenerationConfig.speculative`` every launch is read at once: the same
round with nothing ever in flight.

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
the span's own clock.  Where a round lands a window, the stretch in
which the chip has nothing is a ``serving.boundary`` span (`_boundary`:
the read, ``serving.boundary.check``, the first launch) with the
``generation.boundary_*`` counters.  ``generation.restaged`` counts the
boundaries at which an unforeseen end changed the staged window, and
``generation.overrun_slot_steps`` the slot-steps run for a stream that
had ended on its first token.
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


def _alive(r):
    """A slot-holding request that has no terminal reply yet (`_retire`
    takes the slot away with the reply)."""
    return r.slot is not None


def _landed(toks):
    """Whether a flown window's tokens had landed before anyone read
    them (`decode._Pending.landed`: one `is_ready()`, no wait).  A
    stand-in that cannot say (a test's wrapper) says no."""
    landed = getattr(toks, 'landed', None)
    return landed is not None and bool(landed())


class _Plan(object):
    """What one boundary launches: at most one prefill chunk (``chunk``
    the request, ``tokens`` its slice, ``ring`` a one-shot ring prefill)
    and one window over ``dec`` with its per-slot vectors.  ``short``:
    a block table could not grow while another window was running."""
    __slots__ = ('chunk', 'tokens', 'ring', 'short', 'dec', 'active',
                 'seeds', 'temps', 'topks')

    def __init__(self, slots):
        self.chunk = self.tokens = None
        self.ring = self.short = False
        self.dec = []
        self.active = np.zeros(slots, bool)
        self.seeds = np.zeros(slots, np.int32)
        self.temps = np.zeros(slots, np.float32)
        self.topks = np.zeros(slots, np.int32)

    @property
    def vectors(self):
        return self.active, self.seeds, self.temps, self.topks

    def join(self, r):
        self.dec.append(r)
        self.active[r.slot] = True
        self.seeds[r.slot] = r.params.seed
        self.temps[r.slot] = r.params.temperature
        self.topks[r.slot] = r.params.top_k

    def keep(self, alive):
        """Drop the streams ``alive`` refuses; True when one went.  Only
        ``active`` changes (a NEW array: the old one may be staged)."""
        dec = [r for r in self.dec if alive(r)]
        if len(dec) == len(self.dec):
            return False
        self.dec = dec
        self.active = np.zeros_like(self.active)
        for r in dec:
            self.active[r.slot] = True
        return True


class _Flight(object):
    """A launched window: the streams riding it and its tokens (on the
    device until read; a speculative window's, {slot: accepted}), or
    the ``error`` its launch raised."""
    __slots__ = ('dec', 'toks', 'error')

    def __init__(self, dec, toks, error=None):
        self.dec, self.toks, self.error = dec, toks, error


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
        self._flight = None      # the window the chip is running, if any
        # a speculative window's draft needs the last tokens on the host
        self._speculative = (self._gen.speculative
                             and self._gen.decode_window > 1)

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
            alive = self._work()
        if _obs.enabled():
            _obs.metrics.counter('generation.round_s').inc(work.seconds)
        return alive

    def _work(self):
        """One round with work (the order is the module docstring's);
        False when the engine is stopping."""
        flight, self._flight = self._flight, None
        # under the running window: all that the next launches need
        if not self._admit_and_sweep():
            return False
        plan = self._plan(flight)
        toks = None
        if flight is not None:
            if not plan.short:
                self._stage(plan)
            flight, toks, plan, first, flown = self._boundary(flight, plan)
        elif self._speculative:
            # the draft starts from the last tokens, on the host: the
            # chunk is read before its window goes, and the window (read
            # inside its launch) lands in the round that launched it
            first = self._launch_chunk(plan)
            self._finish_chunk(plan, first, None)
            plan.keep(_alive)
            flight = flown = self._launch_window(plan)
            toks = flown.toks if flown is not None else None
        else:
            first = self._launch_chunk(plan)
            flown = self._launch_window(plan)
        # under the launched window: what the landed one and the chunk
        # gave; a launch that failed is replied to after them, as in the
        # serial order (partial output stays readable)
        if flight is not None:
            self._emit_window(flight, toks)
        if not self._speculative:
            self._finish_chunk(plan, first, flown)
        if flown is not None and flown.error is not None:
            self._fail_window(flown.error, flown.dec)
        elif flown is not None:
            if first is not None:
                _obs.metrics.counter('generation.mixed_dispatches').inc()
            if not self._speculative:
                self._flight = flown
        return True

    def _boundary(self, flight, plan):
        """Read the landed window and launch what goes behind it: from
        the read's return to the first dispatch the chip has nothing.
        `serving.boundary` holds the read (`decode.window.fetch`), the
        check between the read and the first launch call
        (`serving.boundary.check`) and that launch, the chunk's when
        there is one, else the window's; the ``generation.boundary_*``
        counters stop where the runtime says that launch's dispatch span
        ended (`DecodeRuntime.dispatched_at`), so the counting behind a
        dispatch, under the launched work, is outside.  Returns (the
        landed flight or None when it is already emitted, its tokens,
        the plan as launched, the chunk's sample, the window flown)."""
        rt = self.runtime
        with _obs.span('serving.boundary', cat='serving') as edge:
            # had the host's work under the window outlasted it, the chip
            # went dry BEFORE this boundary began
            late = _obs.enabled() and _landed(flight.toks)
            toks = self._land(flight)
            with _obs.span('serving.boundary.check', cat='serving') as check:
                ended = self._eos_rows(flight, toks)
                self._sweep_active()
                if plan.keep(lambda r: _alive(r) and id(r) not in ended):
                    _obs.metrics.counter('generation.restaged').inc()
                if plan.chunk is not None and not _alive(plan.chunk):
                    plan.chunk = None
                waiting = plan.chunk is None and bool(self._queue)
                serial = plan.short or (waiting
                                        and self._leavers(flight, plan))
                if serial:
                    # a table that could not grow, or a queued request
                    # and nothing to run for it, while streams of the
                    # landed window are about to give slots and pages
                    # back: this boundary keeps the serial order (emit
                    # and retire, admit, grow or `kv_oom`, upload, launch)
                    self._emit_window(flight, toks)
                    flight = None
                    self._admit_and_sweep()
                    plan = self._plan(None)
                elif waiting:
                    # it arrived after the staging: its first chunk goes
                    # directly behind the landed window all the same
                    self._admit_and_sweep()
                    if self._pick_chunk(plan):
                        plan.join(plan.chunk)
            rt.dispatched_at = None
            first = self._launch_chunk(plan)
            dry_to = rt.dispatched_at
            window_first = dry_to is None       # no chunk went
            if window_first:
                flown = self._launch_window(plan)
                dry_to = rt.dispatched_at
            if _obs.enabled():
                edge.args.update(
                    serial=bool(serial), late=late,
                    first='none' if dry_to is None
                    else 'window' if window_first else 'chunk')
        if not window_first:
            flown = self._launch_window(plan)
        if dry_to is not None:
            counter = _obs.metrics.counter
            counter('generation.boundaries').inc()
            counter('generation.boundary_dry_s').inc(dry_to - check.t0)
            counter('generation.boundary_check_s').inc(check.seconds)
            if late:
                counter('generation.boundary_late').inc()
            if serial:
                counter('generation.boundary_serial').inc()
        return flight, toks, plan, first, flown

    def _admit_and_sweep(self):
        with _obs.span('serving.admit', cat='serving'):
            if not self._admit_round():
                return False
            self._sweep_active()
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

    # ------------------------------------------------------- planning
    def _pick_chunk(self, plan):
        """Put the OLDEST still-prefilling request's next chunk (or its
        one ring shot) into ``plan``: bounded work per round, so long
        prompts cannot starve the decode batch.  True when that chunk
        completes a prompt whose stream then rides the window behind
        it: its first token is fed on the device, so one that is EOS is
        found a window late (``generation.overrun_slot_steps``)."""
        rt = self.runtime
        pre = [r for r in self._active if r.offset < r.prompt.size]
        if not pre:
            return False
        r = plan.chunk = min(pre, key=lambda x: x.t_submit)
        plan.ring = (rt.mesh is not None and r.offset == 0
                     and r.prompt.size >= rt.ring_min_len)
        plan.tokens = (r.prompt if plan.ring else
                       r.prompt[r.offset:r.offset + rt.prefill_chunk])
        return (r.offset + plan.tokens.size >= r.prompt.size
                and r.max_new > 1)

    def _plan(self, flight):
        """What the next boundary launches, from what the host knows
        while ``flight`` (a window, or None) still runs: one chunk, and
        one window over every stream that will be decoding then — the
        lengths are known (`host_len` moved at the launch), and a rider
        of ``flight`` whose ``produced + K`` reaches ``max_new`` is
        known to leave.  Every block table is grown to cover that
        window FIRST.  With nothing in flight a stream the pool cannot
        grow gets a terminal kv_oom reply (it is never truncated and
        never silently stalled) and its freed pages may rescue the
        streams after it; under a running window the verdict waits for
        the boundary (``plan.short``), where the leavers' pages are
        back."""
        rt, K = self.runtime, self._gen.decode_window
        plan = _Plan(rt.slots)
        joins = self._pick_chunk(plan)
        riding = set(map(id, flight.dec)) if flight is not None else ()
        for r in self._active:
            if joins if r is plan.chunk else (
                    r.offset >= r.prompt.size
                    and r.produced + (K if id(r) in riding else 0)
                    < r.max_new):
                plan.join(r)
        for r in list(plan.dec):
            start = (r.prompt.size if r is plan.chunk
                     else int(rt.host_len[r.slot]))
            if rt.ensure_capacity(r.slot, start + K):
                continue
            if flight is not None:
                plan.short = True
                break
            _obs.metrics.counter('generation.kv_oom').inc()
            snap = rt.pool_snapshot()
            _flight.record('serving.kv_oom', slot=int(r.slot),
                           produced=int(r.produced), **snap)
            self._retire(
                r, ERROR, reason='kv_oom',
                error='KV page pool exhausted mid-stream (%d/%d pages '
                      'live); partial output is in tokens_so_far()'
                      % (snap['pages_in_use'], snap['pages_capacity']))
            _flight.maybe_dump('kv_oom', extra={'kv_pool': snap})
        plan.keep(_alive)
        return plan

    def _stage(self, plan):
        """Upload the next launches' arguments while the chip is busy;
        the launches find them by value (`DecodeRuntime.stage_*`)."""
        rt, r = self.runtime, plan.chunk
        try:
            if r is not None and not plan.ring:
                rt.stage_prefill(r.slot, plan.tokens, r.offset, r.params)
            if plan.dec:
                rt.stage_window(*plan.vectors)
        except Exception:  # noqa: BLE001 - the launch raises it again,
            pass           # and there it is replied to

    # ------------------------------------------------------- launching
    def _launch_chunk(self, plan):
        """Launch ``plan.chunk``; returns its sample, still on the
        device, or None (no chunk, or a fault: the request has its
        ERROR reply)."""
        r, rt = plan.chunk, self.runtime
        if r is None:
            return None
        slot = r.slot
        with _obs.span('serving.prefill', cat='serving') as sp:
            try:
                if plan.ring:
                    first, _logits = rt.prefill_ring(slot, r.prompt,
                                                     r.params)
                else:
                    first, _logits = rt.prefill(slot, plan.tokens,
                                                r.offset, r.params)
            except BaseException as e:  # noqa: BLE001 - replied per request
                self._fail_chunk(r, e)
                plan.chunk = first = None
                plan.keep(_alive)
            else:
                r.offset += int(plan.tokens.size)
            # the span's args, behind the dispatch: the chip has its work
            if _obs.enabled():
                sp.args.update(slot=int(slot), ring=bool(plan.ring))
                if r.trace is not None:
                    sp.args.update(trace_id=r.trace.trace_id,
                                   parent_span_id=r.trace.span_id)
                if first is not None:
                    sp.args['offset'] = int(r.offset)
        if first is None:
            return None
        r.chunks += 1
        _obs.metrics.counter('generation.prefill_chunks').inc()
        return first

    def _fail_chunk(self, r, e):
        self.breaker.record_failure()
        _obs.metrics.counter('serving.batch_failures').inc()
        _flight.record('serving.prefill_failure', error=repr(e)[:300])
        self._retire(r, ERROR, error=e, reason='prefill')
        _flight.maybe_dump('serving_prefill_failure')

    def _finish_chunk(self, plan, first, flown):
        """Read the launched chunk's sample if it completed the prompt
        (the wait is for the chunk alone, whatever is queued behind it):
        publish the prompt's full pages for later prefix-sharing
        requests, then emit the first token (TTFT).  ``flown`` is the
        window launched behind the chunk, if any."""
        r = plan.chunk
        if first is None or r.offset < r.prompt.size:
            return
        try:
            tok = int(first)
        except BaseException as e:  # noqa: BLE001 - replied per request
            self._fail_chunk(r, e)
        else:
            self.runtime.promote_prefix(r.slot, r.prompt)
            with _obs.span('serving.emit', cat='serving'):
                self._emit_tokens(r, [tok])
        if not _alive(r) and flown is not None and flown.error is None \
                and r in flown.dec:
            # it ended on its first token with the window already gone
            _obs.metrics.counter('generation.overrun_slot_steps').inc(
                self._gen.decode_window)

    def _launch_window(self, plan):
        """Launch one fused K-token window (plain decode or speculative
        verify) over ``plan.dec``; returns it as a `_Flight` (holding
        the fault, had the launch one), or None: nobody decodes."""
        if not plan.dec:
            return None
        rt, K = self.runtime, self._gen.decode_window
        toks = error = None
        with _obs.span('serving.decode_step', cat='serving') as sp:
            try:
                if _faults.any_active():
                    _faults.maybe_fail('decode_step')
                if self._speculative:
                    toks = self._verify_step(plan, K)
                else:
                    toks = rt.decode_window(K, *plan.vectors)
            except BaseException as e:  # noqa: BLE001 - replied per request
                error = e
            # the span's args, behind the dispatch: the chip has its work
            if _obs.enabled():
                sp.args.update(
                    steps=int(K), requests=len(plan.dec),
                    speculative=self._speculative,
                    links=[r.trace.trace_id for r in plan.dec
                           if r.trace is not None])
        if error is not None:
            return _Flight(plan.dec, None, error)
        _obs.metrics.counter('generation.decode_windows').inc()
        return _Flight(plan.dec, toks)

    def _fail_window(self, e, dec):
        self.breaker.record_failure()
        _obs.metrics.counter('serving.batch_failures').inc()
        _flight.record('serving.decode_failure', error=repr(e)[:300],
                       requests=len(dec),
                       steps=int(self._gen.decode_window))
        for r in dec:
            if _alive(r):
                self._retire(r, ERROR, error=e, reason='decode_step')
        _flight.maybe_dump('serving_decode_failure')

    def _verify_step(self, plan, K):
        """One speculative window: build each stream's fed row (last
        emitted token + n-gram draft), run the fused verify, keep the
        longest accepted prefix per stream, and roll the runtime back
        to the committed lengths.  Returns {slot: tokens}."""
        from .sampling import draft_ngram
        rt = self.runtime
        S = rt.slots
        fed = np.zeros((S, K), np.int32)
        for r in plan.dec:
            fed[r.slot, 0] = rt.host_tok[r.slot]
            ctx = np.concatenate([
                r.prompt, np.asarray(r.future.tokens_so_far(), np.int32)])
            fed[r.slot, 1:] = draft_ngram(ctx, K - 1)
        g = rt.verify_window(K, fed, *plan.vectors)
        emitted, accepted, kept = {}, {}, 0
        for r in plan.dec:
            row = g[r.slot]
            m = 1
            while m < K and fed[r.slot, m] == row[m - 1]:
                m += 1
            accepted[r.slot] = (m, int(row[m - 1]))
            emitted[r.slot] = row[:m]
            kept += m - 1
        _obs.metrics.counter('generation.spec_proposed').inc(
            (K - 1) * len(plan.dec))
        _obs.metrics.counter('generation.spec_accepted').inc(kept)
        # commit BEFORE emitting: finishing streams retire (and free
        # their pages) with the runtime already consistent
        rt.commit_speculation(accepted)
        return emitted

    # -------------------------------------------------------- landing
    def _land(self, flight):
        """The landed window's [slots, K] tokens: the one wait for the
        chip in a round.  None when nobody is left to read them for, or
        on a fault (every rider has its ERROR reply)."""
        if not any(map(_alive, flight.dec)):
            return None
        try:
            return np.asarray(flight.toks)
        except BaseException as e:  # noqa: BLE001 - replied per request
            self._fail_window(e, flight.dec)
            return None

    def _eos_rows(self, flight, toks):
        """ids of the riders whose landed window holds EOS: the one end
        the host could not foresee from the counts."""
        eos = self._gen.eos_id
        riders = list(filter(_alive, flight.dec))
        if eos is None or toks is None or not riders:
            return ()
        hit = (toks[[r.slot for r in riders]] == eos).any(axis=1)
        return {id(r) for r, h in zip(riders, hit) if h}

    @staticmethod
    def _leavers(flight, plan):
        """Whether a rider of the landed window is about to retire."""
        staying = set(map(id, plan.dec))
        return any(_alive(r) and id(r) not in staying
                   for r in flight.dec)

    def _emit_window(self, flight, toks):
        """Stream a landed window's tokens to its riders; one that was
        retired while the window ran (a cancel, a deadline) gets none."""
        if toks is None:
            return
        self.breaker.record_success(cold=False)
        with _obs.span('serving.emit', cat='serving'):
            for r in flight.dec:
                if _alive(r):
                    r.windows += 1
                    self._emit_tokens(r, [int(t) for t in toks[r.slot]])

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
