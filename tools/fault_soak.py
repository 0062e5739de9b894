#!/usr/bin/env python
"""Fault-injection soak: a short training run that SURVIVES the armed
PT_FAULT matrix and proves it with counters.

Drives the full resilience stack end-to-end — async Checkpointer (+
signal flush), RecoveryPolicy rollback, FeedPrefetcher, run_steps fused
launches, the executor's fused check_nan verdict — under whatever faults
the caller armed via PT_FAULT (see paddle_tpu/testing/faults.py for the
site table).  Used by tools/ci_smoke.sh:

  phase 1: in-process faults (nan_step, ckpt_write, cache_read,
           cache_write, prefetch_stall) — must COMPLETE, with
           recovery.rollbacks > 0, faults.injected > 0, all losses
           finite, zero post-recovery retraces, zero pipeline stalls
           (--assert-recovery);
  phase 2: PT_FAULT=sigterm:at=K kills the process mid-run (the signal
           handler flushes a final checkpoint); a second invocation with
           --expect-resume must restore it and finish the run;
  phase 3: PT_ASYNC=1 PT_NAN_POLL=N re-runs phase 1 fully async —
           FetchFuture launches, deferred nan verdict — and
           --expect-async requires >=1 verdict poll AND >=1 deferred
           trip with zero steady-state stalls;
  phase 4: PT_FAULT=nan_step:at=N:row=R with --expect-forensics arms a
           single poisoned batch row; the forensic pipeline
           (train/forensics.py) must name the exact (step, op, row),
           quarantine the sample, HEAL the window by replay, and the
           surviving losses must be bitwise-identical to an in-process
           uninjected reference run over the same quarantine.

Prints one JSON line: {"steps_done": ..., "start": ..., "counters": ...}.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _harness  # noqa: E402 - shared stage/watchdog/JSON-tail contract

BATCH = 8


def build_model(fluid):
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 17
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, 16, act='relu')
            h = fluid.layers.dropout(h, 0.2)
            logits = fluid.layers.fc(h, 4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    main_prog.set_amp(True)
    return main_prog, startup, loss


def feed_at(i):
    import numpy as np
    rng = np.random.RandomState(1000 + i)
    return {'x': rng.rand(BATCH, 8).astype('float32'),
            'lbl': rng.randint(0, 4, (BATCH, 1)).astype('int64')}


def reference_losses(fluid, quarantine_state, steps, launch_k):
    """Uninjected in-process reference: same model/seed/feeds/launch
    structure, the forensic run's quarantine pre-seeded — the bitwise
    yardstick the healed run must match on surviving samples."""
    import numpy as np
    from paddle_tpu.data_feeder import SampleQuarantine
    from paddle_tpu.testing import faults
    faults.configure('')     # neutralize the armed PT_FAULT matrix
    q = SampleQuarantine()
    q.restore(quarantine_state)
    main_prog, startup, loss = build_model(fluid)
    exe = fluid.Executor(check_nan=True)
    scope = fluid.Scope()
    losses = {}
    with fluid.scope_guard(scope):
        exe.run(startup)
        step = 0
        while step < steps:
            k = min(launch_k, steps - step)
            per = [feed_at(step + j) for j in range(k)]
            stacked = {n: np.stack([f[n] for f in per]) for n in per[0]}
            stacked, _ = q.apply(stacked, step, k)
            out = exe.run_steps(main_prog, feed_list=stacked, steps=k,
                                fetch_list=[loss])
            for j, v in enumerate(np.asarray(out[0]).ravel()):
                losses[step + j] = float(v)
            step += k
    return losses


def first_consumer_of(program, var_name):
    """The op type the forensic report must name: the first program op
    reading ``var_name`` (its output is the first non-finite value a
    poisoned feed can produce)."""
    for op in program.global_block().ops:
        for names in op.inputs.values():
            seq = names if isinstance(names, (list, tuple)) else [names]
            if var_name in seq:
                return op.type
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=12)
    ap.add_argument('--launch-k', type=int, default=2)
    ap.add_argument('--ckpt', required=True)
    ap.add_argument('--assert-recovery', action='store_true',
                    help='require rollbacks>0, injections>0, zero '
                         'post-recovery retraces, zero pipeline stalls')
    ap.add_argument('--expect-resume', action='store_true',
                    help='require a valid checkpoint to resume from')
    ap.add_argument('--expect-async', action='store_true',
                    help='require the deferred-nan async mode (nan_poll>1) '
                         'with >=1 verdict poll and >=1 deferred trip')
    ap.add_argument('--expect-forensics', action='store_true',
                    help='require the armed nan_step:at=N:row=R fault to '
                         'be bisected to the exact (step, op, row), the '
                         'sample quarantined, the window healed by '
                         'replay, and the surviving losses bitwise-equal '
                         'to an uninjected reference run')
    args = ap.parse_args()

    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs
    from paddle_tpu.data_feeder import FeedPrefetcher, SampleQuarantine
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.testing import faults
    from paddle_tpu.train import (CheckpointConfig, Checkpointer,
                                  LaunchRecord, RecoveryPolicy)

    _flight.install()   # an uncaught crash still leaves a postmortem

    _harness.stage('build')
    main_prog, startup, loss = build_model(fluid)

    exe = fluid.Executor(check_nan=True)
    scope = fluid.Scope()
    quarantine = SampleQuarantine()
    ck = Checkpointer(CheckpointConfig(args.ckpt, step_interval=1,
                                       max_num_checkpoints=3),
                      exe, main_prog, scope=scope, quarantine=quarantine)
    ck.install_signal_handlers()
    meta = ck.restore()
    start = meta['step_id'] + 1 if meta else 0
    if args.expect_resume and (meta is None or start < 1):
        sys.exit('fault_soak: --expect-resume but no valid checkpoint '
                 'found in %s (meta=%r)' % (args.ckpt, meta))

    policy = RecoveryPolicy(ck, max_retries=4)
    K = args.launch_k
    # PT_ASYNC=1 / PT_NAN_POLL>1 puts the soak in the fully-async mode:
    # launches return FetchFuture handles, the fused all-finite verdict
    # accumulates on device, and losses only land on the host after a
    # CLEAN poll — a deferred trip condemns (drops) the whole window
    use_async = exe.nan_poll > 1
    pf = FeedPrefetcher((feed_at(i) for i in range(start, args.steps)),
                        steps=K, to_device=False)
    losses = {}           # step id -> loss (insertion order = land order)
    skipped = 0
    healed = 0            # steps recovered by the quarantine-replay rung
    pending = []          # [(loss_future, k, step0)] awaiting a verdict
    retrace_mark = None   # executor.retraces at the first rollback
    stall_mark = None     # executor.stall_count once steady state begins

    def flush_pending():
        for f, k, s0 in pending:
            for j, v in enumerate(np.asarray(f).ravel()):
                losses[s0 + j] = float(v)
        del pending[:]

    def land(out, k, s0):
        for j, v in enumerate(np.asarray(out).ravel()):
            losses[s0 + j] = float(v)

    def land_replay():
        # rung 1 healed the condemned window: futures fetched before the
        # trip were computed on the poisoned timeline — the replay's
        # (materialized, clean-polled) outputs supersede them
        n = 0
        del pending[:]
        for s0, k, out in policy.last_replay:
            land(out[0], k, s0)
            n += k
        return n

    def saved(step_id):
        if ck.maybe_save(0, step_id):
            policy.note_checkpoint(step_id)

    _harness.stage('train')
    with fluid.scope_guard(scope):
        if meta is None:
            exe.run(startup)
            # restore point BEFORE any step: recovery can roll back even
            # a first-step divergence
            ck.save(0, -1)
            ck.wait()
        step = start
        for stacked, k in pf:
            launch = None
            if args.expect_forensics:
                launch = LaunchRecord(main_prog, stacked, k, [loss], step)
            out = policy.run(lambda: exe.run_steps(
                main_prog, feed_list=stacked, steps=k, fetch_list=[loss],
                as_futures=use_async), launch=launch)
            if stall_mark is None:
                # steady state starts AFTER the first fused launch: the
                # cold-start gap (startup program, initial blocking save,
                # the injected prefetch_stall fault) is not what the
                # async-checkpointing stall budget is about
                stall_mark = int(
                    obs.counters().get('executor.stall_count') or 0)
            if out is None:
                # rolled back: steps pending a verdict were computed on
                # the now-condemned window — drop them with the rollback
                dropped = sum(n for _, n, _ in pending)
                del pending[:]
                skipped += k + dropped
                step += k
                # everything after a rollback must reuse the cached
                # executables: restored numpy params have identical
                # specs, so ANY retrace from here on is a regression
                if retrace_mark is None:
                    retrace_mark = int(
                        obs.counters().get('executor.retraces') or 0)
                continue
            if policy.last_replay is not None:
                healed += land_replay()
                saved(step + k - 1)
                step += k
                continue
            if use_async:
                pending.append((out[0], k, step))
                if exe.nan_clean():
                    # verdict window just polled clean: everything
                    # buffered is good — land it and checkpoint
                    flush_pending()
                    saved(step + k - 1)
            else:
                land(out[0], k, step)
                saved(step + k - 1)
            step += k
        if use_async and pending:
            # end of stream with verdicts still on device: force the poll
            # (through recovery, so a late trip rolls back cleanly)
            def drain():
                exe.poll_nan()
                return []
            tail = policy.run(drain)
            if tail is None:
                skipped += sum(n for _, n, _ in pending)
                del pending[:]
            elif policy.last_replay is not None:
                healed += land_replay()
                saved(step - 1)
            else:
                flush_pending()
                saved(step - 1)
        ck.wait()
    _harness.stage('audit')
    c = obs.counters()
    retraces_after_recovery = 0 if retrace_mark is None else \
        int(c.get('executor.retraces') or 0) - retrace_mark
    steady_stalls = 0 if stall_mark is None else \
        int(c.get('executor.stall_count') or 0) - stall_mark

    loss_values = list(losses.values())
    rec = {
        'start': start,
        'steps_done': len(losses),
        'steps_skipped': skipped,
        'steps_healed': healed,
        'losses_finite': bool(np.all(np.isfinite(loss_values))
                              if loss_values else True),
        # shared schema: observability/export.py SCHEMA['resilience']
        'counters': obs.telemetry_snapshot('resilience',
                                           snapshot=c)['counters'],
        'retraces_after_recovery': retraces_after_recovery,
        'steady_state_stalls': steady_stalls,
    }
    if policy.last_report is not None:
        rec['forensics'] = policy.last_report.to_dict()
        rec['quarantine'] = quarantine.state()
    print(json.dumps(rec))

    if not rec['losses_finite']:
        sys.exit('fault_soak: non-finite loss escaped the recovery policy')
    if args.assert_recovery:
        cc = rec['counters']
        if cc['faults.injected'] < 1:
            sys.exit('fault_soak: no faults injected — PT_FAULT matrix '
                     'not armed?')
        if cc['recovery.rollbacks'] < 1:
            sys.exit('fault_soak: no rollbacks — the nan_step fault did '
                     'not exercise recovery')
        if rec['retraces_after_recovery'] > 0:
            sys.exit('fault_soak: %d retrace(s) after rollback — restored '
                     'state no longer matches the compiled executables'
                     % rec['retraces_after_recovery'])
        if rec['steady_state_stalls'] > 0:
            sys.exit('fault_soak: %d steady-state pipeline stall(s) — '
                     'async checkpointing (or recovery) is blocking the '
                     'step loop' % rec['steady_state_stalls'])
    if args.expect_async:
        cc = rec['counters']
        if exe.nan_poll <= 1:
            sys.exit('fault_soak: --expect-async but nan_poll=%d — set '
                     'PT_ASYNC=1 or PT_NAN_POLL>1' % exe.nan_poll)
        if cc['nan_poll.polls'] < 1:
            sys.exit('fault_soak: --expect-async but the deferred verdict '
                     'was never polled')
        if cc['nan_poll.trips'] < 1:
            sys.exit('fault_soak: --expect-async but no deferred trip — '
                     'the nan_step fault did not exercise the window')
    if args.expect_forensics:
        spec = faults.spec('nan_step')
        if spec is None or spec.at is None or spec.row is None:
            sys.exit('fault_soak: --expect-forensics needs '
                     'PT_FAULT=nan_step:at=N:row=R armed')
        rep = policy.last_report
        if rep is None or not rep.tripped:
            sys.exit('fault_soak: --expect-forensics but no forensic '
                     'verdict (report=%r)' % rep)
        if rep.step != spec.at:
            sys.exit('fault_soak: forensics named step %r, injected at %d'
                     % (rep.step, spec.at))
        if not rep.rows or spec.row not in rep.rows:
            sys.exit('fault_soak: forensics named rows %r, injected row %d'
                     % (rep.rows, spec.row))
        want_op = first_consumer_of(main_prog, 'x')
        if rep.op_type not in (want_op, 'fused:%s' % want_op):
            sys.exit('fault_soak: forensics named op %r, expected %r '
                     '(first consumer of the poisoned feed)'
                     % (rep.op_type, want_op))
        if not rep.source_loc:
            sys.exit('fault_soak: forensic report has no source_loc')
        want_idx = spec.at * BATCH + spec.row
        if want_idx not in quarantine.state():
            sys.exit('fault_soak: sample %d not quarantined (state=%r)'
                     % (want_idx, quarantine.state()))
        if rec['counters']['recovery.escalation.quarantine'] < 1:
            sys.exit('fault_soak: the quarantine rung never healed a '
                     'window (escalation counters=%r)' % rec['counters'])
        ref = reference_losses(fluid, quarantine.state(), args.steps, K)
        common = sorted(set(losses) & set(ref))
        if not any(s > spec.at for s in common):
            sys.exit('fault_soak: no surviving post-injection steps to '
                     'compare (common=%r)' % common)
        mismatch = [s for s in common if losses[s] != ref[s]]
        if mismatch:
            sys.exit('fault_soak: healed run diverges bitwise from the '
                     'uninjected reference at steps %r' % mismatch)
        print(json.dumps({'forensics_parity_steps': common,
                          'forensics_healed_steps': healed}))
    return 0


if __name__ == '__main__':
    _harness.set_tool('FAULT_SOAK')
    _harness.main_guard(main, flight_tag='fault_soak.watchdog')
