#!/usr/bin/env python
"""Pod-scale survival soak: N lockstep trainer processes over one shared
checkpoint dir, killed and respawned mid-run, must converge with bitwise
resume parity and leave zero orphaned state.

Topology: each worker is one "host" of a pod — same model, same seeds,
same feed stream (lockstep replicas, the way data-parallel keeps params
identical on every host).  Workers write SHARDED checkpoints
(``CheckpointConfig(host_count=N)``): each host lands only its row-slice
(``arrays_<h>.npz``) into the serial's ``.parts`` staging dir and the
last one to land finalizes ``MANIFEST.json`` under ``ckpt.lock``.  Every
worker heartbeats through ``parallel/health.py``; a peer going silent
trips ``DeviceLossError`` → ``RecoveryPolicy`` rolls back to the last
good manifest and the worker exits ``RESTART_EXIT_CODE`` (75) so the
supervisor respawns the roster.

The workers always run on the CPU (``JAX_PLATFORMS=cpu`` is set in
their environment): a chip belongs to one process, so N trainer
processes on one host cannot share it, and what this soak checks —
checkpoint sharding, heartbeats, rollback, bitwise resume — is host-side
logic.  Nothing it prints is a device number.

Supervisor scenario (the ci_smoke pod gate):

  ref     1-host uninterrupted run of the same stream → the reference
          loss curve every later segment must prefix-match BITWISE.
  wave 1  N workers; once >= 2 manifests have committed the supervisor
          SIGKILLs the last worker (no goodbye, partial shard left
          behind).  Survivors must detect the stale heartbeat, roll
          back, and exit 75 — not hang.
  wave 2  N workers respawned over the same dir (auto-resume); the last
          worker runs with ``PT_FAULT=device_loss:at=K`` — it stops
          heartbeating mid-run and HANGS (a wedged collective).
          Survivors trip, roll back, exit 75; the supervisor reaps the
          hung process.  The health trip must leave a flight-recorder
          dump (PT_FLIGHT_DIR).
  wave 3  the roster SHRINKS to N-1 workers (``host_count=N-1``):
          elastic restore re-slices the manifest onto the smaller
          roster (``ckpt.reshards`` > 0) and the run completes.

Asserts: every segment's losses == reference[start:start+len] (bitwise
resume parity, across kills, rosters, and reshards); the final loss
improved on the first (convergence); rollbacks > 0 and device-loss
trips > 0; zero processes needed killing beyond the two deliberate
victims (zero hung collectives); zero ``.tmp_ckpt_*`` / ``*.parts``
left in the checkpoint dir; a ``health_trip`` flight dump exists.

Prints one JSON verdict line, fault_soak-style.
"""
import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _harness  # noqa: E402 - shared stage/watchdog/JSON-tail contract


# --------------------------------------------------------------- worker
def worker_main(args):
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.parallel.health import (HealthConfig, HealthMonitor,
                                            DeviceLossError,
                                            RESTART_EXIT_CODE)
    from paddle_tpu.train import (CheckpointConfig, Checkpointer,
                                  RecoveryPolicy)

    _flight.install()   # an uncaught crash still leaves a postmortem

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

    def feed_at(i):
        rng = np.random.RandomState(1000 + i)
        return {'x': rng.rand(8, 8).astype('float32'),
                'lbl': rng.randint(0, 4, (8, 1)).astype('int64')}

    exe = fluid.Executor(check_nan=True)
    scope = fluid.Scope()
    ck = Checkpointer(
        CheckpointConfig(args.ckpt, step_interval=1, max_num_checkpoints=3,
                         host_id=args.host, host_count=args.hosts,
                         sharded=True),
        exe, main_prog, scope=scope)
    hm = HealthMonitor(HealthConfig(args.health, host_id=args.host,
                                    host_count=args.hosts,
                                    timeout_s=args.health_timeout))
    policy = RecoveryPolicy(ck, max_retries=4)

    def report(losses, start, restart):
        c = obs.counters()
        rec = {'host': args.host, 'hosts': args.hosts, 'pid': os.getpid(),
               'start': start, 'losses': losses, 'restart': restart,
               'counters': obs.telemetry_snapshot(
                   'resilience', snapshot=c)['counters']}
        print(json.dumps(rec))
        sys.stdout.flush()

    losses = []
    start = 0
    try:
        with fluid.scope_guard(scope):
            meta = ck.restore()
            start = meta['step_id'] + 1 if meta else 0
            if args.expect_resume and start < 1:
                sys.exit('pod_soak worker %d: --expect-resume but no '
                         'valid checkpoint in %s' % (args.host, args.ckpt))
            if meta is None:
                exe.run(startup)
                # restore point BEFORE any step: recovery can roll back
                # even a first-step loss
                ck.save(0, -1)
                ck.wait()
            # compile BEFORE the first heartbeat: the cold trace+compile
            # takes seconds, and a beat followed by a multi-second pause
            # reads exactly like a lost device to every peer
            exe.prepare(main_prog, feed=feed_at(start), fetch_list=[loss])
            for i in range(start, args.steps):
                if not hm.beat(i):
                    # device_loss injected: a lost device does not exit —
                    # it WEDGES.  The supervisor must reap us; peers must
                    # detect the silence.
                    time.sleep(3600)

                def launch(i=i):
                    hm.check(i)
                    return exe.run(main_prog, feed=feed_at(i),
                                   fetch_list=[loss])
                out = policy.run(launch)
                if out is None:
                    continue   # divergence rollback (not armed here)
                losses.append(float(np.asarray(out[0]).ravel()[0]))
                ck.maybe_save(0, i)
                if args.step_delay:
                    time.sleep(args.step_delay)
            hm.mark_done()
            ck.wait()
    except DeviceLossError:
        # policy already rolled the scope back to the last good manifest;
        # hand control to the supervisor for a restart on whatever
        # roster survives
        report(losses, start, restart=True)
        return RESTART_EXIT_CODE
    report(losses, start, restart=False)
    return 0


# ----------------------------------------------------------- supervisor
class Wave(object):
    def __init__(self, name):
        self.name = name
        self.results = []     # (host, rc, parsed-json-or-None)
        self.reaped = []      # hosts the supervisor had to SIGKILL


def _spawn(args, host, hosts, health_dir, extra_env=None, step_delay=0.0,
           expect_resume=False):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'   # see the module docstring
    env['PT_CACHE'] = '0'
    env.pop('PT_FAULT', None)
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, os.path.abspath(__file__), '--worker',
           '--ckpt', args.ckpt, '--health', health_dir,
           '--host', str(host), '--hosts', str(hosts),
           '--steps', str(args.steps),
           '--step-delay', str(step_delay),
           '--health-timeout', str(args.health_timeout)]
    if expect_resume:
        cmd.append('--expect-resume')
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)


def _finish(proc, timeout):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, None
    rec = None
    for line in reversed((out or '').strip().splitlines()):
        if line.startswith('{'):
            try:
                rec = json.loads(line)
            except ValueError:
                pass
            break
    return proc.returncode, rec


def _manifests(ckpt_dir):
    return len(glob.glob(os.path.join(ckpt_dir, 'checkpoint_*',
                                      '_SUCCESS')))


def _orphans(ckpt_dir):
    return (glob.glob(os.path.join(ckpt_dir, '.tmp_ckpt_*')) +
            glob.glob(os.path.join(ckpt_dir, '*.parts')))


def supervisor_main(args):
    os.makedirs(args.dir, exist_ok=True)
    args.ckpt = os.path.join(args.dir, 'ckpt')
    flight_dir = os.path.join(args.dir, 'flight')
    os.environ['PT_FLIGHT_DIR'] = flight_dir
    fails = []

    def check(cond, msg):
        if not cond:
            fails.append(msg)
            print('pod_soak: FAIL %s' % msg, file=sys.stderr)

    # ---- reference: 1 uninterrupted host, same stream --------------
    _harness.stage('reference')
    ref_args = argparse.Namespace(**vars(args))
    ref_args.ckpt = os.path.join(args.dir, 'ref_ckpt')
    p = _spawn(ref_args, host=0, hosts=1,
               health_dir=os.path.join(args.dir, 'ref_health'))
    rc, ref = _finish(p, args.wave_timeout)
    if rc != 0 or not ref:
        sys.exit('pod_soak: reference run failed (rc=%r)' % (rc,))
    R = ref['losses']
    print('pod_soak: reference %d steps, loss %.4f -> %.4f'
          % (len(R), R[0], R[-1]))
    check(len(R) == args.steps and all(
        isinstance(v, float) and v == v and abs(v) != float('inf')
        for v in R), 'reference run incomplete or non-finite')

    waves = []
    segments = [ref]

    def run_wave(name, hosts, fault_host_env=None, step_delay=None,
                 kill_after_manifests=None, expect_resume=False,
                 wedge_host=None):
        wave = Wave(name)
        waves.append(wave)
        _harness.stage('wave_%s' % name)
        health_dir = os.path.join(args.dir, 'health_%s' % name)
        delay = args.step_delay if step_delay is None else step_delay
        procs = {}
        for h in range(hosts):
            extra = fault_host_env if (fault_host_env and
                                       h == hosts - 1) else None
            procs[h] = _spawn(args, host=h, hosts=hosts,
                              health_dir=health_dir, extra_env=extra,
                              step_delay=delay,
                              expect_resume=expect_resume)
        deadline = time.time() + args.wave_timeout
        if kill_after_manifests is not None:
            while _manifests(args.ckpt) < kill_after_manifests:
                if time.time() > deadline:
                    for pr in procs.values():
                        pr.kill()
                    sys.exit('pod_soak: wave %s never reached %d '
                             'manifests' % (name, kill_after_manifests))
                time.sleep(0.05)
            victim = hosts - 1
            procs[victim].send_signal(signal.SIGKILL)
            print('pod_soak: wave %s SIGKILLed host %d at %d manifests'
                  % (name, victim, _manifests(args.ckpt)))
        pending = dict(procs)
        wedge_grace = None
        while pending:
            now = time.time()
            for h in list(pending):
                if pending[h].poll() is None:
                    continue
                rc, rec = _finish(pending.pop(h), 10.0)
                wave.results.append((h, rc, rec))
                if rec:
                    segments.append(rec)
            if not pending:
                break
            if set(pending) == {wedge_host} and wedge_grace is None:
                # every peer has exited: the deliberately-wedged
                # device_loss worker is the only process allowed to
                # need reaping — give it one last detection window
                wedge_grace = now + max(2.0, 4 * args.health_timeout)
            if now > deadline or (wedge_grace is not None and
                                  now > wedge_grace):
                # anything ELSE still running here is a hung collective —
                # the exact failure this layer exists to prevent
                for h, pr in pending.items():
                    pr.kill()
                    pr.communicate()
                    wave.reaped.append(h)
                    print('pod_soak: wave %s reaped hung host %d'
                          % (name, h))
                pending.clear()
            time.sleep(0.05)
        return wave

    # wave 1: hard SIGKILL mid-run; survivors must trip + roll back
    w1 = run_wave('gen0', hosts=args.workers, kill_after_manifests=2)
    survivors = [(h, rc, rec) for h, rc, rec in w1.results
                 if rc not in (None, -9)]
    check(len(survivors) == args.workers - 1,
          'wave gen0: expected %d survivors, got %d'
          % (args.workers - 1, len(survivors)))
    for h, rc, rec in survivors:
        check(rc == 75, 'wave gen0 host %d: expected exit 75 (rollback + '
              'restart request), got %r' % (h, rc))
    check(not w1.reaped, 'wave gen0: hung worker(s) %r' % w1.reaped)

    # wave 2: injected device loss — the victim WEDGES instead of dying
    loss_at = max(2, args.device_loss_at)
    w2 = run_wave('gen1', hosts=args.workers,
                  fault_host_env={'PT_FAULT': 'device_loss:at=%d' % loss_at},
                  expect_resume=True, wedge_host=args.workers - 1)
    survivors2 = [(h, rc, rec) for h, rc, rec in w2.results]
    check(w2.reaped == [args.workers - 1],
          'wave gen1: expected exactly the wedged host %d reaped, got %r'
          % (args.workers - 1, w2.reaped))
    check(len(survivors2) == args.workers - 1,
          'wave gen1: expected %d survivors, got %d'
          % (args.workers - 1, len(survivors2)))
    for h, rc, rec in survivors2:
        check(rc == 75, 'wave gen1 host %d: expected exit 75, got %r'
              % (h, rc))
        if rec:
            check(rec['counters'].get('health.lost_hosts', 0) >= 1,
                  'wave gen1 host %d: no health.lost_hosts trip' % h)
            check(rec['counters'].get('recovery.device_loss', 0) >= 1,
                  'wave gen1 host %d: no recovery.device_loss rollback' % h)

    # wave 3: the roster SHRINKS — elastic restore onto fewer hosts
    w3 = run_wave('gen2', hosts=args.workers - 1, step_delay=0.0,
                  expect_resume=True)
    check(not w3.reaped, 'wave gen2: hung worker(s) %r' % w3.reaped)
    check(len(w3.results) == args.workers - 1 and
          all(rc == 0 for _, rc, _ in w3.results),
          'wave gen2: shrunken roster did not complete cleanly: %r'
          % [(h, rc) for h, rc, _ in w3.results])
    for h, rc, rec in w3.results:
        if not rec:
            continue
        if args.expect_resume:
            check(rec['start'] > 0,
                  'wave gen2 host %d: did not resume (start=0)' % h)
        if args.expect_reshard:
            check(rec['counters'].get('ckpt.reshards', 0) >= 1,
                  'wave gen2 host %d: no ckpt.reshards — restore did not '
                  'cross the roster change' % h)

    # ---- cross-cutting asserts -------------------------------------
    _harness.stage('audit')
    # bitwise resume parity: EVERY segment (all waves, all hosts) must
    # prefix-match the uninterrupted reference from its start step
    for seg in segments[1:]:
        s, got = seg['start'], seg['losses']
        want = R[s:s + len(got)]
        check(got == want,
              'host %d (hosts=%d, start=%d): losses diverge from the '
              'reference stream' % (seg['host'], seg['hosts'], s))
    rollbacks = sum(seg['counters'].get('recovery.rollbacks', 0)
                    for seg in segments[1:])
    check(rollbacks > 0, 'no rollbacks anywhere — the kills never '
          'exercised recovery')
    finals = [seg for seg in segments[1:] if not seg.get('restart')]
    check(all(seg['start'] + len(seg['losses']) == args.steps
              for seg in finals) and finals,
          'final segment(s) did not complete the run: %r'
          % [(seg['host'], seg['start'], len(seg['losses']))
             for seg in finals])
    orphans = _orphans(args.ckpt)
    check(not orphans, 'orphaned checkpoint state left behind: %r'
          % orphans)
    dumps = glob.glob(os.path.join(flight_dir, '*health_trip*.json'))
    check(len(dumps) >= 1, 'no health_trip flight dump in %s' % flight_dir)

    verdict = {
        'ok': not fails,
        'reference_steps': len(R),
        'segments': len(segments) - 1,
        'rollbacks': rollbacks,
        'manifests': _manifests(args.ckpt),
        'reaped': {w.name: w.reaped for w in waves},
        'health_trip_dumps': len(dumps),
        'failures': fails,
    }
    print(json.dumps(verdict))
    return 0 if not fails else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--worker', action='store_true')
    ap.add_argument('--workers', type=int, default=2,
                    help='pod size (supervisor mode)')
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--dir', default=None,
                    help='supervisor workdir (ckpt + health + flight)')
    ap.add_argument('--ckpt', default=None)
    ap.add_argument('--health', default=None)
    ap.add_argument('--host', type=int, default=0)
    ap.add_argument('--hosts', type=int, default=1)
    ap.add_argument('--step-delay', type=float, default=0.15,
                    help='per-step sleep so staleness detection lands '
                         'mid-run, not post-run')
    ap.add_argument('--health-timeout', type=float, default=1.5)
    ap.add_argument('--device-loss-at', type=int, default=None,
                    help='step the wave-2 victim stops heartbeating at '
                         '(default steps//2)')
    ap.add_argument('--wave-timeout', type=float, default=240.0)
    ap.add_argument('--expect-resume', action='store_true')
    ap.add_argument('--expect-reshard', action='store_true')
    args = ap.parse_args()
    if args.device_loss_at is None:
        args.device_loss_at = args.steps // 2
    if args.worker:
        if not (args.ckpt and args.health):
            sys.exit('pod_soak --worker needs --ckpt and --health')
        return worker_main(args)
    if args.workers < 2:
        sys.exit('pod_soak needs --workers >= 2 (the scenario kills one)')
    if args.dir is None:
        import tempfile
        args.dir = tempfile.mkdtemp(prefix='pt_pod_soak.')
    return supervisor_main(args)


if __name__ == '__main__':
    _harness.set_tool('POD_SOAK')
    _harness.main_guard(main, flight_tag='pod_soak.watchdog')
