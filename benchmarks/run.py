#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, in this process, on this machine.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights on the device from --seed, the cell's shapes warmed,
executables from the compile cache), the measured window, the comparison
with the configuration's plain reference, then ONE JSON result as the
last line of standard output.  With --trace 0 the metrics are the cell's
end-to-end metrics, with --trace 1 its per-layer metrics.

Nothing here names a cell, a configuration, a traffic mix or a metric:
the cell is an entry of BENCHMARK.json; its configuration is
configs/<config>.json, which names a runner (runners/<runner>.py) and has
a plain reference (references/<config>.py); its traffic is
traffic/<traffic>.json; each metric is read by metrics/<metric>.py.

    python3 benchmarks/run.py --workload <cell> --sweep 1.5,2,2.5 --seconds 40

runs the knee sweep of a serving cell (one window per rate, one process,
no result line): how the rate in a traffic file was found.  It is never
part of a cell's run.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# PT_AUTOTUNE: the kernel tier's block-size search draws another pick in
# every checkout (PERF.md, Findings of PR 23), so the training
# configurations pin it off; the pin goes when the pick repeats.
PROGRAM_SWITCHES = ('PT_AUTOTUNE',)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    path = os.path.join(HERE, kind, name + '.py')
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        'bench_%s_%s' % (kind, name.replace('.', '_').replace('-', '_')),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(what, **fields):
    """A line before the result: what was measured or compared."""
    print('%s: %s' % (what, json.dumps(fields, sort_keys=True, default=str)),
          flush=True)


def wanted_metrics(manifest, cell_name, trace):
    """The metric entries this run reports: the cell's end-to-end metrics
    without a trace, its per-layer metrics with one.  Which those are is
    BENCHMARK.json's own rule, so that a cell a later PR appends is judged
    with no entry edited but its end-to-end metric's list: an entry that
    lists `workloads` belongs to the cells it lists (arithmetic tied to
    one architecture or to a mesh); an end-to-end entry without the key
    belongs to every cell; a per-layer entry without it to every cell
    that reports the end-to-end metric it `moves`."""
    end_to_end = [m for m in manifest['end_to_end']
                  if cell_name in m.get('workloads', (cell_name,))]
    if not trace:
        return end_to_end
    reported = {m['name'] for m in end_to_end}
    return [m for m in manifest['per_layer']
            if (cell_name in m['workloads'] if 'workloads' in m
                else m['moves'] in reported)]


def prepare_environment():
    """The compile cache lives at a fixed path inside the checkout unless
    the caller placed it; small programs are cached too, so that a second
    run of a cell compiles nothing."""
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(ROOT, '.jax_cache'))
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS', '0')
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES', '-1')
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)


def device_block(device, ctx):
    """The device as JAX reports it, and the peak on the fullest chip as
    the runner sampled it through the window (lib/memory.py)."""
    peak = ctx.get('memory_peak_bytes')
    if peak is None:                 # a runner that took no samples
        import jax
        from lib import memory
        sampler = memory.PeakSampler(jax.devices()[:int(ctx['chips'])])
        sampler.sample()
        peak = sampler.result()
    out = dict(device, memory_peak_bytes=int(peak))
    if ctx.get('trace'):
        out['busy_s'] = ctx['trace']['busy_s']
        out['window_s'] = ctx['trace']['window_s']
    return out


def load_cell(root, workload):
    """(manifest, cell, config, traffic, runner) of one cell, the
    environment prepared and the configuration's switches set."""
    prepare_environment()
    manifest = load_json(root, 'BENCHMARK.json')
    cells = {c['name']: c for c in manifest['workloads']}
    if workload not in cells:
        raise SystemExit('no cell %r in BENCHMARK.json (have: %s)'
                         % (workload, ', '.join(sorted(cells))))
    cell = cells[workload]
    config = load_json(HERE, 'configs', cell['config'] + '.json')
    config.setdefault('name', cell['config'])
    traffic = load_json(HERE, 'traffic', cell['traffic'] + '.json')
    # switches of the program that a configuration may fix, with the
    # reason in its file; set before the program is imported.  The list
    # is closed: a later PR cannot edit this file, so a configuration
    # cannot switch off whatever else is inconvenient.
    for key, value in config.get('env', {}).items():
        if key not in PROGRAM_SWITCHES:
            raise SystemExit('configuration %r sets %s: only %s may be set'
                             % (cell['config'], key,
                                ', '.join(PROGRAM_SWITCHES)))
        os.environ[key] = str(value)
    return manifest, cell, config, traffic, load_module('runners',
                                                        config['runner'])


def run_cell(workload, seed, seconds, trace, allow_cpu=False, root=ROOT):
    """Run one cell; returns the result object (what the last line holds).
    `allow_cpu` is for the benchmark's own tests at tiny sizes."""
    manifest, cell, config, traffic, runner = load_cell(root, workload)
    from lib import peaks
    device = peaks.require_device(cell['chips'], allow_cpu=allow_cpu)
    say('device', **device)
    ctx = runner.run(cell, config, traffic, seed, seconds, bool(trace),
                     T_START, device, say)
    ctx['peaks'] = None if allow_cpu and device['platform'] != 'tpu' \
        else peaks.peaks(device['kind'])
    ctx['cell'] = cell

    metrics = {}
    for m in wanted_metrics(manifest, workload, trace):
        reader = load_module('metrics', m['name'])
        if reader is None:
            raise SystemExit('metric %r has no reader benchmarks/metrics/'
                             '%s.py' % (m['name'], m['name']))
        value = reader.read(ctx)
        if value is not None:
            metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
    result = {'correct': bool(ctx['correct']),
              'attempted': int(ctx['attempted']),
              'failed': int(ctx['failed']),
              'metrics': metrics,
              'device': device_block(device, ctx)}
    if trace and ctx.get('trace'):
        result['breakdown'] = {
            'device_ops': ctx['trace']['device_ops'][:10],
            'idle_gaps': ctx['trace']['idle_gaps'][:10]}
    return result, ctx


def sweep(workload, rates, seed, seconds):
    """The knee sweep: one window per rate, in this one process (one
    set-up).  Prints, per rate, what decides the capacity and the knee:
    failures, the number waiting for a first token at the window's middle
    and end, completed tokens per second and the TTFT percentiles."""
    _, cell, config, traffic, runner = load_cell(ROOT, workload)
    from lib import peaks
    device = peaks.require_device(cell['chips'])
    runner.sweep(cell, config, traffic, seed, seconds, rates, device, say)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--sweep', default=None,
                    help='comma-separated request rates: the knee sweep')
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(load_json(ROOT, 'BENCHMARK.json')['run_seconds'])
    if args.sweep:
        sweep(args.workload, [float(r) for r in args.sweep.split(',')],
              args.seed, args.seconds)
        return 0
    from lib.peaks import NoChip
    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             args.trace)
    except NoChip as e:
        print('benchmarks/run.py: %s; nothing was run' % e, file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
