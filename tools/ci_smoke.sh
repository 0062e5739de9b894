#!/usr/bin/env bash
# CI smoke: lint, rewriter and soak gates on the CPU, then the
# tier-1 tests and the shared telemetry schema.  No gate here measures
# speed: numbers come from benchmarks/run.py on the chip.
set -u
cd "$(dirname "$0")/.."

echo "== ci_smoke: pt-lint over bundled models =="
# static-analysis gate (docs/analysis.md): every bundled model program
# must lint clean of error-severity findings (shape/dtype coverage of
# every op type included — an unknown op is a warning, a shape error is
# an error, and either class regressing shows up here)
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/pt_lint.py \
    --all-builtin --fail-on error
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    echo "ci_smoke: pt-lint FAILED (rc=$lint_rc)"
fi

echo "== ci_smoke: pt-lint over bundled models (post-optimization) =="
# the PT_OPT rewriter gate, part 1 (docs/passes.md): every zoo program
# must ALSO lint error-free after the optimizing pipeline rewrote it —
# a pass emitting broken fused/folded ops shows up here
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/pt_lint.py \
    --all-builtin --optimize --fail-on error
opt_lint_rc=$?
if [ "$opt_lint_rc" -ne 0 ]; then
    echo "ci_smoke: pt-lint --optimize FAILED (rc=$opt_lint_rc)"
fi

echo "== ci_smoke: opt pipeline op-count + bitwise parity =="
# the PT_OPT rewriter gate, part 2: the bench transformer program must
# shrink through the pipeline, and PT_OPT=1 training must be bitwise
# equal to PT_OPT=0 (losses AND end-of-run param/Adam state).
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 python - <<'EOF'
import os
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core import passes
from paddle_tpu.models import transformer as tr

def build(B=2, T=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=256, trg_vocab=256, max_len=T,
                           n_layer=2, n_head=2, d_model=32, d_inner=64,
                           dropout=0.1, use_flash=False)
    return main, startup, out

main, _, out = build()
opt, stats = passes.optimize_program(main, (out['loss'].name,))
raw, cut = stats['op_count_raw'], stats['op_count_opt']
if not cut < raw:
    sys.exit('ci_smoke: opt pipeline did not shrink the program '
             '(raw=%d opt=%d)' % (raw, cut))
print('ci_smoke: opt op-count %d -> %d (-%.0f%%, %d fused, %d removed)'
      % (raw, cut, 100.0 * (raw - cut) / raw, stats['ops_fused'],
         stats['ops_removed']))

def train(pt_opt):
    os.environ['PT_OPT'] = pt_opt
    main, startup, out = build()
    main.set_amp(True)
    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(0)
    feed = tr.synthetic_batch(rng, 2, 16, 256)
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [np.asarray(exe.run(main, feed=feed,
                                     fetch_list=[out['loss']])[0])
                  for _ in range(2)]
    return losses, {n: np.asarray(v) for n, v in scope.vars.items()}

l1, s1 = train('1')
l0, s0 = train('0')
for a, b in zip(l1, l0):
    if not np.array_equal(a, b):
        sys.exit('ci_smoke: PT_OPT=1 losses diverge from PT_OPT=0: '
                 '%r vs %r' % (a, b))
bad = [n for n in s1 if not np.array_equal(s1[n], s0.get(n))]
if set(s1) != set(s0) or bad:
    sys.exit('ci_smoke: PT_OPT=1 end-of-run state diverges: %s'
             % bad[:5])
print('ci_smoke: PT_OPT=1 bitwise-equal to PT_OPT=0 '
      '(%d steps, %d state arrays)' % (len(l1), len(s1)))
EOF
opt_gate_rc=$?
if [ "$opt_gate_rc" -ne 0 ]; then
    echo "ci_smoke: opt pipeline gate FAILED (rc=$opt_gate_rc)"
fi

echo "== ci_smoke: shard pass — 2-device mesh bitwise parity =="
# the GSPMD-style partitioner gate (docs/passes.md, shard pass): the
# bench transformer on a 2-device CPU mesh with PT_SHARD=1 must (a)
# train bitwise-equal to the SAME optimized program on a single device
# (replicated feeds; ZeRO-sharded params + Adam state on the mesh side),
# (b) lint clean of D017 after the rewrite, and (c) insert a stable set
# of collectives — two optimize runs, identical reshards_inserted and
# collective_bytes
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    python - <<'EOF'
import sys

import numpy as np
import jax

import paddle_tpu as fluid
from paddle_tpu.core import passes
from paddle_tpu.analysis import lint_program
from paddle_tpu.models import transformer as tr
from paddle_tpu.parallel.mesh import make_mesh

def build(B=2, T=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=256, trg_vocab=256, max_len=T,
                           n_layer=2, n_head=2, d_model=32, d_inner=64,
                           dropout=0.1, use_flash=False)
    main.set_amp(True)
    main.set_mesh_axes({'data': 2})
    # replicated feeds: the sharded run sees the SAME global batch as
    # the single-device run, so parity is bitwise, not allclose
    for v in main.global_block().vars.values():
        if getattr(v, 'is_data', False) and v.shape is not None:
            v.sharding = (None,) * len(v.shape)
    return main, startup, out

main, _, out = build()
fetch = (out['loss'].name,)
opt1, stats1 = passes.optimize_program(main, fetch)
opt2, stats2 = passes.optimize_program(main, fetch)
s1, s2 = stats1['passes']['shard'], stats2['passes']['shard']
for k in ('reshards_inserted', 'collective_bytes', 'grad_allreduce',
          'all_gathers'):
    if s1[k] != s2[k]:
        sys.exit('ci_smoke: shard pass unstable across runs: %s %r vs %r'
                 % (k, s1[k], s2[k]))
if not (s1['grad_allreduce'] or s1['all_gathers']
        or s1['reshards_inserted']):
    sys.exit('ci_smoke: shard pass inserted no collectives on a meshed '
             'transformer — the partitioner is not running')
res = lint_program(opt1, fetch_names=fetch)
d17 = [d for d in res.diagnostics if d.code == 'D017']
if d17:
    sys.exit('ci_smoke: D017 on the shard-optimized transformer: %s'
             % [d.message[:90] for d in d17[:3]])
print('ci_smoke: shard pass stable (%d grad_allreduce, %d all_gather, '
      '%d reshard, %d bytes), zero D017'
      % (s1['grad_allreduce'], s1['all_gathers'], s1['reshards_inserted'],
         s1['collective_bytes']))

def train(mesh):
    main, startup, out = build()
    exe = fluid.Executor(mesh=make_mesh(data=2) if mesh else None)
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = tr.synthetic_batch(rng, 2, 16, 256)
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [np.asarray(exe.run(main, feed=feed,
                                     fetch_list=[out['loss']])[0])
                  for _ in range(2)]
        state = {n: np.asarray(v) for n, v in scope.vars.items()}
    return losses, state

lm, sm = train(True)
ls, ss = train(False)
for a, b in zip(lm, ls):
    if not np.array_equal(a, b):
        sys.exit('ci_smoke: sharded losses diverge from single-device: '
                 '%r vs %r' % (a, b))
# state keys differ only by the fresh unique-name counter per build;
# compare sorted positionally (same build => same order)
if len(sm) != len(ss):
    sys.exit('ci_smoke: sharded run state count %d != single-device %d'
             % (len(sm), len(ss)))
bad = [n1 for (n1, a), (n2, b)
       in zip(sorted(sm.items()), sorted(ss.items()))
       if not np.array_equal(a, b)]
if bad:
    sys.exit('ci_smoke: sharded end-of-run state diverges: %s' % bad[:5])
print('ci_smoke: PT_SHARD=1 2-device mesh bitwise-equal to '
      'single-device (%d steps, %d state arrays)' % (len(lm), len(sm)))
EOF
shard_rc=$?
if [ "$shard_rc" -ne 0 ]; then
    echo "ci_smoke: shard pass gate FAILED (rc=$shard_rc)"
fi

echo "== ci_smoke: strict-emit zoo coverage =="
# direct-emitter gate, part 1 (docs/emitter.md): every zoo program must
# be fully emit-capable — zero D015 lint findings, an EmitEngine builds
# without fallback under PT_STRICT_EMIT=1, and (dense-feed models) the
# whole training program jit-TRACES through the emitter with synthesized
# params/feeds — runtime emission exercised, no backend compile paid.
# One op losing its emit rule or a new builtin op landing without one
# fails here, not as a silent cold-start regression.
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_EMIT=1 PT_STRICT_EMIT=1 \
    PT_CACHE=0 python - <<'EOF'
import os
import sys
import time

import numpy as np

sys.path.insert(0, 'tools')
import pt_lint  # noqa: E402

from paddle_tpu.core import emit, passes  # noqa: E402
from paddle_tpu.core import executor as ptex  # noqa: E402

fails = []
for name in pt_lint.builtin_names():
    prog, feeds, fetches = pt_lint._zoo_entry(name)()
    res = prog.lint(feed_names=feeds, fetch_list=fetches)
    d15 = [d for d in res if d.code == 'D015']
    if d15:
        fails.append((name, d15[0].render()))
        continue
    opt_prog, _ = passes.maybe_optimize(prog, tuple(fetches))
    try:
        engine = emit.build_engine(opt_prog, feeds, fetches)
    except emit.EmitFallback as e:
        fails.append((name, 'EmitFallback: %s' % e))
        continue
    block = prog.global_block()
    if any(getattr(block.vars[f], 'lod_level', 0) for f in feeds):
        print('ci_smoke: %-14s emit-capable (%d op sigs; LoD feeds -> '
              'static coverage only)' % (name, len(engine.coverage)))
        continue
    rng = np.random.RandomState(0)
    feed_vals = {}
    for f in feeds:
        v = block.vars[f]
        shape = tuple(2 if d in (-1, None) else int(d) for d in v.shape)
        dt = np.dtype(v.dtype)
        feed_vals[f] = (np.zeros(shape, dt) if dt.kind in 'iub'
                        else rng.standard_normal(shape).astype(dt))
    jit_fn, params_in, _ = ptex._lower(
        opt_prog, feeds, fetches, donate=False, check_nan=False,
        emit_engine=engine)
    params = {}
    for pn in params_in:
        v = block.vars[pn]
        params[pn] = np.zeros(tuple(int(d) for d in v.shape),
                              np.dtype(v.dtype))
    t0 = time.perf_counter()
    try:
        jit_fn.trace(params, feed_vals, np.uint32(0))
    except (emit.EmitError, emit.EmitFallback) as e:
        fails.append((name, 'trace-time: %s' % e))
        continue
    print('ci_smoke: %-14s traced under strict emit (%d op sigs, %.1fs)'
          % (name, len(engine.coverage), time.perf_counter() - t0))
if fails:
    for name, why in fails:
        print('ci_smoke: STRICT-EMIT GAP in %s: %s' % (name, why))
    sys.exit('ci_smoke: %d zoo program(s) not fully emit-capable'
             % len(fails))
print('ci_smoke: all %d zoo programs emit with zero fallbacks '
      'under PT_STRICT_EMIT=1' % len(pt_lint.builtin_names()))
EOF
emit_zoo_rc=$?
if [ "$emit_zoo_rc" -ne 0 ]; then
    echo "ci_smoke: strict-emit zoo gate FAILED (rc=$emit_zoo_rc)"
fi

echo "== ci_smoke: ruff =="
# style/bug gate with the committed ruff.toml; the container image may
# not ship ruff (and pip installs are off-limits in CI images) — fall
# back through `python -m ruff` to the stdlib-AST checker
# tools/lint_lite.py so SOME source lint always gates the smoke
if command -v ruff >/dev/null 2>&1; then
    ruff check paddle_tpu/ tests/ tools/
    ruff_rc=$?
elif python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check paddle_tpu/ tests/ tools/
    ruff_rc=$?
else
    echo "ci_smoke: ruff not installed; running tools/lint_lite.py"
    python tools/lint_lite.py paddle_tpu/ tests/ tools/
    ruff_rc=$?
fi

echo "== ci_smoke: pt-lint --json schema =="
# the machine-readable lint surface is a contract like the shared
# telemetry schema: validate every --all-builtin --json --memplan
# result against the key tuples diagnostics.py pins, and require the
# serving-side generation entries to be present and error-free
timeout -k 10 600 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
import subprocess
import sys

from paddle_tpu.analysis.diagnostics import (CODES, SEVERITIES,
                                             DIAG_JSON_KEYS,
                                             RESULT_JSON_KEYS)
from paddle_tpu.analysis.passes.memplan import MEMPLAN_JSON_KEYS

proc = subprocess.run(
    [sys.executable, 'tools/pt_lint.py', '--all-builtin', '--json',
     '--memplan', '--fail-on', 'error'],
    capture_output=True, text=True)
if proc.returncode not in (0, 2):
    sys.exit('ci_smoke: pt_lint --json crashed (rc=%d):\n%s'
             % (proc.returncode, proc.stderr[-2000:]))
out = json.loads(proc.stdout)
if set(out) != {'fail_on', 'results'}:
    sys.exit('ci_smoke: unexpected top-level keys %s' % sorted(out))
results = out['results']
for label in ('builtin:llama_prefill', 'builtin:llama_decode'):
    if label not in results:
        sys.exit('ci_smoke: generation program %s missing from '
                 '--all-builtin' % label)
checked = 0
for label, res in results.items():
    if 'error' in res:
        sys.exit('ci_smoke: %s failed to build: %s'
                 % (label, res['error']))
    if set(res) - {'memplan'} != set(RESULT_JSON_KEYS):
        sys.exit('ci_smoke: %s result keys %s != %s'
                 % (label, sorted(res), sorted(RESULT_JSON_KEYS)))
    if set(res['memplan']) != set(MEMPLAN_JSON_KEYS):
        sys.exit('ci_smoke: %s memplan keys %s != %s'
                 % (label, sorted(res['memplan']),
                    sorted(MEMPLAN_JSON_KEYS)))
    if res['errors']:
        sys.exit('ci_smoke: %s has %d lint error(s)'
                 % (label, res['errors']))
    for d in res['diagnostics']:
        if set(d) != set(DIAG_JSON_KEYS):
            sys.exit('ci_smoke: %s diagnostic keys %s != %s'
                     % (label, sorted(d), sorted(DIAG_JSON_KEYS)))
        if d['code'] not in CODES or d['severity'] not in SEVERITIES:
            sys.exit('ci_smoke: %s bad code/severity %s/%s'
                     % (label, d['code'], d['severity']))
        checked += 1
print('ci_smoke: pt_lint --json schema OK (%d programs, %d diagnostics, '
      'all memplans shaped)' % (len(results), checked))
EOF
lint_schema_rc=$?
if [ "$lint_schema_rc" -ne 0 ]; then
    echo "ci_smoke: pt-lint json schema gate FAILED (rc=$lint_schema_rc)"
fi

echo "== ci_smoke: fault-injection soak =="
# resilience gate (docs/robustness.md): a short training run survives the
# armed PT_FAULT matrix — NaN burst (divergence rollback), torn checkpoint
# write, compile-cache read/write OSErrors (retry_with_backoff), prefetch
# stall — and proves it with counters: recovery.rollbacks > 0,
# faults.injected > 0, zero post-recovery retraces, zero steady-state
# pipeline stalls.  Phase 2 rehearses preemption: SIGTERM mid-run (the
# handler flushes a final checkpoint), then a fresh process must
# auto-resume from it and finish.
soak_dir=$(mktemp -d /tmp/pt_soak.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=1 \
    JAX_COMPILATION_CACHE_DIR="$soak_dir/cache" \
    PT_FAULT="nan_step:at=4,ckpt_write:at=2,cache_read:at=1,cache_write:at=1,prefetch_stall:at=1:s=0.05" \
    python tools/fault_soak.py --steps 12 --ckpt "$soak_dir/ckpt" \
    --assert-recovery
soak_rc=$?
if [ "$soak_rc" -ne 0 ]; then
    echo "ci_smoke: fault-injection soak FAILED (rc=$soak_rc)"
fi

echo "== ci_smoke: preemption (SIGTERM) + auto-resume =="
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
    PT_FAULT="sigterm:at=6" \
    python tools/fault_soak.py --steps 12 --ckpt "$soak_dir/ckpt2"
term_rc=$?
if [ "$term_rc" -eq 0 ]; then
    echo "ci_smoke: SIGTERM fault did not terminate the soak (rc=0)"
    resume_rc=1
else
    timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
        python tools/fault_soak.py --steps 12 --ckpt "$soak_dir/ckpt2" \
        --expect-resume
    resume_rc=$?
fi
if [ "$resume_rc" -ne 0 ]; then
    echo "ci_smoke: preemption auto-resume FAILED (rc=$resume_rc)"
fi
rm -rf "$soak_dir"

echo "== ci_smoke: async executor soak (deferred nan poll, PT_ASYNC=1) =="
# fully-async gate (docs/async.md): the SAME fault soak but with the
# executor in async mode — launches return FetchFuture handles, the fused
# all-finite verdict stays device-resident between polls (PT_NAN_POLL=4),
# and a mid-window nan_step fault must trip a DEFERRED poll, roll back to
# the last clean-verdict checkpoint, and finish with finite losses.
# --expect-async requires nan_poll.polls>=1 AND nan_poll.trips>=1;
# --assert-recovery keeps steady-state stalls pinned at ZERO — the whole
# point of the async executor.
async_dir=$(mktemp -d /tmp/pt_async.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 PT_ASYNC=1 \
    PT_NAN_POLL=4 PT_FAULT="nan_step:at=5" \
    python tools/fault_soak.py --steps 16 --ckpt "$async_dir/ckpt" \
    --assert-recovery --expect-async
async_rc=$?
if [ "$async_rc" -ne 0 ]; then
    echo "ci_smoke: async executor soak FAILED (rc=$async_rc)"
fi
rm -rf "$async_dir"

echo "== ci_smoke: NaN forensics (bisection + quarantine heal, sync) =="
# forensics gate (docs/robustness.md): a single poisoned batch row
# (nan_step:at=5:row=3) trips the verdict; the forensic pipeline must
# replay the condemned window, bisect to the EXACT (step, op, row),
# quarantine the sample, HEAL the window by replaying it with the row
# substituted, and finish with losses bitwise-identical to an
# in-process uninjected reference run over the same quarantine —
# --expect-forensics asserts every link of that chain.
forensic_dir=$(mktemp -d /tmp/pt_forensic.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
    PT_FAULT="nan_step:at=5:row=3" \
    python tools/fault_soak.py --steps 12 --ckpt "$forensic_dir/ckpt" \
    --expect-forensics --assert-recovery
forensic_rc=$?
if [ "$forensic_rc" -ne 0 ]; then
    echo "ci_smoke: forensics (sync) FAILED (rc=$forensic_rc)"
fi

echo "== ci_smoke: NaN forensics (deferred async window, PT_NAN_POLL=8) =="
# the same gate with the deferred verdict: the trip only surfaces at an
# 8-step poll boundary, so the forensic step walk must localize the
# poison INSIDE the condemned window before the op/row bisection
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 PT_ASYNC=1 \
    PT_NAN_POLL=8 PT_FAULT="nan_step:at=5:row=3" \
    python tools/fault_soak.py --steps 16 --ckpt "$forensic_dir/ckpt2" \
    --expect-forensics --expect-async
forensic_async_rc=$?
if [ "$forensic_async_rc" -ne 0 ]; then
    echo "ci_smoke: forensics (async) FAILED (rc=$forensic_async_rc)"
fi
rm -rf "$forensic_dir"

echo "== ci_smoke: pod soak (sharded ckpt, kill-and-resume, reshard) =="
# pod-resilience gate (docs/robustness.md): two sharded-checkpoint
# trainers over one directory; wave 1 SIGKILLs a worker mid-run (the
# survivor must exit RESTART_EXIT_CODE via the health watchdog), wave 2
# arms the device_loss fault site (a worker goes silent and wedges; the
# peer must trip, roll back to the last good manifest, and request a
# restart; the supervisor reaps exactly the wedged host), wave 3
# restarts on the SMALLER roster and must elastically reshard
# (--expect-resume --expect-reshard) and finish with losses bitwise
# equal to an uninterrupted single-host run.  Zero orphaned tmp/.parts
# dirs and >= 1 health-trip flight dump are asserted by the tool.
pod_dir=$(mktemp -d /tmp/pt_pod.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
    python tools/pod_soak.py --workers 2 --steps 30 --dir "$pod_dir" \
    --expect-resume --expect-reshard
pod_rc=$?
if [ "$pod_rc" -ne 0 ]; then
    echo "ci_smoke: pod soak FAILED (rc=$pod_rc)"
fi
rm -rf "$pod_dir"

echo "== ci_smoke: serving soak (continuous batching under chaos) =="
# serving gate (docs/serving.md): serve_soak drives a real
# Predictor-backed ServingEngine with closed+open-loop traffic while
# four fault sites are armed — slow batches, consecutive dispatch
# failures (the breaker must trip AND recover), a compile-cache-miss
# storm, and a mid-run SIGTERM that must turn into a graceful drain.
# --assert-slo fails the gate unless p99 is finite, every admitted
# request got a terminal reply (admitted == completed + errors +
# deadline_exceeded + shed), serving.deadlocks == 0, and the shed rate
# stays under the ceiling.
#
# Observability gates ride the same soak (docs/observability.md):
#   --trace-out      exported Perfetto trace must decompose a request
#                    into queue/dispatch/device child spans linked to
#                    its batch span, covering >= 90% of its latency
#   --metrics-port   /metrics scraped mid-run (serving_admitted_total
#                    present) and post-drain (accounting identity holds
#                    in the scraped values)
#   --expect-flight  the serve_dispatch mid-batch crash must leave a
#                    flight dump holding that batch's span + the
#                    fault.injected event (PT_FLIGHT_DIR below)
flight_dir=$(mktemp -d /tmp/pt_flight.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=0 \
    PT_FLIGHT_DIR="$flight_dir" \
    PT_FAULT="serve_slow_batch:at=1:times=1:s=0.05,serve_dispatch:at=2:times=3,compile_storm:at=12:times=3:s=0.03,queue_overflow:at=30:times=2,sigterm:at=70" \
    python tools/serve_soak.py --requests 80 --qps 150 --clients 2 \
    --deadline-ms 4000 --shed-ceiling 0.35 \
    --assert-slo --expect-breaker --expect-drain \
    --trace-out "$flight_dir/soak_trace.json" --metrics-port 0 \
    --expect-flight
serve_rc=$?
if [ "$serve_rc" -ne 0 ]; then
    echo "ci_smoke: serving soak FAILED (rc=$serve_rc)"
fi
rm -rf "$flight_dir"

echo "== ci_smoke: decode soak (streaming generation under chaos) =="
# generation gate (docs/generation.md): serve_soak --scenario decode
# drives a GenerationEngine over the PAGED KV pool with every density
# multiplier armed — int8-quantized pages (--kv-quant int8), shared-prefix
# caching (the prompts open with one full shared page), speculative
# draft/verify decoding — with open-loop traffic of mixed prompt
# lengths, mid-soak cancellations, periodic overlong prompts (must be
# REFUSED, never truncated), and a decode_step fault that must turn
# into clean error replies while the engine keeps serving.
# --assert-slo fails the gate unless the accounting identity holds
# (terminal == admitted), serving.deadlocks == 0, TTFT/ITL histograms
# are populated, at least one mixed prefill+decode dispatch happened,
# zero compiles landed after warmup (the fused window executables are
# closed over page GEOMETRY, never per-request block tables), the
# prefix cache actually hit (prefix_hits > 0), speculation actually
# accepted tokens (spec_accepted > 0), and every KV slot AND page is
# back on the free list after drain.  --capacity-floor then reruns a
# fixed 16 KiB page budget with an oversubscribed slot table: excess
# streams must queue at admission backpressure (never die mid-stream
# as kv_oom) while >= 8 concurrent streams hold SLO — 4x what the
# dense PR-11 layout fits in the same bytes.  PT_CACHE=1 so the
# decode/prefill/verify executables round-trip the persistent AOT
# cache on repeat runs.
decode_cache=$(mktemp -d /tmp/pt_decode_cache.XXXXXX)
timeout -k 10 600 env JAX_PLATFORMS=cpu PT_CACHE=1 \
    JAX_COMPILATION_CACHE_DIR="$decode_cache" \
    PT_FAULT="decode_step:at=3" \
    python tools/serve_soak.py --scenario decode --requests 40 --qps 60 \
    --assert-slo --speculative --page-len 4 --kv-quant int8 \
    --capacity-floor 8
decode_rc=$?
if [ "$decode_rc" -ne 0 ]; then
    echo "ci_smoke: decode soak FAILED (rc=$decode_rc)"
fi
rm -rf "$decode_cache"

echo "== ci_smoke: tier-1 tests =="
set -o pipefail
rm -f /tmp/_t1.log
# six workers, a file to a worker: one process does not reach the end
# inside any limit worth waiting for (505 s this way, PR 51).  The
# libtpu variable lets several workers describe a v5e at once, on this
# CPU only: never send this line to the machine with the chip
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
    --dist loadfile -p no:randomly 2>&1 | tee /tmp/_t1.log
t1_rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"

echo "== ci_smoke: shared telemetry schema =="
# shared-schema contract (observability/export.py): the soak tools
# (serve_soak.py, fault_soak.py, pod_soak.py) print sections of one
# SCHEMA table — validate the declarative table itself once, here
env JAX_PLATFORMS=cpu python - <<'EOF'
import sys

from paddle_tpu.observability import export as obs_export
for section, need in (('serving', ('admitted', 'terminal_replies',
                                   'shed_rate', 'p50_ms', 'p99_ms',
                                   'ttft_p50_ms', 'ttft_p99_ms',
                                   'itl_p50_ms', 'itl_p99_ms',
                                   'kv_slots_in_use', 'counters')),
                      ('resilience', ('counters',))):
    have = obs_export.schema_keys(section)
    absent = [k for k in need if k not in have]
    if absent:
        sys.exit('ci_smoke: SCHEMA[%r] is missing keys %s'
                 % (section, absent))
print('ci_smoke: telemetry schema ok')
EOF
schema_rc=$?

if [ "$t1_rc" -ne 0 ]; then
    echo "ci_smoke: tier-1 tests FAILED (rc=$t1_rc)"
fi
[ "$t1_rc" -eq 0 ] && [ "$schema_rc" -eq 0 ] && [ "$lint_rc" -eq 0 ] && \
    [ "$lint_schema_rc" -eq 0 ] && \
    [ "$ruff_rc" -eq 0 ] && [ "$opt_lint_rc" -eq 0 ] && \
    [ "$opt_gate_rc" -eq 0 ] && [ "$shard_rc" -eq 0 ] && \
    [ "$emit_zoo_rc" -eq 0 ] && \
    [ "$soak_rc" -eq 0 ] && \
    [ "$resume_rc" -eq 0 ] && [ "$async_rc" -eq 0 ] && \
    [ "$forensic_rc" -eq 0 ] && [ "$forensic_async_rc" -eq 0 ] && \
    [ "$pod_rc" -eq 0 ] && \
    [ "$serve_rc" -eq 0 ] && [ "$decode_rc" -eq 0 ]
