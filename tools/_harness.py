"""Shared bench/soak process harness: stage tracking, the structured
{"error", "stage"} JSON tail, the hang watchdog, and the one rule for
which device a measuring tool may run on.

One implementation, one contract, five consumers (bench.py, perflab
children, fault_soak, serve_soak, pod_soak): whatever kills the process
— an exception, a hang — the LAST stdout line is

    {"error": <kind>, "stage": <last stage entered>, "detail": ...}

so a dead round is still a diagnosable artifact instead of a bare
stack (or nothing).  Stdlib-only at import: perflab's parent imports
this and must stay off JAX, because a chip belongs to one process and
its children need it.
"""
import json
import os
import sys
import threading
import traceback

_TOOL = ['BENCH']
_STAGE = ['startup']


def set_tool(name):
    """Stage-line prefix, e.g. set_tool('PERFLAB') -> 'PERFLAB: stage=x'."""
    _TOOL[0] = name


def current_stage():
    return _STAGE[0]


def stage(name):
    _STAGE[0] = name
    print('%s: stage=%s' % (_TOOL[0], name), file=sys.stderr)


def emit_error(kind, detail, **extra):
    """The structured JSON death tail.  Extra keys (e.g. scenario=...)
    ride along so supervisors can attribute the failure."""
    rec = {'error': kind, 'stage': _STAGE[0], 'detail': str(detail)[:2000]}
    rec.update(extra)
    print(json.dumps(rec), flush=True)


def install_watchdog(default_s=1800.0, env='BENCH_WATCHDOG_S',
                     flight_tag=None, **extra):
    """A hung in-process compile/launch used to produce a DEAD round: no
    JSON, no diagnosis.  The watchdog emits the structured JSON tail
    naming the last stage entered, dumps every thread's stack to stderr,
    leaves a flight-recorder postmortem, and exits hard.  <env>=0
    disables.  Returns the timer (cancel it on clean exit) or None."""
    budget = float(os.environ.get(env, str(default_s)))
    if budget <= 0:
        return None

    def _trip():
        emit_error('watchdog expired after %.0fs' % budget,
                   'hung in stage %r' % _STAGE[0], **extra)
        try:
            import faulthandler
            faulthandler.dump_traceback(file=sys.stderr)
        except Exception:
            pass
        try:
            # a flight postmortem naming the hung stage (only if the
            # observability plane was imported — never import jax here)
            if 'paddle_tpu.observability.flight' in sys.modules:
                _flight = sys.modules['paddle_tpu.observability.flight']
                _flight.record(flight_tag or 'harness.watchdog',
                               stage=_STAGE[0], budget_s=budget)
                _flight.maybe_dump('watchdog')
        except Exception:
            pass
        os._exit(3)

    t = threading.Timer(budget, _trip)
    t.daemon = True
    t.start()
    return t


def cpu_requested():
    """A deliberate ``JAX_PLATFORMS=cpu`` run: CI plumbing, labelled cpu."""
    return 'cpu' in (os.environ.get('JAX_PLATFORMS') or '')


def require_device():
    """(platform, device_kind) of the device this process runs on.  A
    tool that wants the chip and finds none FAILS — there is no probe
    subprocess (a child that opens the chip takes it from its parent)
    and no fall-back to the CPU; only ``cpu_requested()`` runs on it."""
    import jax
    dev0 = jax.devices()[0]
    if dev0.platform != 'tpu' and not cpu_requested():
        raise RuntimeError(
            'this run wants a TPU and JAX found %s (%s); set '
            'JAX_PLATFORMS=cpu for a plumbing run labelled cpu'
            % (dev0.platform, dev0.device_kind))
    return dev0.platform, str(dev0.device_kind)


def main_guard(main, watchdog=True, watchdog_default_s=1800.0,
               watchdog_env='BENCH_WATCHDOG_S', flight_tag=None, **extra):
    """Run ``main()`` under the watchdog with the JSON-tail contract:
    an uncaught exception prints its traceback to stderr and the
    structured {"error", "stage"} line to stdout, then exits 1.
    SystemExit passes through untouched (soak SLO failures keep their
    messages and codes).  ``extra`` keys (e.g. scenario=...) ride along
    in the JSON tail.  Returns main()'s return code via sys.exit."""
    wd = install_watchdog(watchdog_default_s, env=watchdog_env,
                          flight_tag=flight_tag,
                          **extra) if watchdog else None
    try:
        rc = main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 - structured JSON death
        traceback.print_exc()
        emit_error(type(e).__name__, e, **extra)
        sys.exit(1)
    finally:
        if wd is not None:
            wd.cancel()
    sys.exit(rc)
