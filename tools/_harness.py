"""Shared soak process harness: stage tracking, the structured
{"error", "stage"} JSON tail and the hang watchdog.

One implementation, one contract, three consumers (fault_soak,
serve_soak, pod_soak): whatever kills the process — an exception, a
hang — the LAST stdout line is

    {"error": <kind>, "stage": <last stage entered>, "detail": ...}

so a dead soak is still a diagnosable artifact instead of a bare
stack (or nothing).  Stdlib-only at import.
"""
import json
import os
import sys
import threading
import traceback

_TOOL = ['SOAK']
_STAGE = ['startup']


def set_tool(name):
    """Stage-line prefix, e.g. set_tool('POD_SOAK') -> 'POD_SOAK: stage=x'."""
    _TOOL[0] = name


def stage(name):
    _STAGE[0] = name
    print('%s: stage=%s' % (_TOOL[0], name), file=sys.stderr)


def emit_error(kind, detail):
    """The structured JSON death tail."""
    rec = {'error': kind, 'stage': _STAGE[0], 'detail': str(detail)[:2000]}
    print(json.dumps(rec), flush=True)


def install_watchdog(flight_tag=None):
    """A hung in-process compile/launch used to produce a DEAD soak: no
    JSON, no diagnosis.  The watchdog emits the structured JSON tail
    naming the last stage entered, dumps every thread's stack to stderr,
    leaves a flight-recorder postmortem, and exits hard.
    PT_SOAK_WATCHDOG_S=0 disables.  Returns the timer (cancel it on
    clean exit) or None."""
    budget = float(os.environ.get('PT_SOAK_WATCHDOG_S', '1800'))
    if budget <= 0:
        return None

    def _trip():
        emit_error('watchdog expired after %.0fs' % budget,
                   'hung in stage %r' % _STAGE[0])
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


def main_guard(main, flight_tag=None):
    """Run ``main()`` under the watchdog with the JSON-tail contract:
    an uncaught exception prints its traceback to stderr and the
    structured {"error", "stage"} line to stdout, then exits 1.
    SystemExit passes through untouched (soak SLO failures keep their
    messages and codes).  Returns main()'s return code via sys.exit."""
    wd = install_watchdog(flight_tag)
    try:
        rc = main()
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 - structured JSON death
        traceback.print_exc()
        emit_error(type(e).__name__, e)
        sys.exit(1)
    finally:
        if wd is not None:
            wd.cancel()
    sys.exit(rc)
