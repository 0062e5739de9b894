"""Telemetry export surfaces: Prometheus text rendering, the one shared
telemetry-snapshot schema, and a stdlib-only HTTP endpoint.

Three consumers, one source of truth:

  * ``render_prometheus()`` walks the live metrics registry and emits
    Prometheus text exposition format (0.0.4).  Dotted names become
    underscored families; counters get the ``_total`` suffix, so
    ``serving.admitted`` scrapes as ``serving_admitted_total``.
    Histograms render as real cumulative-``le`` histograms straight
    from the bounded log buckets.
  * ``telemetry_snapshot(section, ...)`` is the ONE JSON emitter behind
    ``tools/serve_soak.py``, ``tools/fault_soak.py`` and
    ``tools/pod_soak.py``, and the ``setup`` block of ``/varz`` — each
    section's keys live in ``SCHEMA``,
    so a renamed counter breaks one declarative table (which ci_smoke
    validates once) instead of silently drifting three tools apart.
  * ``MetricsServer`` serves ``/metrics`` (Prometheus text),
    ``/healthz`` (ServingEngine health, 503 while not accepting) and
    ``/varz`` (full JSON debug dump) from a daemon thread.  The
    ServingEngine owns one when ``PT_METRICS_PORT`` (or
    ``ServingConfig.metrics_port``) is set — it starts at ``start()``
    and is torn down by ``stop()``.
"""
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import flight as _flight
from . import metrics
from . import retrace
from . import tracing

__all__ = ['render_prometheus', 'prom_name', 'telemetry_snapshot',
           'schema_keys', 'SCHEMA', 'MetricsServer', 'start_http_server',
           'resolve_metrics_port', 'PROM_CONTENT_TYPE']

PROM_CONTENT_TYPE = 'text/plain; version=0.0.4; charset=utf-8'


# ------------------------------------------------------------------ prom
def prom_name(name, suffix=''):
    """`serving.admitted` -> `serving_admitted` (+ optional suffix)."""
    n = ''.join(ch if (ch.isalnum() or ch == '_') else '_' for ch in name)
    if n and n[0].isdigit():
        n = '_' + n
    return n + suffix


def _fmt(v):
    return '%.10g' % float(v)


def render_prometheus():
    """The whole registry in Prometheus text exposition format."""
    lines = []
    for name, m in metrics.registry().items():
        if isinstance(m, metrics.Counter):
            pn = prom_name(name, '_total')
            lines.append('# TYPE %s counter' % pn)
            lines.append('%s %s' % (pn, _fmt(m.snapshot())))
        elif isinstance(m, metrics.Gauge):
            v = m.snapshot()
            if v is None or not isinstance(v, (int, float)):
                continue
            pn = prom_name(name)
            lines.append('# TYPE %s gauge' % pn)
            lines.append('%s %s' % (pn, _fmt(v)))
        elif isinstance(m, metrics.Histogram):
            pn = prom_name(name)
            snap = m.snapshot()
            lines.append('# TYPE %s histogram' % pn)
            for le, cum in m.cumulative_buckets():
                lines.append('%s_bucket{le="%s"} %d' % (pn, _fmt(le), cum))
            lines.append('%s_bucket{le="+Inf"} %d' % (pn, snap['count']))
            lines.append('%s_sum %s' % (pn, _fmt(snap.get('sum', 0.0))))
            lines.append('%s_count %d' % (pn, snap['count']))
    return '\n'.join(lines) + '\n'


# ------------------------------------------------- shared JSON schema
# Spec kinds: ('int', counter) reads one counter; ('sum_int', names) /
# ('ratio', num, den) derive; ('quantile', hist, q) reads a
# bounded-histogram quantile; ('block_prefix', prefixes, names) /
# ('block_names', names) build the nested counters dict soak tools print.
SCHEMA = {
    'serving': (
        ('admitted', ('int', 'serving.admitted')),
        ('terminal_replies', ('sum_int', ('serving.completed',
                                          'serving.errors',
                                          'serving.deadline_exceeded',
                                          'serving.shed'))),
        ('shed_rate', ('ratio', 'serving.shed', 'serving.admitted')),
        ('p50_ms', ('quantile', 'serving.latency_ms', 0.50)),
        ('p99_ms', ('quantile', 'serving.latency_ms', 0.99)),
        ('breaker_trips', ('int', 'serving.breaker_trips')),
        ('breaker_recoveries', ('int', 'serving.breaker_recoveries')),
        ('deadlocks', ('int', 'serving.deadlocks')),
        ('ttft_p50_ms', ('quantile', 'serving.ttft_ms', 0.50)),
        ('ttft_p99_ms', ('quantile', 'serving.ttft_ms', 0.99)),
        ('itl_p50_ms', ('quantile', 'serving.itl_ms', 0.50)),
        ('itl_p99_ms', ('quantile', 'serving.itl_ms', 0.99)),
        ('kv_slots_in_use', ('int', 'generation.kv_slots_in_use')),
        ('kv_pages_in_use', ('int', 'generation.kv_pages_in_use')),
        ('kv_bytes_reserved', ('int', 'generation.kv_bytes_reserved')),
        ('kv_bytes_live', ('int', 'generation.kv_bytes_live')),
        ('counters', ('block_prefix', ('serving.', 'faults.',
                                       'generation.'),
                      ('bucketer.bucket_count',))),
    ),
    'resilience': (
        ('counters', ('block_names', (
            'faults.injected', 'recovery.rollbacks', 'recovery.divergences',
            'recovery.skipped_steps', 'recovery.device_loss', 'ckpt.saves',
            'ckpt.write_failures', 'ckpt.torn_deleted', 'ckpt.restores',
            'ckpt.corrupt_skipped', 'ckpt.shard_writes',
            'ckpt.shard_manifests', 'ckpt.partial_swept', 'ckpt.reshards',
            'ckpt.desync_dropped', 'health.beats', 'health.trips',
            'health.lost_hosts', 'health.desyncs', 'retry.attempts',
            'executor.retraces', 'executor.stall_count',
            'prefetch.starvation_count',
            'nan_poll.polls', 'nan_poll.trips',
            'executor.host_blocked_s', 'recovery.forensics_runs',
            'recovery.forensics_replay_steps',
            'recovery.escalation.quarantine', 'recovery.escalation.skip',
            'feed.quarantined', 'retry.attempts.feed_read'))),
    ),
    # "why did this restart take 40 s": every phase between process start
    # and the first warm launch, in seconds, nested as the spans are
    # (docs/observability.md, "Set-up from inside")
    'setup': (
        ('process_s', ('block_names', (
            'process.before_import_s', 'process.import_s'))),
        ('training_s', ('block_names', (
            'program.build_s', 'executor.prepare_s', 'executor.lint_s',
            'executor.optimize_s', 'executor.emit_build_s',
            'executor.lower_s', 'executor.gather_params_s',
            'executor.emit_s', 'executor.trace_s',
            'executor.backend_compile_s', 'executor.run_s'))),
        ('serving_s', ('block_names', (
            'generation.init_s', 'generation.warmup_s',
            'generation.compile_s'))),
        ('compile_cache_s', ('block_names', (
            'compile_cache.fingerprint_s', 'compile_cache.load_s',
            'compile_cache.store_s'))),
        ('executables', ('block_names', (
            'executor.lowerings', 'generation.compiles',
            'compile_cache.disk_hits', 'compile_cache.disk_misses',
            'compile_cache.disk_stores'))),
    ),
}


def schema_keys(section):
    return [k for k, _ in SCHEMA[section]]


def telemetry_snapshot(section, snapshot=None):
    """Build the section's telemetry dict from the live registry (or
    from ``snapshot``, an earlier ``obs.counters()``)."""
    spec = SCHEMA[section]
    c = metrics.counters() if snapshot is None else snapshot

    def val(name):
        return c.get(name) or 0

    out = {}
    for key, s in spec:
        kind = s[0]
        if kind == 'int':
            out[key] = int(val(s[1]))
        elif kind == 'sum_int':
            out[key] = sum(int(val(n)) for n in s[1])
        elif kind == 'ratio':
            out[key] = round(float(val(s[1])) / float(max(1, val(s[2]))), 4)
        elif kind == 'quantile':
            q = metrics.histogram(s[1]).quantile(s[2])
            out[key] = None if q is None else float(q)
        elif kind == 'block_prefix':
            prefixes, names = s[1], s[2]
            out[key] = {k: c.get(k) for k in sorted(c)
                        if k.startswith(prefixes) or k in names}
        elif kind == 'block_names':
            out[key] = {k: c.get(k) or 0 for k in s[1]}
        else:
            raise ValueError('unknown telemetry spec kind %r' % (kind,))
    return out


# ------------------------------------------------------- HTTP endpoint
def _varz():
    snap = metrics.metrics_snapshot()
    snap['spans'] = tracing.span_summary()
    snap['retrace_reports'] = list(retrace.explainer().reports)
    snap['flight_events'] = len(_flight.flight().events())
    snap['setup'] = telemetry_snapshot('setup')
    snap['env'] = {k: v for k, v in os.environ.items()
                   if k.startswith('PT_') or k == 'JAX_PLATFORMS'}
    return snap


class _Handler(BaseHTTPRequestHandler):
    server_version = 'paddle-tpu-obs/1'

    def log_message(self, fmt, *args):   # no stderr spam per scrape
        pass

    def do_GET(self):
        path = self.path.split('?', 1)[0]
        if path == '/metrics':
            body, ctype, code = render_prometheus().encode(), \
                PROM_CONTENT_TYPE, 200
        elif path == '/healthz':
            engine = getattr(self.server, 'pt_engine', None)
            if engine is not None:
                h = engine.health()
                code = 200 if h.get('accepting') else 503
            else:
                h, code = {'state': 'ok', 'accepting': True}, 200
            body, ctype = (json.dumps(h) + '\n').encode(), 'application/json'
        elif path == '/varz':
            body, ctype, code = \
                (json.dumps(_varz(), default=str) + '\n').encode(), \
                'application/json', 200
        else:
            body, ctype, code = b'not found\n', 'text/plain', 404
        self.send_response(code)
        self.send_header('Content-Type', ctype)
        self.send_header('Content-Length', str(len(body)))
        self.end_headers()
        self.wfile.write(body)


class MetricsServer(object):
    """Daemon-threaded HTTP server for /metrics, /healthz, /varz.
    ``port=0`` binds an ephemeral port (tests); ``.port`` is the bound
    one.  ``engine`` (optional) backs /healthz."""

    def __init__(self, port=0, host='127.0.0.1', engine=None):
        self._host = host
        self._want_port = int(port)
        self._engine = engine
        self._httpd = None
        self._thread = None

    def start(self):
        if self._httpd is not None:
            return self
        httpd = ThreadingHTTPServer((self._host, self._want_port), _Handler)
        httpd.daemon_threads = True
        httpd.pt_engine = self._engine
        self._httpd = httpd
        self._thread = threading.Thread(target=httpd.serve_forever,
                                        name='ObsMetricsHTTP', daemon=True)
        self._thread.start()
        metrics.gauge('obs.metrics_port').set(self.port)
        return self

    @property
    def port(self):
        return self._httpd.server_address[1] if self._httpd else None

    def url(self, path='/metrics'):
        return 'http://%s:%d%s' % (self._host, self.port, path)

    def stop(self):
        httpd, self._httpd = self._httpd, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


def start_http_server(port=0, host='127.0.0.1', engine=None):
    return MetricsServer(port=port, host=host, engine=engine).start()


def resolve_metrics_port(explicit=None):
    """Config beats env (`PT_METRICS_PORT`); None means no server."""
    if explicit is not None:
        return int(explicit)
    env = os.environ.get('PT_METRICS_PORT')
    if env in (None, ''):
        return None
    return int(env)
