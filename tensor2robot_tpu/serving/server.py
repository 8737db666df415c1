"""HTTP front door for the batched serving plane.

Same dependency discipline as ``observability/metricsz.py``: pure stdlib
``http.server.ThreadingHTTPServer`` on daemon threads — no web framework,
no RPC stack. Each connection thread only parses JSON and blocks on a
:class:`~tensor2robot_tpu.serving.batching.ServingFuture`; ALL device
work stays on the batcher's single dispatcher thread, so N concurrent
clients become one padded device dispatch per assembly window.

The server fronts either ONE model (``ServingServer(predictor, ...)``,
the historical shape) or a whole :class:`~tensor2robot_tpu.serving.
router.ModelRouter` (``ServingServer(router=router, ...)``) — the
multi-model/multi-tenant plane with HBM-budgeted paging and priority
admission.

Endpoints:

* ``POST /v1/predict`` — body ``{"features": {<name>: <nested lists>}}``
  (a bare feature dict is also accepted). Each feature carries a leading
  batch dim shared across features; a single example may omit it (the
  predictor's dim-expansion contract). Reply: ``{"outputs": {...},
  "model_version": N, "examples": n, "request_id": "..."}``. An
  ``X-Request-Id`` request header is honored as the request's ID (else
  one is generated) and echoed back as the same response header on every
  status — the handle that joins a client log line to the plane's
  latency exemplars, slow-request log, and flight-ring trace slice.
* ``POST /v1/models/<name>/predict`` — same contract against a named
  model (router mode; a single-model server only knows its one model).
* ``X-Priority: interactive|best_effort`` request header — the
  admission-control class (router mode; default ``interactive``).
  Best-effort traffic is shed first under queue pressure: 503 with a
  ``Retry-After`` header.
* ``GET /healthz`` — liveness + loaded model version(s); the balancer's
  ejection/readmission signal.
* ``GET /statz`` — the plane's report (same document the registry's
  ``/metricsz`` embeds via ``register_report_provider``), including the
  bounded slow-request log and latency exemplars; router mode nests
  per-model sections plus paging/admission SLOs. Also the process's
  ``device`` (platform / kind / count as jax reports them) and its
  ``compile`` counters (backend compiles, seconds, persistent-cache
  hits and misses).

Status codes: 400 malformed request, 404 unknown path/model, 503 shed /
queue full / shutting down (back off and honor ``Retry-After``), 504
request timed out in the plane, 500 dispatch failure.
"""

from __future__ import annotations

import http.server
import json
import logging
import math
import threading
import time
import urllib.parse
from typing import Any, Dict, Optional

import numpy as np

from tensor2robot_tpu.observability import device as device_lib
from tensor2robot_tpu.observability import slo as slo_lib
from tensor2robot_tpu.observability import tracing
from tensor2robot_tpu.serving import batching as batching_lib
from tensor2robot_tpu.utils import compilation_cache

_MODELS_PREFIX = '/v1/models/'
_PREDICT_SUFFIX = '/predict'


class _Handler(http.server.BaseHTTPRequestHandler):
  """Thin JSON adapter over the batcher/router; never touches the device."""

  protocol_version = 'HTTP/1.1'  # keep-alive: clients reuse connections

  def log_message(self, format, *args):  # noqa: A002 - stdlib signature
    del format, args  # a load test would spam one line per request

  def _reply(self, code: int, payload: Dict[str, Any],
             request_id: Optional[str] = None,
             retry_after_secs: Optional[float] = None) -> None:
    body = json.dumps(payload).encode()
    self.send_response(code)
    self.send_header('Content-Type', 'application/json')
    self.send_header('Content-Length', str(len(body)))
    if request_id:
      self.send_header('X-Request-Id', request_id)
    if retry_after_secs is not None:
      self.send_header('Retry-After',
                       str(max(1, int(math.ceil(retry_after_secs)))))
    self.end_headers()
    try:
      self.wfile.write(body)
    except (BrokenPipeError, ConnectionResetError):
      pass  # client gave up; the batch result is already accounted

  def do_GET(self):  # noqa: N802 - stdlib naming
    parsed = urllib.parse.urlparse(self.path)
    path = parsed.path.rstrip('/') or '/'
    query = urllib.parse.parse_qs(parsed.query)
    router = self.server.router  # type: ignore[attr-defined]
    batcher = self.server.batcher  # type: ignore[attr-defined]
    if path == '/healthz':
      if router is not None:
        versions = router.versions()
        self._reply(200, {'status': 'ok', 'models': versions,
                          'model_version': versions.get(
                              router.default_model, -1)})
      else:
        self._reply(200, {'status': 'ok',
                          'model_version': batcher.model_version})
    elif path == '/statz':
      plane = router if router is not None else batcher
      doc = plane.report()
      # What this replica runs on and what its start-up cost: the
      # process facts a check of a chip deployment reads beside the
      # plane's own.
      doc['device'] = device_lib.describe()
      doc['compile'] = compilation_cache.report()
      engine = slo_lib.global_engine()
      if engine is not None:
        doc['slo'] = engine.report()
      self._reply(200, doc)
    elif path == '/tracez':
      self._reply(200, tracing.tracez_document(
          trace_id=query.get('trace_id', [None])[0] or None,
          request_id=query.get('request_id', [None])[0] or None,
          probe_only=query.get('probe', [''])[0] not in ('', '0')))
    else:
      self._reply(404, {'error': f'unknown path {path!r}',
                        'endpoints': ['/v1/predict',
                                      '/v1/models/<name>/predict',
                                      '/healthz', '/statz', '/tracez']})

  def _route(self, path: str) -> Optional[str]:
    """Predict path → model name ('' = default) or None (not predict)."""
    if path == '/v1/predict':
      return ''
    if path.startswith(_MODELS_PREFIX) and path.endswith(_PREDICT_SUFFIX):
      name = path[len(_MODELS_PREFIX):-len(_PREDICT_SUFFIX)]
      if name and '/' not in name:
        return name
    return None

  def do_POST(self):  # noqa: N802 - stdlib naming
    path = self.path.split('?', 1)[0].rstrip('/')
    # Ingress request ID: honor the client's X-Request-Id (distributed-
    # trace convention) or let the batcher mint one; either way it is
    # echoed on EVERY reply below so the client can quote it.
    request_id = (self.headers.get('X-Request-Id') or '').strip() or None
    # Ingress trace context: a traceparent header puts this request's
    # ingress span (and the batcher's request/queued/dispatch spans
    # below it) into the process /tracez index under the fleet-wide
    # trace id — every status, including sheds: the failed replica of a
    # retried request must show up in the assembled timeline.
    ctx = tracing.parse_traceparent(
        self.headers.get(tracing.TRACEPARENT_HEADER))
    ingress_start = time.time() if ctx else 0.0
    ingress_span = tracing.mint_span_id() if ctx else ''

    def reply(code, payload, request_id=None, **kwargs):
      self._reply(code, payload, request_id=request_id, **kwargs)
      if ctx is not None:
        tracing.record_span(
            'server/request', 'server', ctx.trace_id, ingress_span,
            ctx.span_id, ingress_start, time.time(),
            request_id=request_id or '',
            detail=f'status={code} path={path}',
            service_label=getattr(self.server, 'service_label', None))

    model = self._route(path)
    if model is None:
      reply(404, {'error': f'unknown path {path!r}'},
            request_id=request_id)
      return
    priority = (self.headers.get('X-Priority') or '').strip() or None
    try:
      length = int(self.headers.get('Content-Length', 0))
      payload = json.loads(self.rfile.read(length) or b'{}')
      raw = payload.get('features', payload)
      if not isinstance(raw, dict) or not raw:
        raise ValueError('body must carry a non-empty feature dict')
      features = {k: np.asarray(v) for k, v in raw.items()}
    except (ValueError, TypeError) as e:
      reply(400, {'error': f'malformed request: {e}'},
            request_id=request_id)
      return
    router = self.server.router  # type: ignore[attr-defined]
    child_ctx = (tracing.TraceContext(ctx.trace_id, ingress_span)
                 if ctx is not None else None)
    try:
      if router is not None:
        future = router.submit(
            features, model=model or None,
            priority=priority or 'interactive', request_id=request_id,
            trace=child_ctx)
      else:
        if model or (priority not in (None, 'interactive')):
          # A single-model plane has no router: a named model or a
          # non-default priority class is a contract the caller holds
          # that this server cannot honor — fail loudly, don't ignore.
          reply(
              404 if model else 400,
              {'error': 'this server fronts a single model with no '
                        'admission classes (no router configured)'},
              request_id=request_id)
          return
        future = self.server.batcher.submit(  # type: ignore[attr-defined]
            features, request_id=request_id, trace=child_ctx)
    except batching_lib.SheddedError as e:
      reply(503, {'error': str(e), 'shed': True},
            request_id=request_id,
            retry_after_secs=e.retry_after_secs)
      return
    except batching_lib.OverloadedError as e:
      reply(503, {'error': str(e)}, request_id=request_id,
            retry_after_secs=1.0)
      return
    except batching_lib.RequestError as e:
      reply(400, {'error': str(e)}, request_id=request_id)
      return
    request_id = future.request_id
    timeout = self.server.request_timeout_secs  # type: ignore[attr-defined]
    try:
      outputs = future.result(timeout=timeout)
    except TimeoutError as e:
      reply(504, {'error': str(e)}, request_id=request_id)
      return
    except batching_lib.ServingError as e:
      reply(500, {'error': str(e)}, request_id=request_id)
      return
    examples = next(iter(outputs.values())).shape[0] if outputs else 0
    reply(200, {
        'outputs': {k: np.asarray(v).tolist() for k, v in outputs.items()},
        'model_version': future.model_version,
        'examples': int(examples),
        'request_id': request_id,
    }, request_id=request_id)


class ServingServer:
  """Batcher/router + HTTP server lifecycle as one unit.

  ``port=0`` binds an ephemeral port (read ``.port``/``.url`` after
  :meth:`start`); the bind is loopback by default — serving beyond the
  host is an operator decision via ``host=``. ``close()`` is orderly:
  the listener stops, queued requests drain, the last response leaves
  before threads die.

  Single-model: ``ServingServer(predictor, **batcher_kwargs)`` (knobs:
  ``max_batch``, ``batch_deadline_ms``, ``max_queue``,
  ``reload_interval_secs``, ``quantize='int8'``/``'fp8'`` + its
  ``quant_parity_*`` band — see :class:`~tensor2robot_tpu.serving.
  batching.DynamicBatcher`). Multi-model: ``ServingServer(router=
  ModelRouter(...))`` — the router owns its batchers; batcher kwargs are
  rejected here (configure them on the router).
  """

  def __init__(self,
               predictor=None,
               port: int = 0,
               host: str = '127.0.0.1',
               request_timeout_secs: float = 30.0,
               timeseries_interval_secs: float = 10.0,
               router=None,
               **batcher_kwargs):
    if (predictor is None) == (router is None):
      raise ValueError('pass exactly one of predictor= or router=')
    if router is not None and batcher_kwargs:
      raise ValueError(
          f'batcher kwargs {sorted(batcher_kwargs)} are configured on the '
          'ModelRouter, not the server, in router mode')
    # Persistent compile cache first: bucket warmup is the serving
    # plane's restart cost, and a cache hit turns each bucket compile
    # into a deserialize (utils/compilation_cache.py has the one rule
    # for where the cache lives).
    compilation_cache.enable_compilation_cache()
    device_lib.announce('Serving plane')
    # Metrics history for /metricsz?history=1 and postmortem bundles
    # (0 disables; idempotent process-global recorder).
    from tensor2robot_tpu.observability import timeseries

    timeseries.maybe_start(timeseries_interval_secs or None)
    self._router = router
    self._batcher = (None if router is not None else
                     batching_lib.DynamicBatcher(predictor,
                                                 **batcher_kwargs))
    self._requested = (host, int(port))
    self._request_timeout_secs = request_timeout_secs
    self._httpd: Optional[http.server.ThreadingHTTPServer] = None
    self._thread: Optional[threading.Thread] = None

  @property
  def batcher(self) -> Optional[batching_lib.DynamicBatcher]:
    return self._batcher

  @property
  def router(self):
    return self._router

  @property
  def port(self) -> Optional[int]:
    return None if self._httpd is None else self._httpd.server_address[1]

  @property
  def url(self) -> Optional[str]:
    if self._httpd is None:
      return None
    host, port = self._httpd.server_address[:2]
    return f'http://{host}:{port}'

  def start(self) -> 'ServingServer':
    if self._httpd is not None:
      return self
    if self._router is not None:
      self._router.start()
    else:
      self._batcher.start()
    self._httpd = http.server.ThreadingHTTPServer(self._requested, _Handler)
    self._httpd.daemon_threads = True
    self._httpd.batcher = self._batcher  # type: ignore[attr-defined]
    self._httpd.router = self._router  # type: ignore[attr-defined]
    self._httpd.request_timeout_secs = (  # type: ignore[attr-defined]
        self._request_timeout_secs)
    # Fleet-timeline attribution: this replica's spans (ingress + its
    # batchers') carry one service label, so an assembled cross-process
    # trace names WHICH replica served (or refused) each hop — even when
    # several replicas share one test process and its span index.
    service = f'replica-{self.port}'
    self._httpd.service_label = service  # type: ignore[attr-defined]
    if self._router is not None:
      for name in self._router.models():
        self._router.batcher(name).service_label = service
    else:
      self._batcher.service_label = service
    self._thread = threading.Thread(
        target=self._httpd.serve_forever, kwargs={'poll_interval': 0.2},
        daemon=True, name='t2r-serving-http')
    self._thread.start()
    if self._router is not None:
      logging.info('Serving plane listening at %s (models=%s)',
                   self.url, self._router.models())
    else:
      logging.info(
          'Serving plane listening at %s (max_batch=%d, deadline=%.1fms, '
          'buckets=%s)', self.url, self._batcher._max_batch,  # pylint: disable=protected-access
          self._batcher._deadline_s * 1e3, list(self._batcher.buckets))  # pylint: disable=protected-access
    return self

  def close(self) -> None:
    if self._httpd is not None:
      self._httpd.shutdown()
      self._httpd.server_close()
      if self._thread is not None:
        self._thread.join(timeout=10.0)
      self._httpd = None
      self._thread = None
    if self._router is not None:
      self._router.close()
    else:
      self._batcher.close()

  def __enter__(self) -> 'ServingServer':
    return self.start()

  def __exit__(self, *exc) -> None:
    self.close()
