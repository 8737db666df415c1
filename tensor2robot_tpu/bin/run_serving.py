"""Serving binary: batched multi-client action serving from export roots.

Loads the newest committed export version (waiting for the trainer's
first export when ``--restore-timeout-secs`` is set), warms every batch
bucket, and serves ``POST /v1/predict`` with dynamic cross-client
batching. Hot model swap is on by default: the reload poller follows the
export root's commit markers and swaps new versions in between dispatches
with zero dropped requests (a torn or broken export leaves the last-good
model serving).

Single model:
  python -m tensor2robot_tpu.bin.run_serving \
      --export_dir /models/m/export/latest_exporter_numpy \
      --port 8000 --max-batch 64 --batch-deadline-ms 5 \
      --metricsz-port 8001 --quantize int8

Multi-model (ModelRouter: N export roots, one device, LRU paging under
an HBM byte budget, priority-class admission control — best-effort
sheds with 503 + Retry-After before interactive is ever refused):
  python -m tensor2robot_tpu.bin.run_serving \
      --model grasp=/models/grasp/export --model eval=/models/eval/export \
      --hbm-budget-mb 4096 --shed-queue-fraction 0.25 --port 8000

Named models serve at ``POST /v1/models/<name>/predict``; the priority
class rides the ``X-Priority`` header. Replicas of this binary go behind
``tensor2robot_tpu.bin.run_balancer``.

SIGTERM/SIGINT drain: the HTTP listener stops, queued requests complete,
then the process exits 0 — a fleet scheduler can roll the serving tier
without failing client requests.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--export_dir', default=None,
                      help='Versioned export root (the trainer exporter '
                           'output, e.g. .../export/latest_exporter_numpy). '
                           'Single-model mode; exclusive with --model.')
  parser.add_argument('--model', action='append', default=[],
                      metavar='NAME=EXPORT_DIR',
                      help='Repeatable: serve EXPORT_DIR as model NAME '
                           'behind a ModelRouter (multi-model mode). '
                           'The first --model is the default model.')
  parser.add_argument('--hbm-budget-mb', type=float, default=None,
                      help='HBM byte budget for the router: models past '
                           'the budget are paged out LRU (host params + '
                           'compiled executables kept, so page-in is a '
                           'device_put, never a recompile). Unset: all '
                           'models stay resident.')
  parser.add_argument('--shed-queue-fraction', type=float, default=0.25,
                      help='Best-effort traffic sheds (503 + Retry-After) '
                           'once a model\'s queue passes this fraction of '
                           '--max-queue; interactive is only ever refused '
                           'by the hard bound itself.')
  parser.add_argument('--retry-after-secs', type=float, default=1.0,
                      help='Retry-After hint on shed responses.')
  parser.add_argument('--port', type=int, default=8000)
  parser.add_argument('--host', default='127.0.0.1',
                      help='Bind address; loopback by default — serving '
                           'beyond the host is an operator decision.')
  parser.add_argument('--max-batch', type=int, default=64,
                      help='Largest single device dispatch (the batch-64 '
                           'CEM optimum from BENCH_r05).')
  parser.add_argument('--batch-deadline-ms', type=float, default=5.0,
                      help='Max assembly wait: a batch dispatches at '
                           'max-batch examples or this deadline, '
                           'whichever first.')
  parser.add_argument('--max-queue', type=int, default=1024,
                      help='Queued-request bound; beyond it clients get '
                           '503 (backpressure, not unbounded latency).')
  parser.add_argument('--request-timeout-secs', type=float, default=30.0)
  parser.add_argument('--reload-interval-secs', type=float, default=10.0,
                      help='Export-root poll cadence for hot swap; '
                           '<= 0 disables reloading.')
  parser.add_argument('--restore-timeout-secs', type=float, default=0.0,
                      help='How long to wait for the FIRST export to '
                           'appear before giving up.')
  parser.add_argument('--metricsz-port', type=int, default=None,
                      help='Also serve the metrics registry (incl. the '
                           'serving report section) at /metricsz.')
  parser.add_argument('--quantize', choices=('off', 'int8', 'fp8'),
                      default='off',
                      help='Weight-only quantized serving: int8 (or fp8 '
                           'where jaxlib supports float8_e4m3fn) params '
                           'with per-output-channel scales, dequantized '
                           'inline on-chip. Parity-gated: a generation '
                           'outside the band serves full precision '
                           'instead (serving/quant_parity_rejects).')
  parser.add_argument('--quant-parity-atol', type=float, default=0.05,
                      help='Absolute term of the quantization parity '
                           'band checked on calibration batches before '
                           'a quantized generation may serve.')
  parser.add_argument('--quant-parity-rtol', type=float, default=0.05,
                      help='Relative term of the quantization parity '
                           'band (scaled by the full-precision output '
                           'magnitude).')
  parser.add_argument('--request-trace-sample', type=float, default=0.0,
                      help='Fraction of requests whose queued/assembled/'
                           'dispatched/returned lifecycle is recorded '
                           'into the flight ring (0 disables; request '
                           'IDs + latency exemplars are always on).')
  parser.add_argument('--postmortem-dir', default=None,
                      help='Directory for incident bundles: a reload '
                           'failure falling back to the last-good model '
                           'dumps flight events + metrics history here; '
                           '--slo / --anomaly-watch escalations write '
                           'LIVE bundles to the same place (render with '
                           'tools/postmortem.py).')
  parser.add_argument('--slo', action='store_true',
                      help='Run the SLO burn-rate engine over the '
                           'serving objectives (per-class availability + '
                           'interactive latency threshold): multi-window '
                           'burn alerts land in /statz, /metricsz, the '
                           'flight ring, and — with --postmortem-dir — '
                           'one rate-limited live forensics bundle.')
  parser.add_argument('--slo-latency-threshold-ms', type=float,
                      default=512.0,
                      help='Interactive latency SLO threshold (good '
                           'request = at or under this).')
  parser.add_argument('--anomaly-watch', action='store_true',
                      help='Watch serving time-series signals (request '
                           'p99, queue depth, shed rate, page-in time) '
                           'with robust median/MAD detectors; anomalies '
                           'flag flight events and escalate to live '
                           'bundles.')
  args = parser.parse_args(argv)
  logging.basicConfig(
      level=logging.INFO,
      format='%(asctime)s %(levelname)s %(name)s: %(message)s')

  from tensor2robot_tpu.observability import metricsz
  from tensor2robot_tpu.predictors import ExportedModelPredictor
  from tensor2robot_tpu.serving import ModelRouter, ServingServer

  if bool(args.export_dir) == bool(args.model):
    parser.error('pass exactly one of --export_dir or --model NAME=DIR '
                 '(repeatable)')

  def load_predictor(export_dir):
    predictor = ExportedModelPredictor(
        export_dir=export_dir, timeout=args.restore_timeout_secs)
    if not predictor.restore():
      logging.error('No committed export appeared under %r within %.1fs.',
                    export_dir, args.restore_timeout_secs)
      return None
    return predictor

  reload_interval = (args.reload_interval_secs
                     if args.reload_interval_secs > 0 else None)
  batcher_kwargs = dict(
      max_batch=args.max_batch,
      batch_deadline_ms=args.batch_deadline_ms,
      max_queue=args.max_queue,
      reload_interval_secs=reload_interval,
      quantize=args.quantize,
      quant_parity_atol=args.quant_parity_atol,
      quant_parity_rtol=args.quant_parity_rtol,
      request_trace_sample=args.request_trace_sample,
      postmortem_dir=args.postmortem_dir)
  server_kwargs = dict(
      port=args.port,
      host=args.host,
      request_timeout_secs=args.request_timeout_secs)

  if args.model:
    predictors = {}
    default_model = None
    for spec in args.model:
      name, sep, export_dir = spec.partition('=')
      if not sep or not name or not export_dir:
        parser.error(f'--model {spec!r} is not NAME=EXPORT_DIR')
      predictor = load_predictor(export_dir)
      if predictor is None:
        return 1
      predictors[name] = predictor
      default_model = default_model or name
    router = ModelRouter(
        predictors,
        hbm_budget_bytes=(None if args.hbm_budget_mb is None
                          else int(args.hbm_budget_mb * 1e6)),
        default_model=default_model,
        shed_queue_fraction=args.shed_queue_fraction,
        retry_after_secs=args.retry_after_secs,
        **batcher_kwargs)
    server = ServingServer(router=router, **server_kwargs)
  else:
    predictor = load_predictor(args.export_dir)
    if predictor is None:
      return 1
    server = ServingServer(predictor, **server_kwargs, **batcher_kwargs)

  stop = threading.Event()

  def handle_signal(signum, frame):
    del frame
    logging.info('Received signal %d; draining and shutting down.', signum)
    stop.set()

  previous = {sig: signal.signal(sig, handle_signal)
              for sig in (signal.SIGTERM, signal.SIGINT)}
  engine = None
  watch = None
  try:
    with server:
      metricsz.maybe_start(args.metricsz_port)
      if args.slo:
        from tensor2robot_tpu.observability import slo as slo_lib

        models = (server.router.models()
                  if server.router is not None else [])
        engine = slo_lib.SLOEngine(
            slo_lib.serving_objectives(
                models=models,
                latency_threshold_ms=args.slo_latency_threshold_ms),
            postmortem_dir=args.postmortem_dir).start()
      if args.anomaly_watch:
        from tensor2robot_tpu.observability import anomaly as anomaly_lib

        watch = anomaly_lib.AnomalyWatch(
            postmortem_dir=args.postmortem_dir).start()
      if server.router is not None:
        logging.info('Serving models %s at %s',
                     server.router.versions(), server.url)
      else:
        logging.info('Serving model version %d at %s',
                     server.batcher.model_version, server.url)
      stop.wait()
  finally:
    if watch is not None:
      watch.stop()
    if engine is not None:
      engine.stop()
    for sig, handler in previous.items():
      signal.signal(sig, handler)
  return 0


if __name__ == '__main__':
  sys.exit(main())
