"""One run of one cell: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Everything particular to a cell is data found by name from
``BENCHMARK.json``: the configuration (``benchmark/configs/<config>.json``,
its plain reference ``benchmark/reference/<reference>.py``), the traffic
mix (``benchmark/workloads/<traffic>.json``), the kind of run that the
mix names (``benchmark/kinds/<kind>.py``: set-up, window and check) and
one reader a per-layer metric (``benchmark/metrics/<metric>.py``). This
file knows none of them: it finds the cell, keeps the caches inside the
checkout, looks for the chips, reads the trace and prints the result.

``--rehearse`` runs the same code at the configuration's ``rehearsal``
sizes on whatever backend jax has, prints the compared numbers and NO
metric of the device. Without it, a run that finds no TPU fails.
``--stand-in`` reads the controls and planted faults that the limits
were set from (PERF.md, section 4). The driver passes neither.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # as near the process's start as Python gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, 'benchmark')
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

CACHE = os.path.join(ROOT, '.bench_cache')


def place_caches() -> None:
  """Whatever the program builds or caches goes inside the checkout, at a
  fixed path there, so that two checkouts share nothing and only the first
  run of a cell in a checkout compiles. Called before jax is imported: the
  program takes jax's own variable for its compile cache and sets no other
  directory; its native codec would build into /tmp/t2r_native. No size
  limit: the machine's own (192 MiB on the chip tool's machines) is less
  than one cell's programs, and a cache that evicts them compiles in every
  run (PERF.md, Findings)."""
  os.environ['JAX_COMPILATION_CACHE_DIR'] = os.path.join(CACHE, 'jax')
  os.environ['JAX_COMPILATION_CACHE_MAX_SIZE'] = '-1'
  os.environ['T2R_NATIVE_CACHE'] = os.path.join(CACHE, 'native')


def log(message: str) -> None:
  print(message, flush=True)


def watch_host_memory(step_gib: float = 4.0):
  """A line whenever the process's resident set has grown by another
  ``step_gib``: a run that the machine ends for memory says where.
  Returns the call that ends the watch, before the result is printed."""
  import threading

  done = threading.Event()

  def loop(last=0.0):
    while not done.wait(5):
      gib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
      if gib >= last + step_gib:
        last = gib
        log(f'host peak rss {gib:.1f} GiB at '
            f'{time.perf_counter() - _T0:.0f} s')

  thread = threading.Thread(target=loop, daemon=True)
  thread.start()

  def stop():
    done.set()
    thread.join()

  return stop


def merge(base: dict, over: dict) -> dict:
  out = dict(base)
  for key, value in over.items():
    if isinstance(value, dict) and isinstance(out.get(key), dict):
      out[key] = merge(out[key], value)
    else:
      out[key] = value
  return out


def load_cell(workload: str, rehearse: bool, bench_file: str = None):
  """The cell's entries and files, by name from ``BENCHMARK.json`` (or,
  for the benchmark's own tests, from a file of the same shape)."""
  with open(bench_file or os.path.join(ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  cells = {w['name']: w for w in bench['workloads']}
  if workload not in cells:
    raise SystemExit(f'unknown workload {workload!r}; have {sorted(cells)}')
  cell = cells[workload]
  configs = {c['name']: c for c in bench['configs']}
  with open(os.path.join(ROOT, configs[cell['config']]['file'])) as f:
    cfg = json.load(f)
  with open(os.path.join(HERE, 'workloads', f'{cell["traffic"]}.json')) as f:
    mix = json.load(f)
  if rehearse:
    cfg = merge(cfg, cfg.get('rehearsal', {}))
    mix = merge(mix, mix.get('rehearsal', {}))
  return bench, cell, cfg, mix


def cell_metrics(bench: dict, section: str, cell_name: str):
  """The cell's metrics of one section: those that list it, or none."""
  return [m for m in bench[section]
          if 'workloads' not in m or cell_name in m['workloads']]


def load_reader(name: str):
  path = os.path.join(HERE, 'metrics', f'{name}.py')
  spec = importlib.util.spec_from_file_location(
      'bench_metric_' + name.replace('.', '_').replace('-', '_'), path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module.read


class Job:
  """What a kind's ``run(job)`` is handed: the cell's data, the run's
  arguments, a directory that is removed at the end, and the look for
  the chips."""

  def __init__(self, args, stand_ins, cell, cfg, mix, tmp):
    self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
    self.rehearse, self.stand_ins = args.rehearse, stand_ins
    self.cell, self.cfg, self.mix, self.tmp = cell, cfg, mix, tmp
    self.t0 = _T0
    self.log = log
    self.platform = self.device_kind = self.peaks = None
    self._on_exit = []

  def on_exit(self, call) -> None:
    """``call()`` runs when the run ends, however it ends."""
    self._on_exit.append(call)

  def close(self) -> None:
    while self._on_exit:
      self._on_exit.pop()()

  def chips(self):
    """jax's devices, once the kind has imported what it needs. A run
    that is no rehearsal and finds no TPU, fewer chips than the cell asks
    for, or a chip with no row in ``peaks.json`` ends here with no
    result."""
    import jax

    devices = jax.local_devices()
    self.platform = devices[0].platform
    self.device_kind = devices[0].device_kind
    if self.rehearse:
      return devices
    if self.platform != 'tpu' or len(devices) < self.cell['chips']:
      raise SystemExit(f'need {self.cell["chips"]} TPU chip(s); jax has '
                       f'{len(devices)} {self.platform} device(s)')
    with open(os.path.join(HERE, 'peaks.json')) as f:
      peaks_table = json.load(f)
    if self.device_kind not in peaks_table:
      raise SystemExit(f'no peaks for device kind {self.device_kind!r}')
    self.peaks = peaks_table[self.device_kind]
    return devices


def main(argv=None, bench_file=None) -> int:
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, required=True)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  parser.add_argument('--rehearse', action='store_true',
                      help='tiny sizes, any backend, no device metric')
  parser.add_argument('--stand-in', default='',
                      help='comma list of the kind\'s stand-ins: after the '
                           'check (no window) put the reference, one '
                           'precision down or with a planted fault, in the '
                           'program\'s place; each must come out not correct')
  args = parser.parse_args(argv)

  stand_ins = [name for name in args.stand_in.split(',') if name]
  bench, cell, cfg, mix = load_cell(args.workload, args.rehearse, bench_file)
  place_caches()
  stop_watch = watch_host_memory()
  kind = importlib.import_module(f'benchmark.kinds.{mix["kind"]}')
  job = Job(args, stand_ins, cell, cfg, mix,
            tempfile.mkdtemp(prefix='bench_'))
  try:
    out = kind.run(job)

    # ----------------------------------------------------- the metrics
    values = {}
    breakdown = None
    device = {'platform': job.platform, 'kind': job.device_kind,
              'count': cell['chips'],
              'memory_peak_bytes': out['memory_peak_bytes']}
    if args.rehearse or stand_ins:
      pass  # never a device metric from a rehearsal or a control
    elif args.trace:
      from benchmark.lib import trace as trace_lib

      xplane = trace_lib.find_xplane(out['trace_dir'])
      profile = trace_lib.load(xplane)
      log(f'trace: {os.path.getsize(xplane)} bytes; its clock\'s start is '
          f'{"known" if trace_lib.profile_start_ns(profile) else "not known"}')
      for line in trace_lib.summary(profile):
        if line.startswith(trace_lib.DEVICE_PLANE_PREFIX):
          log('trace ' + line)
      ctx = dict(out['context'], profile=profile, peaks=job.peaks,
                 chips=cell['chips'], cache={})
      for metric in cell_metrics(bench, 'per_layer', cell['name']):
        value = load_reader(metric['name'])(ctx)
        if value is not None:
          values[metric['name']] = {'value': value, 'unit': metric['unit']}
      device['busy_s'], device['window_s'], breakdown = kind.device_times(ctx)
    else:
      for metric in cell_metrics(bench, 'end_to_end', cell['name']):
        value = out['end_to_end'].get(metric['name'])
        if value is not None:
          values[metric['name']] = {'value': value, 'unit': metric['unit']}

    stop_watch()
    result = {
        'correct': bool(out['correct']),
        'attempted': out['attempted'], 'failed': out['failed'],
        'metrics': values, 'device': device,
    }
    if breakdown is not None:
      result['breakdown'] = breakdown
    if args.rehearse:
      result['rehearsal'] = True

    def brief(numbers):
      return {k: {'value': v['value'], 'limit': v['limit'],
                  **({'at': v['at']} if v.get('at') else {})}
              for k, v in numbers.items()}

    def say(numbers, verdict, who=''):
      for name, entry in numbers.items():
        print(f'{who}compared {name}: {entry["value"]:.6g} limit '
              f'{entry["limit"]}'
              + (f' at {entry["at"]}' if entry.get('at') else ''),
              file=sys.stderr)
      print(f'{who}correct: {verdict}', file=sys.stderr, flush=True)

    if out['stand_ins']:
      result['stand_ins'] = {
          name: {'correct': verdict, 'compared': brief(numbers)}
          for name, (numbers, verdict) in out['stand_ins'].items()}
      for name, (numbers, verdict) in out['stand_ins'].items():
        say(numbers, verdict, f'stand-in {name} ')
    result['compared'] = brief(out['compared'])
    say(out['compared'], out['correct'])
    print(json.dumps(result), flush=True)
    return 0
  finally:
    job.close()
    shutil.rmtree(job.tmp, ignore_errors=True)


if __name__ == '__main__':
  sys.exit(main())
