"""Records the small trace that ``test_trace.py`` reduces.

Run on the chip: ``python3 benchmark/tests/record_small_trace.py <dir>``.
Three calls of a small jitted program (two matrix products and a sum),
each under the benchmark's feed span, with a sleep between them so that
the device has idle gaps the reduction must find. Prints what the test
asserts: how many program events, and the host's view of the timings.
"""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> None:
  import jax
  import jax.numpy as jnp

  from benchmark.lib import trace, window

  @jax.jit
  def small_step(x):
    return jnp.sum(jnp.tanh(x @ x) @ x)

  x = jnp.ones((1024, 1024), jnp.bfloat16)
  small_step(x).block_until_ready()
  trace_dir = os.path.join(out_dir, 'raw')
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  options.host_tracer_level = 2
  options.enable_hlo_proto = False
  jax.profiler.start_trace(trace_dir, profiler_options=options)
  for _ in range(3):
    with jax.profiler.TraceAnnotation(window.FEED_SPAN):
      time.sleep(0.01)
    with jax.profiler.TraceAnnotation(window.CALLBACK_SPAN):
      small_step(x).block_until_ready()
  jax.profiler.stop_trace()
  path = trace.find_xplane(trace_dir)
  shutil.copy(path, os.path.join(out_dir, 'small_trace.xplane.pb'))
  profile = trace.load(path)
  print('\n'.join(trace.summary(profile)))
  reduced = trace.reduce(profile, [window.FEED_SPAN, window.CALLBACK_SPAN])
  for d in reduced['devices']:
    print(json.dumps({'device': d['name'], 'busy_ns': d['busy_ns'],
                      'by_module': d['by_module'],
                      'gaps': len(d['idle_gaps_ns']),
                      'ops': sorted(d['by_op_ns'].items())}))
  print(json.dumps({k: len(v) for k, v in reduced['host_spans'].items()}))
  print('bytes', os.path.getsize(path))


if __name__ == '__main__':
  main(sys.argv[1])
