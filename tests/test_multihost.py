"""Multi-host feeding tests: 2 real jax.distributed processes on CPU.

Validates the per-host data contract (VERDICT #8): each process feeds its
OWN shard — per-process file sharding in the pipeline plus
``jax.make_array_from_process_local_data`` in ``shard_batch`` — and the
assembled global batch contains every host's data exactly once (the
reference gets this from TPUEstimator's per-host ``input_fn``,
``utils/tfdata.py:43-66``).
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent("""
    import os, sys, json
    import numpy as np

    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'

    import jax

    coordinator, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=2, process_id=pid,
                               local_device_ids=[0, 1])
    assert jax.process_count() == 2
    assert jax.device_count() == 4

    from tensor2robot_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.create_mesh(data=4)

    # Each host contributes a DISTINCT process-local shard: host p feeds
    # the constant p+1 on its slice of the global batch of 8.
    local = np.full((4, 3), pid + 1, np.float32)
    global_batch = mesh_lib.shard_batch({'x': local}, mesh)['x']
    assert global_batch.shape == (8, 3), global_batch.shape

    # Sum over the GLOBAL batch: 4*3*(1) + 4*3*(2) = 36 iff both hosts'
    # shards are present exactly once (duplicated host-global feeding
    # would give 24 or 48).
    import jax.numpy as jnp
    total = jax.jit(
        lambda x: jnp.sum(x),
        in_shardings=(mesh_lib.batch_sharding(mesh),),
        out_shardings=None)(global_batch)
    assert float(total) == 36.0, float(total)

    # Per-process file sharding: 4 files -> each process sees 2, disjoint.
    from tensor2robot_tpu.data import pipeline
    files = ['f0', 'f1', 'f2', 'f3']
    mine, by_file = pipeline.shard_filenames_for_process(files)
    assert by_file and len(mine) == 2, (mine, by_file)
    print(json.dumps({'pid': pid, 'files': mine, 'total': float(total)}))
""")


@pytest.mark.slow
@pytest.mark.skip(
    reason="this jaxlib's CPU backend cannot run cross-process XLA "
           'programs — the global-mesh drill dies with "Multiprocess '
           'computations aren\'t implemented" (ROADMAP carried '
           'follow-up: re-point at a real pod or a newer jaxlib; the '
           'control-plane equivalents live in '
           'tests/test_distributed_resilience.py)')
def test_two_process_distinct_shards(tmp_path):
  port = socket.socket()
  port.bind(('127.0.0.1', 0))
  coordinator = f'127.0.0.1:{port.getsockname()[1]}'
  port.close()

  env = dict(os.environ)
  env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
  env.pop('JAX_PLATFORMS', None)
  env.pop('XLA_FLAGS', None)
  procs = [
      subprocess.Popen(
          [sys.executable, '-c', _WORKER, coordinator, str(pid)],
          stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
          cwd=str(tmp_path))
      for pid in (0, 1)
  ]
  outputs = []
  for proc in procs:
    out, _ = proc.communicate(timeout=300)
    outputs.append(out.decode())
  for proc, out in zip(procs, outputs):
    assert proc.returncode == 0, out

  import json

  results = [json.loads(out.strip().splitlines()[-1]) for out in outputs]
  files = {r['pid']: set(r['files']) for r in results}
  assert files[0].isdisjoint(files[1])
  assert files[0] | files[1] == {'f0', 'f1', 'f2', 'f3'}
  assert all(r['total'] == 36.0 for r in results)
