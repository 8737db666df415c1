"""chip_smoke.py's parent logic, with stub children.

No trainer and no server is started here (the full tiny-size rehearsal,
``python chip_smoke.py --rehearse``, is run by hand before a chip run):
the stubs below write what the real children would leave behind — a
``run_report.json``, a committed checkpoint and export, a ``/statz`` —
and misbehave on request. What is tested is what the parent does with
it: it stays off jax, it ends with the contract's line and nothing more
on success, and a child that ran elsewhere, failed, hung or served
through the degraded executor makes it exit non-zero without that line.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_STUB_TRAINER = textwrap.dedent('''
    import json, os, re, sys, time
    mode = os.environ['STUB_MODE']
    bindings = ' '.join(sys.argv)
    model_dir = re.search(r"model_dir = '([^']+)'", bindings).group(1)
    steps = int(re.search(r'max_train_steps = (\\d+)', bindings).group(1))
    with open(os.path.join(os.environ['STUB_DIR'], 'trainer.pid'), 'w') as f:
      f.write(str(os.getpid()))
    if mode == 'train_fails':
      sys.exit(3)
    if mode == 'train_hangs':
      time.sleep(600)
    def write(path, doc):
      os.makedirs(os.path.dirname(path), exist_ok=True)
      with open(path, 'w') as f:
        json.dump(doc, f)
    device = {'platform': 'cpu' if mode == 'train_on_cpu' else 'tpu',
              'kind': 'TPU v5 lite', 'count': 1, 'jax': 'stub'}
    write(os.path.join(model_dir, 'run_report.json'), {
        'device': device,
        'result': {'loss': 0.69},
        'compile': {'dir': None},
        'programs': {'train/step': {'custom_calls': 0}},
        'metrics': {'trainer/steps': steps, 'trainer/dispatches': steps // 8,
                    'trainer/examples': steps * 32,
                    'trainer/prefetch/place_stage': 1.0,
                    'kernels/refused': 1 if mode == 'kernel_refused' else 0},
    })
    write(os.path.join(model_dir, 'checkpoints', f'ckpt_{steps}',
                       'commit.json'), {})
    export = os.path.join(model_dir, 'export', 'latest_exporter_numpy', '17')
    write(os.path.join(export, 'export_commit.json'), {})
    write(os.path.join(export, 'export_meta.json'),
          {'self_contained_serving_fn': True, 'global_step': steps})
    write(os.path.join(export, 'assets.extra', 't2r_assets.json'), {
        'feature_spec': {
            'state/image': {'shape': [4, 4, 3], 'dtype': 'uint8'},
            'action/world_vector': {'shape': [3], 'dtype': 'float32'}}})
''')

_STUB_SERVER = textwrap.dedent('''
    import http.server, json, os, signal, sys
    mode = os.environ['STUB_MODE']
    if mode == 'server_fails':
      sys.exit(3)
    port = int(sys.argv[sys.argv.index('--port') + 1])
    seen = {'requests': 0, 'actions': 0}

    class Handler(http.server.BaseHTTPRequestHandler):
      def log_message(self, *args):
        pass
      def reply(self, doc):
        body = json.dumps(doc).encode()
        self.send_response(200)
        self.send_header('Content-Length', str(len(body)))
        self.end_headers()
        self.wfile.write(body)
      def do_GET(self):
        if self.path == '/healthz':
          return self.reply({'status': 'ok'})
        self.reply(dict(
            seen, request_errors=0, buckets=[1, 2], bucket_compiles=2,
            compile={'dir': None},
            executor=('PredictCallableExecutor' if mode == 'degraded_executor'
                      else 'JitBucketExecutor'),
            device={'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}))
      def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers['Content-Length'])))
        n = len(body['features']['state/image'])
        assert [len(body['features']['state/image'][0]),
                len(body['features']['action/world_vector'][0])] == [4, 3]
        seen['requests'] += 1
        seen['actions'] += n
        self.reply({'outputs': {'q_predicted': [0.5] * n}, 'examples': n})

    server = http.server.HTTPServer(('127.0.0.1', port), Handler)
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
    server.serve_forever()
''')

_STUB_MULTICHIP = textwrap.dedent('''
    import json
    print('INFO: a log line')
    print(json.dumps({'device': {'platform': 'tpu', 'kind': 'TPU v5 lite',
                                 'count': 4}, 'comparisons': {}}))
''')

# Runs chip_smoke.main() with the stubs in place of its children, in a
# process of its own: "the parent ends with jax not imported" is a fact
# about a process, and pytest's own has long since imported it.
_DRIVER = textwrap.dedent('''
    import sys
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    stubs = sys.argv[2]
    chip_smoke.TRAIN_CMD = [sys.executable, stubs + '/trainer.py']
    chip_smoke.SERVE_CMD = [sys.executable, stubs + '/server.py']
    chip_smoke.MULTICHIP_CMD = [sys.executable, stubs + '/multichip.py']
    chip_smoke.TRAIN_LIMIT_S = 2.0
    rc = chip_smoke.main(['--out', stubs + '/out'] + sys.argv[3:])
    assert 'jax' not in sys.modules and 'numpy' not in sys.modules
    sys.exit(rc)
''')

_CONTRACT_LINE = ('{"ok": true, "device": {"platform": "tpu", '
                  '"kind": "TPU v5 lite", "count": %d}}')


def _run(tmp_path, mode, *args):
  for name, source in (('trainer.py', _STUB_TRAINER),
                       ('server.py', _STUB_SERVER),
                       ('multichip.py', _STUB_MULTICHIP)):
    (tmp_path / name).write_text(source)
  env = dict(os.environ, STUB_MODE=mode, STUB_DIR=str(tmp_path))
  return subprocess.run(
      [sys.executable, '-c', _DRIVER, REPO, str(tmp_path), *args],
      capture_output=True, text=True, timeout=120, env=env)


def test_success_ends_with_the_contract_line_and_no_jax(tmp_path):
  proc = _run(tmp_path, 'ok')
  assert proc.returncode == 0, proc.stderr[-2000:]
  lines = proc.stdout.splitlines()
  assert lines[-1] == _CONTRACT_LINE % 1
  phases = [json.loads(line) for line in lines[:-1]]
  assert [p['phase'] for p in phases] == ['native', 'train', 'serve']
  assert all(p['ok'] for p in phases)
  assert phases[2]['observations']['requests'] == 4
  assert phases[2]['observations']['drain_rc'] == 0
  # The run directory is not left behind.
  assert not (tmp_path / 'out' / 'model').exists()


def test_multichip_runs_that_phase_and_no_other(tmp_path):
  proc = _run(tmp_path, 'ok', '--multichip')
  assert proc.returncode == 0, proc.stderr[-2000:]
  lines = proc.stdout.splitlines()
  assert lines[-1] == _CONTRACT_LINE % 4
  assert [json.loads(line)['phase'] for line in lines[:-1]] == ['multichip']
  assert not (tmp_path / 'trainer.pid').exists()


@pytest.mark.parametrize('mode, said', [
    ('train_on_cpu', "not on a 'tpu' platform"),
    ('train_fails', 'the trainer exited 3'),
    ('train_hangs', 'overran its 2s limit'),
    ('kernel_refused', 'degraded paths were taken'),
    ('server_fails', 'the server exited 3 before /healthz'),
    ('degraded_executor', 'not the jitted bucket executor'),
])
def test_a_bad_child_fails_the_script(tmp_path, mode, said):
  proc = _run(tmp_path, mode)
  assert proc.returncode != 0
  assert '"ok": true, "device"' not in proc.stdout
  assert said in proc.stderr, proc.stderr[-2000:]
  # No child outlives the script, the hung one included.
  pid = int((tmp_path / 'trainer.pid').read_text())
  with pytest.raises(ProcessLookupError):
    os.kill(pid, 0)


def test_rehearsal_can_never_report_a_tpu(tmp_path):
  # Children that claim a tpu while the run was told to rehearse on the
  # CPU: the parent refuses, whatever they say.
  proc = _run(tmp_path, 'ok', '--rehearse')
  assert proc.returncode != 0
  assert '"ok": true, "device"' not in proc.stdout
  assert "not on a 'cpu' platform" in proc.stderr
