"""launch.env compile-cache placement and chip_smoke.py's refusal to run
without a TPU.  Each case runs a fresh interpreter: jax reads
``JAX_COMPILATION_CACHE_DIR`` once, at import."""
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE = ('import json, jax; from repro.launch import env; '
                'st = env.configure(); '
                'print(json.dumps([st["compile_cache_dir"], '
                'jax.config.jax_compilation_cache_dir]))')


def _run(args, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.path.join(_ROOT, 'src')
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=_ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


def test_compile_cache_defaults_to_fixed_checkout_path():
    r = _run(['-c', _PRINT_CACHE], drop=('JAX_COMPILATION_CACHE_DIR',))
    assert r.returncode == 0, r.stderr
    recorded, live = json.loads(r.stdout.strip().splitlines()[-1])
    assert recorded == live == os.path.join(_ROOT, '.jax_cache')


def test_compile_cache_follows_env_var(tmp_path):
    outside = str(tmp_path / 'cache')
    r = _run(['-c', _PRINT_CACHE],
             env_extra={'JAX_COMPILATION_CACHE_DIR': outside})
    assert r.returncode == 0, r.stderr
    recorded, live = json.loads(r.stdout.strip().splitlines()[-1])
    assert recorded == live == outside


def test_chip_smoke_refuses_cpu():
    r = _run([os.path.join(_ROOT, 'chip_smoke.py')])
    assert r.returncode != 0
    assert 'no TPU' in r.stderr
    assert '"ok": true' not in r.stdout
