"""Driver entry points (__graft_entry__.py).

A caller that exports ``JAX_PLATFORMS=cpu`` gets the cpu backend on
every entry path, and ``dryrun_multichip`` fails outright when the
process has fewer devices than it was asked for — it never swaps in
another backend. The checks run in a subprocess because backend
selection is a process-global, one-shot decision.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout: int = 240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_PLATFORM_NAME", None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


def test_import_and_entry_stay_on_cpu():
    code = (
        "import elasticsearch_tpu\n"
        "import sys, os\n"
        "sys.path.insert(0, os.getcwd())\n"
        "import __graft_entry__ as g\n"
        "import jax\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n"
        "assert jax.default_backend() == 'cpu', jax.default_backend()\n"
        "assert all(d.platform == 'cpu' for d in jax.devices()), "
        "jax.devices()\n"
        "print('CPU-PIN-OK')\n")
    r = _run(code)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "CPU-PIN-OK" in r.stdout


def _dryrun(n_host: int, n_ask: int):
    code = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + "
        f"' --xla_force_host_platform_device_count={n_host}'\n"
        "sys.path.insert(0, os.getcwd())\n"
        "import __graft_entry__ as g\n"
        f"g.dryrun_multichip({n_ask})\n")
    r = _run(code, timeout=420)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert lines, (r.stdout, r.stderr)
    return r, json.loads(lines[-1])


def test_multichip_dryrun_emits_sectioned_json_on_cpu():
    """Every section records a status into the incrementally printed
    JSON line, and the run passes on 2 virtual devices."""
    r, payload = _dryrun(2, 2)
    assert payload["n_devices"] == 2
    sections = payload["sections"]
    assert "preflight" not in sections
    assert sections["backend_init"]["ok"] is True
    for sec in sections.values():
        assert "ok" in sec
    assert r.returncode == 0 and payload["ok"], (r.stdout, r.stderr)


def test_multichip_dryrun_fails_short_of_devices():
    r, payload = _dryrun(2, 4)
    assert r.returncode != 0
    assert payload["ok"] is False
    init = payload["sections"]["backend_init"]
    assert init["ok"] is False and "needs 4 devices" in init["error"]
    assert set(payload["sections"]) == {"backend_init"}
