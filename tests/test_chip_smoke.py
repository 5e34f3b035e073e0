"""Bring-up contracts (ISSUE 21): what must hold for the system to start
on a chip at all, checked here as far as a CPU can check it.

* importing the package initialises no jax backend (a parent that only
  imports must not hold the chip its child needs);
* the compile cache is placed from outside: `JAX_COMPILATION_CACHE_DIR`
  when set, else `<checkout>/.jax_cache`;
* `on_tpu_backend()` raises on a broken backend instead of answering
  "not a TPU";
* `chip_smoke.py` refuses to pass without a TPU, and its `--rehearse`
  mode runs every phase on the CPU and says that is what it did.

Each subprocess pins `JAX_PLATFORMS=cpu`; none of them needs the 8-device
mesh of the suite.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout, **env):
    e = {k: v for k, v in os.environ.items()
         if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=timeout)


_PROBE = """
import json, jax
from jax._src import xla_bridge
import paddle_tpu
from paddle_tpu.core.compile_cache import use_compile_cache
paddle_tpu.seed(7)
cold = xla_bridge.backends_are_initialized()
print(json.dumps({"initialized_by_import": cold,
                  "returned": use_compile_cache(),
                  "configured": jax.config.jax_compilation_cache_dir,
                  "still_cold": not xla_bridge.backends_are_initialized()}))
"""


def test_import_initialises_no_backend_and_cache_lands_in_checkout():
    p = _run(["-c", _PROBE], 120)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["initialized_by_import"] is False
    assert got["still_cold"] is True
    want = os.path.join(REPO, ".jax_cache")
    assert got["returned"] == want and got["configured"] == want


def test_cache_helper_obeys_the_environment(tmp_path):
    outside = str(tmp_path / "placed_from_outside")
    p = _run(["-c", _PROBE], 120, JAX_COMPILATION_CACHE_DIR=outside)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    # jax read the variable itself; the helper set nothing else
    assert got["returned"] == outside and got["configured"] == outside


def test_on_tpu_backend_raises_on_a_broken_backend(monkeypatch):
    import jax

    from paddle_tpu.core import place

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        place.on_tpu_backend()


def test_chip_smoke_refuses_to_pass_without_a_tpu():
    p = _run(["chip_smoke.py"], 300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_chip_smoke_rehearsal_runs_every_phase():
    p = _run(["chip_smoke.py", "--rehearse"], 900)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    lines = p.stdout.strip().splitlines()
    assert lines[0].startswith("chip_smoke CPU REHEARSAL: platform=cpu")
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    for phase in ("train_phase", "serve_phase", "kernel_phase"):
        assert f"{phase} took" in p.stdout
    assert "FAILED" not in p.stdout
