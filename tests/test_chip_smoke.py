"""chip_smoke.py's contract, as far as a machine without a chip can show it:
the rehearsal passes and says so on every line, the real invocation refuses a
non-TPU platform before running a leg, the compile cache goes where the one
rule says — and bench.py refuses the same way."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
CACHE = os.path.join(REPO, ".jax_cache")


def _run(args, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    # a foreign cwd: the cache path must come from the checkout, not the cwd
    return subprocess.run([sys.executable, SMOKE, *args], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_passes_and_every_line_says_so(tmp_path):
    proc = _run(["--rehearsal"], tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = proc.stdout.splitlines()
    assert all(ln.startswith("REHEARSAL on cpu: ") for ln in lines), lines
    text = proc.stdout
    assert "leg A:" in text and "leg B:" in text and "leg C:" in text
    assert "WRONG" not in text
    assert f"compile cache: {CACHE}" in text      # process 1 of 2, see below
    last = json.loads(lines[-1].split(": ", 1)[1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"


def test_no_tpu_exits_nonzero_before_any_leg(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "leg" not in proc.stdout and '"ok"' not in proc.stdout


def test_compile_cache_rule(monkeypatch, tmp_path):
    import jax
    from windflow_tpu.runtime import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    # an entry's key takes the program's metadata (scope paths, source lines)
    # wherever the cache lives: a profile is read by them
    # (each operation located by its own source line, not by the call stack)
    metadata = [("jax_compilation_cache_include_metadata_in_key", True),
                ("jax_traceback_in_locations_limit", 1)]
    # set: JAX reads the variable itself; the code sets no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == metadata
    del updates[:]
    # unset: the checkout-relative path, the same one the rehearsal's
    # process printed from another cwd (process 2 of 2)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable_compile_cache() == CACHE
    assert updates == metadata + [("jax_compilation_cache_dir", CACHE)]


def test_bench_refuses_to_measure_without_a_tpu(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench
    with pytest.raises(SystemExit) as exc:
        bench.device_info()                   # the tests' backend is the CPU
    assert exc.value.code == 2
    with pytest.raises(KeyError, match="no peak figures"):
        bench._peaks()                        # unknown device: never a default
    src = open(bench.__file__).read()
    for gone in ("stale", "last_good", "healthcheck", "subprocess"):
        assert gone not in src, gone
