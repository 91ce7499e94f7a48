"""Launch surface: where the compile cache goes, which chip a utilization
is quoted against, and the chip smoke's CPU rehearsal."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


# -- compile cache placed from outside ---------------------------------------

def _cache_probe(env_value: str | None, trace_dir: str | None = None) -> dict:
    """Run the helper in a FRESH interpreter (jax reads the variable at
    import, and the helper mutates process-wide state)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("APEX_TRACE_DIR", None)
    if trace_dir is not None:
        env["APEX_TRACE_DIR"] = trace_dir
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = (
        "import json, os\n"
        "from apex_tpu.utils.compile_cache import ensure_compile_cache\n"
        "path = ensure_compile_cache()\n"
        "import jax\n"
        "print(json.dumps({'path': path,\n"
        "    'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
        "    'jax': jax.config.jax_compilation_cache_dir,\n"
        "    'metadata_in_key':\n"
        "    jax.config.jax_compilation_cache_include_metadata_in_key}))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_compile_cache_honours_the_operators_directory(tmp_path):
    got = _cache_probe(str(tmp_path / "elsewhere"))
    assert got["path"] == got["env"] == got["jax"] \
        == str(tmp_path / "elsewhere")
    # nothing else configured: an edit to traced source costs no compile
    assert got["metadata_in_key"] is False


def test_a_traced_run_counts_metadata_in_the_cache_key(tmp_path):
    # a cache from before a trace scope moved must not serve its programs
    # to the run that reads the scopes
    got = _cache_probe(str(tmp_path / "elsewhere"),
                       trace_dir=str(tmp_path / "ring"))
    assert got["path"] == got["jax"] == str(tmp_path / "elsewhere")
    assert got["metadata_in_key"] is True


def test_compile_cache_defaults_to_a_fixed_in_tree_path():
    got = _cache_probe(None)
    want = str(REPO / ".jax_cache")
    # fixed: derived from the checkout, nothing per-process or per-run
    assert got["path"] == want
    assert got["env"] == want          # exported: spawned workers share it
    assert got["jax"] == want
    assert got["metadata_in_key"] is False
    assert not re.search(r"tmp|\d{3,}", os.path.relpath(want, REPO))
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# -- peaks keyed by device_kind ----------------------------------------------

def test_peak_lookup_raises_on_an_unknown_device():
    from benchmark import costs

    assert costs.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9000"):
        costs.peaks_for("TPU v9000")
    with pytest.raises(KeyError, match="cpu"):    # what a CPU run reports
        costs.peaks_for("cpu")


# -- chip_smoke.py CPU rehearsal ---------------------------------------------

def _dry_run(tmp_path, *extra: str) -> tuple[list[str], dict]:
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--dry-run", *extra],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


def _assert_labelled_cpu(lines: list[str], final: dict, tmp_path) -> None:
    assert final["ok"] is True and final["dry_run"] is True
    assert final["device"]["platform"] == "cpu"
    stage_lines = [ln for ln in lines if ln.startswith("chip_smoke[")]
    assert stage_lines
    # no line of a rehearsal can be read as a chip pass
    assert all("DRY RUN on cpu" in ln for ln in stage_lines)
    assert any("platform=cpu" in ln for ln in stage_lines)
    # the cache went where the operator said, and nowhere in-tree
    assert any(f"compile_cache={tmp_path / 'cache'}" in ln
               for ln in stage_lines)
    assert any((tmp_path / "cache").iterdir())


def test_chip_smoke_dry_run_passes_and_says_cpu(tmp_path):
    """The dp=4 host-fed stage (actors over shm -> staged ingest ->
    sharded fused step) and the kernel stage, at toy sizes on the virtual
    CPU mesh; the full five-stage rehearsal is the slow twin below."""
    lines, final = _dry_run(tmp_path, "--stages",
                            "host_fed_dp4,gather_kernel")
    _assert_labelled_cpu(lines, final, tmp_path)
    assert final["partial"] == ["host_fed_dp4", "gather_kernel"]


@pytest.mark.slow
def test_chip_smoke_full_dry_run(tmp_path):
    lines, final = _dry_run(tmp_path)
    _assert_labelled_cpu(lines, final, tmp_path)
    assert "partial" not in final
    summary = next(json.loads(ln[len("chip_smoke summary "):])
                   for ln in lines if ln.startswith("chip_smoke summary "))
    assert summary["stages"] == ["host_fed", "fused", "host_fed_dp4",
                                 "fused_dp4", "gather_kernel"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_chip_smoke_refuses_to_pass_without_a_chip(tmp_path):
    """Plain invocation on a machine whose JAX finds no TPU: non-zero
    exit and no result line."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--stages",
         "gather_kernel"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
