"""chip_smoke.py: refuses to run without a TPU, and its phases' checks
pass on the CPU at a small size (kernels interpreted) — so a chip call
fails only on what the chip itself does differently."""
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.device import interpret, use_compile_cache
from repro.service.cluster import ClusterSpec, LocalCluster


def _cell_processes() -> int:
    out = subprocess.run(["ps", "-eo", "args"], capture_output=True, text=True)
    return sum("repro.service.cell" in line for line in out.stdout.splitlines())


@pytest.mark.timeout(120)
def test_smoke_refuses_a_host_without_tpu(capsys):
    before = _cell_processes()
    prev = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
            chip_smoke.main(["--events", "2000"])
    finally:  # main() pointed the persistent cache at the repo
        jax.config.update("jax_compilation_cache_dir", prev)
    assert '"ok"' not in capsys.readouterr().out
    assert _cell_processes() == before  # the cluster was stopped


@pytest.mark.timeout(300)
def test_smoke_phases_pass_on_cpu(tmp_path, capsys):
    events, store = chip_smoke.phase_build(6000, seed=3)
    chip_smoke.phase_retrieval(events, store)
    chip_smoke.phase_device_fold(store)
    chip_smoke.phase_fused(store)
    spec = ClusterSpec(n_cells=3, r=2, backend="file", root=str(tmp_path))
    with LocalCluster(spec, mode="subprocess") as cluster:
        chip_smoke.phase_served(cluster, 1500, seed=3)
    chip_smoke.phase_sharded(3000, seed=3, chips=len(jax.devices()))
    out = capsys.readouterr().out
    for n in (2, 3, 4, 5, 6, "4-chip"):
        assert f"phase {n} " in out, out


def test_interpret_mode_follows_the_backend():
    assert interpret() == (jax.default_backend() != "tpu")


def test_compile_cache_dir(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert use_compile_cache(tmp_path) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache(tmp_path) == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_storage_cells_never_import_jax():
    code = ("import sys, repro.service.cell, repro.service.cluster; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
