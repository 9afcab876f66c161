"""Guards that keep the served path honest about where it runs: no
silent interpretation or host fallback off the CPU, no co-located shard
slabs on an accelerator, a compile cache placed from outside or at one
fixed path, peaks only for known chips, and a chip smoke test that
refuses the CPU."""
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from repro.core import DedupConfig, LSHConfig, ModelStore, StoreConfig
from repro.kernels import ops
from repro.launch import cache, mesh
from repro.roofline import analysis
from repro.serving.device_pool import DevicePagePool
from repro.serving.engine import StorageModel, WeightServer

ROOT = os.path.join(os.path.dirname(__file__), "..")


# --------------------------------------------------------- kernel modes --
@pytest.mark.parametrize("backend,expect", [("cpu", True), ("tpu", False)])
def test_interpret_only_on_cpu(monkeypatch, backend, expect):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is expect


def test_interpret_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


@pytest.mark.parametrize("platform,mode", [
    ("cpu", "host"), ("tpu", "pallas"), ("gpu", "xla")])
def test_auto_mode_is_host_only_on_cpu(platform, mode):
    pool = types.SimpleNamespace(kernel_mode="auto",
                                 platform=lambda: platform)
    assert DevicePagePool.mode(pool) == mode


def _store16():
    store = ModelStore(StoreConfig(
        dedup=DedupConfig(block_shape=(16, 16),
                          lsh=LSHConfig(num_bands=8, rows_per_band=2,
                                        r=8.0, collision_threshold=6),
                          validate=False),
        blocks_per_page=4))
    w = np.random.default_rng(0).standard_normal((64, 48))
    store.register("m0", {"w": w.astype(np.float32)})
    return store


def test_pallas_matmul_raises_for_unaligned_blocks_on_tpu(monkeypatch):
    """Off interpret mode, pallas virtual_matmul refuses 16x16 blocks
    before any kernel is built: it never falls back."""
    store = _store16()
    server = WeightServer(store, store.num_pages(),
                          storage=StorageModel("dram"), backend="device",
                          kernel_mode="pallas")
    server.access_pages("m0", store.model_pages("m0"))
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="DESIGN.md"):
        server.device_matmul("m0", "w", np.ones((8, 64), np.float32))


# ------------------------------------------------------------ shard mesh --
def _fake_devices(monkeypatch, platform, n):
    devs = [types.SimpleNamespace(platform=platform, id=i) for i in range(n)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    return devs


def test_shards_never_share_an_accelerator(monkeypatch):
    _fake_devices(monkeypatch, "tpu", 1)
    with pytest.raises(ValueError, match="4 shards need 4 devices"):
        mesh.shard_devices(4)
    with pytest.raises(ValueError, match="4 shards need 4 devices"):
        mesh.make_shard_mesh(4)


def test_shards_map_one_per_chip(monkeypatch):
    devs = _fake_devices(monkeypatch, "tpu", 4)
    assert mesh.shard_devices(4) == devs


def test_cpu_shards_reuse_devices_round_robin(monkeypatch):
    devs = _fake_devices(monkeypatch, "cpu", 1)
    assert mesh.shard_devices(3) == devs * 3


# --------------------------------------------------------- compile cache --
def test_compile_cache_defaults_to_one_ignored_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert path == str(cache.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert os.path.samefile(cache.DEFAULT_CACHE_DIR.parent, ROOT)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    where = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(where),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = ("from repro.launch.cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(where)]
    assert any(where.iterdir())


# ----------------------------------------------------------------- peaks --
def test_peaks_keyed_by_device_kind():
    v5e = analysis.peaks("TPU v5 lite")
    assert (v5e["peak_flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="cpu"):
        analysis.peaks("cpu")
    with pytest.raises(KeyError):
        analysis.roofline_terms(1.0, 1.0, 0.0, "TPU v9")
    t = analysis.roofline_terms(197e12, 0.0, 0.0, "TPU v5 lite")
    assert t["compute_s"] == 1.0 and t["dominant"] == "compute_s"


# ------------------------------------------------------------ chip smoke --
def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
