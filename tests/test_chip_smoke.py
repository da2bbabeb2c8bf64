"""``chip_smoke.py`` on the CPU: the same legs the chip run drives, at
tiny sizes, against real role processes — plus the contract around it
(no chip -> non-zero and no result line; the compile cache is placed
from outside or at one fixed path; roles pin themselves off the
accelerator)."""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_legs_tiny_with_real_role_processes(tmp_path, capsys):
    import jax

    smoke = _load_smoke()
    n, block = 4, 1 * MIB
    data = smoke.Dataset(seed=3, n_blocks=n, block_bytes=block)
    cluster = smoke.start_roles(str(tmp_path), block_bytes=block,
                                mem_bytes=smoke.mem_tier_bytes(8 * block))
    try:
        roles = smoke.assert_roles_off_chip(cluster)
        assert [r.split(":")[0] for r in roles] == ["master", "worker"]
        # extra_conf reached the worker ahead of the 64MB default
        assert cluster.workers[0].env["ATPU_WORKER_RAMDISK_SIZE"] == \
            str(smoke.mem_tier_bytes(8 * block))
        fs = cluster.file_system()
        device = jax.devices()[0]
        data.write(fs)
        assert all(data.sums) and len(set(data.sums)) == n

        smoke.leg_resident(fs, data, device)
        smoke.leg_pallas(fs, data, device, interpret=True)
        smoke.leg_evict(fs, data, device, capacity=2 * block)
        smoke.leg_consumer(fs, device, seed=3, block_bytes=block,
                           n_blocks=2, batch=128, image_shape=(16, 16, 3))
        smoke.leg_mesh(fs, data, jax.devices()[:4], blocks_per_device=1,
                       batch=4)
        fs.close()
    finally:
        cluster.stop()

    facts = {}
    for line in capsys.readouterr().out.splitlines():
        _, tag, payload = line.split(" ", 2)
        facts[tag] = json.loads(payload)
    assert facts["resident"]["epoch1"] == {
        "Client.JaxShortCircuitBlocks": n,
        "Client.JaxHbmAdopts": n,
        "Client.JaxPrefaultBlocks": n,
        "Client.JaxLoaderYields": n,
        "Client.BytesRead.shm": n * block}
    assert facts["resident"]["populated"] in (0, n)  # one kernel, one rung
    assert facts["resident"]["epoch2"] == {"Client.JaxHbmHits": n,
                                           "Client.JaxLoaderYields": n}
    assert all(0 <= e <= n for e in facts["resident"]["early_yields"])
    assert facts["evict"]["high_water"] <= facts["evict"]["capacity"]
    assert facts["pallas"]["equal_to_xla"]
    assert facts["consumer"]["steps"] == (2 * (block // 772)) // 128
    assert facts["mesh"]["owners"] == [0, 1, 2, 3]
    assert facts["mesh"]["all_gather_free"]


def test_byte_check_catches_a_wrong_block():
    """The device-vs-host check is not vacuous: one flipped byte in the
    reference fails the leg."""
    smoke = _load_smoke()

    class OneBlock:
        def epoch(self):
            import jax.numpy as jnp

            yield jnp.arange(256, dtype=jnp.uint8)

    smoke._check_sums(OneBlock(), [sum(range(256))])
    with pytest.raises(AssertionError, match="differ from host"):
        smoke._check_sums(OneBlock(), [sum(range(256)) + 1])


def test_script_without_a_chip_fails_and_prints_no_result():
    scratch_before = set(glob.glob("/dev/shm/atpu_smoke_*"))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--blocks", "4", "--block-mib", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout
    # and it left neither a role process nor its scratch dir behind
    assert set(glob.glob("/dev/shm/atpu_smoke_*")) <= scratch_before
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read()
            home = env.split(b"ATPU_HOME=", 1)[1].split(b"\0")[0].decode() \
                if b"ATPU_HOME=/dev/shm/atpu_smoke_" in env else None
            assert home is None or home in scratch_before
        except OSError:
            pass  # gone, or not ours


def test_bench_without_a_chip_fails_and_prints_no_metric():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    import jax

    from alluxio_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.ensure_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == before  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.ensure_compile_cache() == fixed
        assert compile_cache.ensure_compile_cache() == fixed  # every run
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_roles_pin_jax_to_the_cpu(monkeypatch):
    import jax

    from alluxio_tpu.shell import launch

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    launch._keep_off_the_accelerator()
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert jax.config.jax_platforms == "cpu"


def test_default_device_refuses_a_silent_cpu(monkeypatch):
    import jax

    from alluxio_tpu.client.cache.hbm_store import default_device

    assert default_device().platform == "cpu"  # asked for by name
    before = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", None)
        with pytest.raises(RuntimeError, match="no accelerator"):
            default_device()
    finally:
        jax.config.update("jax_platforms", before)
