"""The pieces that decide whether a run really is on the chip: the
compile-cache placement, the peak table, the device gate.  CPU-only and
cheap — the chip itself is exercised by chip_smoke.py."""

import os
import subprocess
import sys

import jax
import pytest

from singa_tpu import device
from singa_tpu.utils import compile_cache
from singa_tpu.utils.metrics import peak_flops, peak_hbm_bw

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """jax.config.update calls, recorded instead of applied (a test
        must never really turn the cache on for the CPU suite)."""
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        return calls

    def test_env_var_set_means_no_path_set_in_code(self, monkeypatch,
                                                   updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache("tpu") == "/somewhere/else"
        assert updates == []

    def test_unset_uses_the_checkout_cache_dir(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache("tpu") == want
        assert updates == [("jax_compilation_cache_dir", want)]
        # one normalised spelling: the directory is part of the cache key
        assert want == os.path.normpath(want)

    def test_never_on_cpu(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache("cpu") is None
        assert updates == []


class TestPeakTable:
    def test_v5e_is_the_published_peak(self):
        assert peak_flops("TPU v5 lite") == 197e12
        assert peak_hbm_bw("TPU v5 lite") == 819e9

    @pytest.mark.parametrize("kind", ["TPU7x", "Frobnicator 9000"])
    def test_unknown_kind_raises_naming_it(self, kind):
        for fn in (peak_flops, peak_hbm_bw):
            with pytest.raises(ValueError, match=kind):
                fn(kind)


def test_out_of_range_chip_id_raises(monkeypatch):
    # stand-in "chips": the id check is about the list, not the platform
    monkeypatch.setattr(device, "_accelerator_devices", jax.local_devices)
    assert device.TpuDevice(id=0).id == jax.local_devices()[0].id
    with pytest.raises(ValueError, match="out of range"):
        device.TpuDevice(id=99)


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "device: platform=cpu kind=cpu" in r.stdout
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout            # no result line

