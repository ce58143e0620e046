"""Test harness config: force an 8-device virtual CPU mesh so every
sharding/collective path is exercised without TPU hardware (SURVEY.md §4
item 3).  Pinned in-process, so a bare `pytest` works without
JAX_PLATFORMS / XLA_FLAGS in the environment."""

import contextlib
import glob
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from singa_tpu.utils.virtcpu import pin_virtual_cpu  # noqa: E402

assert pin_virtual_cpu(8), "could not pin the 8-device virtual CPU platform"

import jax  # noqa: E402
# exact f32 matmuls for numeric checks (TPU runs keep the fast default)
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_singa_state():
    """Each test starts with eager mode, no mesh, fresh default device."""
    import singa_tpu as st
    st.tensor.set_seed(0)
    st.autograd.set_training(False)
    st.parallel.set_mesh(None)
    st.parallel.mesh.set_data_axis("data")
    dev = st.device.create_cpu_device()
    st.device.set_default_device(dev)
    np.random.seed(0)
    yield
    st.parallel.set_mesh(None)
    st.parallel.mesh.set_data_axis("data")
    st.autograd.set_training(False)


@pytest.fixture
def cpu_dev():
    import singa_tpu as st
    return st.device.get_default_device()


@contextlib.contextmanager
def _host_profile(out_dir, prefixes):
    """A `jax.profiler` session with the Python tracer off, as the
    benchmark's traced window runs it.  Yields a list that holds, once
    the block has ended, one list of (name, start_ns, end_ns, stats)
    per host-thread line of the trace that has events whose names
    start with one of `prefixes` (those events only)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    lines = []
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield lines
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(str(out_dir), "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            kept = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if e.name.startswith(prefixes)]
            if kept and not plane.name.startswith("/device:"):
                lines.append(kept)


@pytest.fixture(scope="session")
def host_profile():
    return _host_profile


def _tiny_model(kind):
    """An eval-mode model at its class's `tiny()` size, weights from
    seed 0: `llama`, `moe` (Llama with four dropless experts at top 2),
    `zaya` or `granite`."""
    import dataclasses
    from singa_tpu import models, tensor
    tensor.set_seed(0)
    if kind == "llama":
        m = models.Llama(models.LlamaConfig.tiny())
    elif kind == "moe":
        m = models.Llama(dataclasses.replace(
            models.LlamaConfig.tiny(), num_experts=4, moe_top_k=2,
            moe_dropless=True))
    elif kind == "zaya":
        m = models.Zaya(models.ZayaConfig.tiny())
    else:
        m = models.GraniteHybrid(models.GraniteHybridConfig.tiny())
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="session")
def tiny_model():
    return _tiny_model
