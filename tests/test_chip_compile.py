"""The Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one kernel for a described (not attached) ``v5e:2x2``
chip and asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes the TPU's tiling rules refuse, and VMEM the chip does not have.
Nothing runs, so the results say nothing about values or times.

The topology is described inside a fixture: describing it loads the TPU
compiler, which only one process may hold, so it must never happen while
a module is imported or collected.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.mamba_scan.mamba_scan import mamba_scan_fwd
from repro.kernels.stacking.stacking import stack_rois_fwd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [8, 150, 600])
def test_stacking_kernel_compiles(one_chip, n):
    """One file's 150 tiles and a 4-file stack of 600 span many blocks."""
    f32 = jnp.float32
    text = _compiled_text(
        lambda r, s, c, y, x: stack_rois_fwd(r, s, c, y, x, interpret=False),
        [((n, 100, 100), f32)] + [((n,), f32)] * 4, one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sky_on_device", [False, True])
def test_stacking_wrapper_keeps_the_kernel_op_name(one_chip, monkeypatch,
                                                   sky_on_device):
    """The jitted wrapper compiles one file's stack (N=150) on both of its
    calls, and the kernel's custom call keeps the wrapper's name,
    ``stack_rois.N``, which is what a device trace finds it by."""
    from repro.kernels.stacking import ops as st_ops

    f32 = jnp.float32
    n = 150
    params = 2 if sky_on_device else 4
    args = [jax.ShapeDtypeStruct((n, 100, 100), f32, sharding=one_chip)] + \
        [jax.ShapeDtypeStruct((n,), f32, sharding=one_chip)] * params
    kw = {"sky_fraction": 0.1} if sky_on_device else {}
    # the wrapper reads the backend while it traces: steer it to the chip,
    # and let no trace made for one backend answer for the other
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        text = st_ops.stack_rois.lower(*args, **kw).compile().as_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    names = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert len(names) == 1 and re.match(r"^stack_rois(\.\d+)?$", names[0]), \
        names


def test_flash_attention_compiles_at_danube3_width(one_chip):
    """h2o-danube-3-4b: 32 query / 8 kv heads, head_dim 120 padded to 128,
    sliding window 4096."""
    bf16 = jnp.bfloat16
    text = _compiled_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, window=4096,
                                            interpret=False),
        [((1, 32, 4096, 128), bf16), ((1, 8, 4096, 128), bf16),
         ((1, 8, 4096, 128), bf16)], one_chip)
    assert "tpu_custom_call" in text


def test_mamba_scan_compiles_at_falcon_mamba_width(one_chip):
    """falcon-mamba-7b: d_inner 8192, state 16."""
    f32 = jnp.float32
    B, S, I, N = 1, 1024, 8192, 16
    text = _compiled_text(
        lambda *a: mamba_scan_fwd(*a, interpret=False),
        [((B, S, I), f32), ((B, S, I), f32), ((I, N), f32),
         ((B, S, N), f32), ((B, S, N), f32), ((I,), f32), ((B, I, N), f32)],
        one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_wrappers_choose_interpret_mode_from_platform(monkeypatch, backend,
                                                      interpret):
    """The three wrappers take no ``interpret`` option: the backend decides,
    and on a TPU every kernel is compiled."""
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.mamba_scan import ops as ms_ops
    from repro.kernels.stacking import ops as st_ops

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    f32 = jnp.float32
    z = jnp.zeros
    calls = [
        (st_ops.stack_rois, (z((16, 8, 8), f32),) + (z((16,), f32),) * 4),
        (fa_ops.flash_attention, (z((1, 64, 4, 16), f32),
                                  z((1, 64, 2, 16), f32),
                                  z((1, 64, 2, 16), f32))),
        (ms_ops.mamba_scan, (z((1, 32, 16), f32), z((1, 32, 16), f32),
                             z((16, 4), f32), z((1, 32, 4), f32),
                             z((1, 32, 4), f32), z((16,), f32))),
    ]
    for wrapper, args in calls:
        # a fresh function around the undecorated wrapper is traced anew,
        # so no trace cached for the other backend can answer
        eqns = jax.make_jaxpr(
            lambda *a, f=wrapper.__wrapped__: f(*a))(*args).jaxpr.eqns
        flags = [bool(e.params["interpret"]) for e in eqns
                 if e.primitive.name == "pallas_call"]
        assert flags == [interpret], wrapper
