"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode on
the CPU, which the wrappers pick from the platform)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.stacking import ops as st_ops
from repro.kernels.stacking.ref import stack_rois_ref

KEY = jax.random.PRNGKey(42)


# --------------------------- flash attention ---------------------------------

FA_CASES = [
    # (B, S, H, KV, D, causal, window, softcap, dtype)
    (2, 64, 4, 2, 16, True, 0, 0.0, jnp.float32),
    (1, 128, 8, 2, 32, True, 32, 0.0, jnp.float32),     # SWA
    (2, 64, 4, 4, 24, True, 0, 50.0, jnp.float32),      # softcap, odd Dh
    (1, 256, 4, 1, 16, True, 0, 0.0, jnp.float32),      # MQA
    (2, 96, 4, 2, 16, True, 0, 0.0, jnp.float32),       # ragged seq (pad)
    (1, 64, 4, 2, 16, False, 0, 0.0, jnp.float32),      # bidirectional
    (2, 64, 4, 2, 16, True, 16, 30.0, jnp.float32),     # SWA + softcap
    (2, 64, 8, 8, 16, True, 0, 0.0, jnp.bfloat16),      # MHA bf16
]


@pytest.mark.parametrize("B,S,H,KV,D,causal,window,softcap,dtype", FA_CASES)
def test_flash_attention_matches_ref(B, S, H, KV, D, causal, window, softcap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    if not causal and S % 32:
        pytest.skip("non-causal ragged falls back to ref (documented)")
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap, block_q=32, block_k=32)
    ref = jnp.swapaxes(attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window, softcap=softcap), 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_ref_vjp_gradients():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    from repro.kernels.flash_attention.ops import flash_attention_with_ref_vjp

    def f_kernel(q, k, v):
        return flash_attention_with_ref_vjp(q, k, v, causal=True).sum()

    def f_ref(q, k, v):
        return jnp.swapaxes(attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=True), 1, 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# --------------------------- mamba scan -------------------------------------

MS_CASES = [
    (1, 32, 16, 4, 16, 16),
    (2, 96, 48, 8, 16, 32),    # I % block, S % chunk nontrivial
    (2, 128, 64, 16, 32, 64),
    (1, 50, 24, 4, 16, 32),    # ragged S (padding path)
]


@pytest.mark.parametrize("B,S,I,N,bi,ck", MS_CASES)
def test_mamba_scan_matches_ref(B, S, I, N, bi, ck):
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (B, S, I))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, I)))
    A = -jnp.exp(jax.random.normal(ks[2], (I, N)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (I,))
    h0 = jnp.full((B, I, N), 0.05)
    y, hl = ms_ops.mamba_scan(u, dt, A, Bm, Cm, D, h0=h0, block_i=bi, chunk=ck)
    yr, hlr = mamba_scan_ref(u, dt, A, Bm, Cm, D, h0=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), atol=2e-4, rtol=1e-3)


def test_mamba_scan_state_chaining():
    """Running two halves with carried state == running the whole."""
    B, S, I, N = 1, 64, 16, 8
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (B, S, I))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, I)))
    A = -jnp.exp(jax.random.normal(ks[2], (I, N)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (I,))
    y_full, h_full = ms_ops.mamba_scan(u, dt, A, Bm, Cm, D, chunk=16)
    y1, h1 = ms_ops.mamba_scan(u[:, :32], dt[:, :32], A, Bm[:, :32],
                               Cm[:, :32], D, chunk=16)
    y2, h2 = ms_ops.mamba_scan(u[:, 32:], dt[:, 32:], A, Bm[:, 32:],
                               Cm[:, 32:], D, h0=h1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=2e-4, rtol=1e-3)


# --------------------------- stacking ---------------------------------------

@pytest.mark.parametrize("N,H,W,bn", [(8, 16, 16, 4), (37, 24, 40, 8),
                                      (100, 100, 100, 16), (3, 8, 8, 8),
                                      (150, 100, 100, 8)])
def test_stacking_matches_ref(N, H, W, bn):
    ks = jax.random.split(KEY, 5)
    rois = jax.random.normal(ks[0], (N, H, W)) * 100 + 500
    sky = jax.random.normal(ks[1], (N,)) * 10
    cal = jax.random.uniform(ks[2], (N,), minval=0.5, maxval=1.5)
    dy = jax.random.uniform(ks[3], (N,))
    dx = jax.random.uniform(ks[4], (N,))
    out = st_ops.stack_rois(rois, sky, cal, dy, dx, block_n=bn, mean=False)
    ref = stack_rois_ref(rois, sky, cal, dy, dx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=np.abs(np.asarray(ref)).max() * 1e-5)


def test_stacking_integer_shift_exactness():
    """dy=dx=0 must be an exact calibrated sum (no interpolation blur)."""
    rois = jnp.arange(2 * 4 * 4, dtype=jnp.float32).reshape(2, 4, 4)
    out = st_ops.stack_rois(rois, jnp.zeros(2), jnp.ones(2),
                            jnp.zeros(2), jnp.zeros(2), mean=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rois.sum(0)))


def _astro_inputs(kind: str) -> dict:
    from repro.workloads.astro import make_tiles, stack_task

    files = {f"astro.g0.o{k}": make_tiles(f"astro.g0.o{k}", 3)
             for k in range(3)}
    if kind == "one_file":
        return {"astro.g0.o1": files["astro.g0.o1"]}
    if kind == "three_files":
        return files
    stack = stack_task({"astro.g0.o0": files["astro.g0.o0"]})
    return {"astro.stack0": stack, "astro.stack1": stack + 1}


@pytest.mark.parametrize("kind", ["one_file", "three_files", "stacks"])
def test_astro_stack_task_coadds_files_and_stacks_like_the_reference(kind):
    """The paper application's task fn: catalog files are calibrated (the
    sky computed on the device), shifted and coadded; produced stacks (the
    mosaic) are coadded as they are.  The reference is the float32 oracle
    on the host's parameters, ``stack_inputs``."""
    from repro.workloads.astro import make_tiles, stack_inputs, stack_task

    assert np.array_equal(make_tiles("astro.g0.o1", 3),
                          make_tiles("astro.g0.o1", 3))
    inputs = _astro_inputs(kind)
    tiles, params = stack_inputs(inputs)
    n = {"one_file": 3, "three_files": 9, "stacks": 2}[kind]
    assert tiles.shape == (n, 100, 100)
    assert (params[2].std() > 0) == (kind != "stacks")
    ref = np.asarray(stack_rois_ref(tiles, *params)) / n
    np.testing.assert_allclose(stack_task(inputs), ref, rtol=1e-5, atol=1e-3)
    if kind == "stacks":
        np.testing.assert_allclose(ref, inputs["astro.stack0"] + 0.5,
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("kind", ["one_file", "three_files", "stacks"])
def test_astro_stack_task_copies_pixels_on_the_host_only_for_several_inputs(
        monkeypatch, kind):
    """A one-file stack copies no pixel on the host: the kernel's call gets
    the cached (read-only) array itself with the two shift vectors, the sky
    is left to the device, and no ``prep.host_copy`` span is recorded.
    Several files, or produced stacks, are copied inside one such span."""
    from repro.obs import Recorder
    from repro.obs.spans import bind
    from repro.workloads.astro import stack_task

    inputs = _astro_inputs(kind)
    for v in inputs.values():
        v.flags.writeable = False
    calls = []
    real = st_ops.stack_rois

    def spy(tiles, *params, **kw):
        calls.append((tiles, params, kw))
        return real(tiles, *params, **kw)

    monkeypatch.setattr(st_ops, "stack_rois", spy)
    rec = Recorder()
    with bind(rec, "t0", "w0"):
        stack_task(inputs)
    (tiles, params, kw), = calls
    names = [e["name"] for e in rec.events()]
    shared = any(np.shares_memory(tiles, v) for v in inputs.values())
    if kind == "one_file":
        assert shared and names == ["stack.prep", "stack.launch",
                                    "stack.result"]
    else:
        assert not shared and names == ["prep.host_copy", "stack.prep",
                                        "stack.launch", "stack.result"]
    # catalog files leave the sky to the device; stacks have none
    assert (len(params), "sky_fraction" in kw) == \
        ((4, False) if kind == "stacks" else (2, True))
