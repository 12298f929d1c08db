"""Per-kernel validation: shape/dtype sweeps in the interpreter vs ref
oracles (assignment deliverable (c): assert_allclose against the pure-jnp
ref).  Every call names its interpreter: the kernels compile for the TPU by
default.  The probe kernels, which DMA and wait on semaphores, run in the
TPU-semantics interpreter (``TPU_INTERPRET``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

# Pallas interpret-mode kernel sweeps: jit-heavy.
# Deselected by `make test-fast`.
pytestmark = pytest.mark.slow
from _hypothesis_compat import given, settings, st

from repro.core.probes.runners import sattolo_cycle
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)
TPU_INTERPRET = pltpu.InterpretParams()


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ------------------------------------------------------------ flash attn
SWEEP = [
    # (b, hq, hkv, sq, sk, d, bq, bk, causal, dtype, tol)
    (1, 2, 2, 128, 128, 64, 64, 64, True, jnp.float32, 2e-5),
    (2, 4, 1, 256, 256, 64, 128, 128, True, jnp.float32, 2e-5),
    (1, 8, 2, 256, 256, 128, 128, 64, True, jnp.float32, 2e-5),
    (1, 4, 4, 256, 512, 128, 64, 128, False, jnp.float32, 2e-5),
    (2, 2, 1, 128, 128, 64, 64, 64, True, jnp.bfloat16, 3e-2),
    (1, 4, 2, 256, 256, 64, 128, 128, False, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,bq,bk,causal,dtype,tol", SWEEP)
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, bq, bk, causal, dtype,
                               tol):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, hq, sq, d), dtype)
    k = _rand(ks[1], (b, hkv, sk, d), dtype)
    v = _rand(ks[2], (b, hkv, sk, d), dtype)
    from repro.kernels.flash_attention import flash_attention
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_model_layout_wrapper():
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (2, 128, 4, 64), jnp.float32)   # (B, S, H, d)
    k = _rand(ks[1], (2, 128, 2, 64), jnp.float32)
    v = _rand(ks[2], (2, 128, 2, 64), jnp.float32)
    out = ops.mha(q, k, v, block_q=64, block_k=64, interpret=True)
    want = jnp.swapaxes(ref.attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)),
        1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------------ wkv6
@pytest.mark.parametrize("b,t,h,k,v,chunk,dtype,tol", [
    (1, 64, 1, 8, 8, 16, jnp.float32, 1e-4),
    (2, 64, 2, 16, 16, 32, jnp.float32, 1e-4),
    (1, 128, 3, 32, 32, 32, jnp.float32, 1e-4),
    (2, 64, 2, 8, 8, 16, jnp.bfloat16, 5e-2),
])
def test_wkv6_kernel_sweep(b, t, h, k, v, chunk, dtype, tol):
    ks = jax.random.split(KEY, 5)
    r = _rand(ks[0], (b, t, h, k), dtype)
    kk = _rand(ks[1], (b, t, h, k), dtype)
    vv = _rand(ks[2], (b, t, h, v), dtype)
    w = jax.random.uniform(ks[3], (b, t, h, k), jnp.float32, 0.05, 0.98
                           ).astype(dtype)
    u = _rand(ks[4], (h, k), dtype)
    y, s = ops.wkv6(r, kk, vv, w, u, chunk=chunk, interpret=True)
    y_ref, s_ref = ref.wkv6_ref(r, kk, vv, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=tol, atol=tol)


def test_wkv6_kernel_matches_model_chunked():
    """Kernel == models.rwkv6.wkv_chunked (the XLA path it replaces)."""
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(KEY, 5)
    b, t, h, k = 2, 64, 2, 16
    r = _rand(ks[0], (b, t, h, k), jnp.float32)
    kk = _rand(ks[1], (b, t, h, k), jnp.float32)
    vv = _rand(ks[2], (b, t, h, k), jnp.float32)
    w = jax.random.uniform(ks[3], (b, t, h, k), jnp.float32, 0.05, 0.98)
    u = _rand(ks[4], (h, k), jnp.float32)
    y1, s1 = ops.wkv6(r, kk, vv, w, u, chunk=16, interpret=True)
    y2, s2 = wkv_chunked(r, kk, vv, w, u,
                         jnp.zeros((b, h, k, k), jnp.float32), chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


# ----------------------------------------------------------- probes
@pytest.mark.parametrize("rows,cols,block_rows,dtype", [
    (64, 256, 16, jnp.float32),
    (128, 512, 32, jnp.bfloat16),
    (32, 1024, 32, jnp.int32),
])
def test_stream_read_kernel(rows, cols, block_rows, dtype):
    x = (jnp.arange(rows * cols) % 97).astype(dtype).reshape(rows, cols)
    got = ops.stream_read(x, block_rows=block_rows, interpret=TPU_INTERPRET)
    want = ref.stream_read_ref(x, block_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("rows,cols,block_rows", [(64, 256, 16),
                                                  (64, 512, 8)])
def test_stream_write_kernel(rows, cols, block_rows):
    x = jnp.arange(rows * cols, dtype=jnp.float32).reshape(rows, cols)
    got = ops.stream_write(x, block_rows=block_rows, interpret=TPU_INTERPRET)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.stream_write_ref(x)))


@given(n=st.sampled_from([64, 256, 1024]), iters=st.integers(1, 2000),
       seed=st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_pchase_kernel_property(n, iters, seed):
    """Kernel chase must agree with the python oracle for any cycle/iters."""
    rng = np.random.default_rng(seed)
    perm = sattolo_cycle(n, rng)
    out = np.asarray(ops.pchase(jnp.asarray(perm), iters=iters,
                               interpret=True))
    cursor, checksum = ref.pchase_ref(perm, iters)
    assert out[0] == cursor
    assert out[1] == checksum


def test_pchase_full_cycle_returns_home():
    """A single cycle of length n returns to 0 after exactly n steps."""
    rng = np.random.default_rng(0)
    perm = sattolo_cycle(128, rng)
    out = np.asarray(ops.pchase(jnp.asarray(perm), iters=128,
                               interpret=TPU_INTERPRET))
    assert out[0] == 0


def test_pchase_batch_matches_single_rows():
    """Grid-batched chase: per-row padded cycles + per-row chain lengths
    must agree with the single kernel (and the python oracle) row by row."""
    rng = np.random.default_rng(3)
    ns = [16, 64, 256]
    steps = np.array([40, 700, 2500], np.int32)
    nmax = max(ns)
    perms = np.zeros((len(ns), nmax), np.int32)
    for i, n in enumerate(ns):
        perms[i, :n] = sattolo_cycle(n, rng)
    out = np.asarray(ops.pchase_batch(jnp.asarray(perms), steps,
                                     interpret=TPU_INTERPRET))
    assert out.shape == (3, 2)
    for i, n in enumerate(ns):
        single = np.asarray(ops.pchase(jnp.asarray(perms[i, :n]),
                                       iters=int(steps[i]), interpret=True))
        assert np.array_equal(out[i], single)
        cursor, checksum = ref.pchase_ref(perms[i, :n], int(steps[i]))
        assert out[i, 0] == cursor and out[i, 1] == checksum


def test_pchase_batch_dynamic_steps_no_retrace():
    """Chain lengths are data, not static args: same shapes with new step
    counts must reuse the compiled kernel (steps live in the same jaxpr)."""
    rng = np.random.default_rng(4)
    perms = np.zeros((2, 64), np.int32)
    for i in range(2):
        perms[i] = sattolo_cycle(64, rng)
    p = jnp.asarray(perms)
    a = np.asarray(ops.pchase_batch(p, np.array([64, 128], np.int32),
                                   interpret=True))
    b = np.asarray(ops.pchase_batch(p, np.array([128, 64], np.int32),
                                   interpret=True))
    # full-cycle rows return home; the swapped steps swap the outcomes
    assert a[0, 0] == 0 and b[1, 0] == 0
    assert np.array_equal(a[0], b[1]) and np.array_equal(a[1], b[0])
