"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ------------------------------------------------------------------ rir_matmul
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 256, 128, 128, 128),
    (256, 384, 512, 128, 128, 128),
    (256, 256, 1024, 128, 256, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rir_matmul_sweep(m, k, n, bm, bn, bk, dtype):
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    perm = tuple(int(x) for x in RNG.permutation(n // bn))
    y = ops.rir_matmul(a, b, perm, block_m=bm, block_n=bn, block_k=bk)
    yr = ref.rir_matmul(a, b, perm, bn)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                    rtol=tol, atol=tol)


def test_rir_matmul_identity_equals_plain():
    a, b = _arr((128, 128)), _arr((128, 256))
    y = ops.rir_matmul(a, b, None)
    assert_allclose(np.asarray(y), np.asarray(a @ b), rtol=2e-4, atol=2e-4)


def test_rir_matmul_is_zero_cost_relayout():
    """The RIR claim: permuted output == plain output with columns moved."""
    a, b = _arr((128, 256)), _arr((256, 512))
    perm = (2, 0, 3, 1)
    y = np.asarray(ops.rir_matmul(a, b, perm))
    plain = np.asarray(a @ b)
    for j, pj in enumerate(perm):
        assert_allclose(y[:, pj * 128:(pj + 1) * 128],
                        plain[:, j * 128:(j + 1) * 128], rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- birrd_reduce
@pytest.mark.parametrize("aw,d", [(8, 128), (16, 256), (16, 512)])
def test_birrd_reduce_sweep(aw, d):
    x = _arr((aw, d))
    gids = [i // 2 for i in range(aw)]           # aw/2 groups of 2
    ports = [2 * g for g in range(aw // 2)]
    y = ops.birrd_reduce(x, gids, ports)
    yr = ref.birrd_reduce(x, jnp.asarray(gids, jnp.int32),
                          jnp.asarray(ports, jnp.int32), aw)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)


def test_birrd_pure_reorder_kernel():
    x = _arr((8, 128))
    perm = [int(p) for p in RNG.permutation(8)]
    y = ops.birrd_reduce(x, list(range(8)), perm)
    yr = ref.birrd_reduce(x, jnp.arange(8, dtype=jnp.int32),
                          jnp.asarray(perm, jnp.int32), 8)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)


def test_birrd_reduce_memoizes_routing_and_lowering():
    """Repeat calls with the same (aw, group_ids, out_ports) must hit the
    routing/compilation cache instead of re-searching the switch network."""
    from repro.kernels.birrd_reduce import _routed_stage_mats
    gids, ports = [i // 2 for i in range(8)], [2 * g for g in range(4)]
    y0 = ops.birrd_reduce(_arr((8, 128)), gids, ports)
    before = _routed_stage_mats.cache_info()
    x = _arr((8, 128))
    y1 = ops.birrd_reduce(x, gids, ports)
    after = _routed_stage_mats.cache_info()
    assert after.hits == before.hits + 1
    assert after.misses == before.misses
    yr = ref.birrd_reduce(x, jnp.asarray(gids, jnp.int32),
                          jnp.asarray(ports, jnp.int32), 8)
    assert_allclose(np.asarray(y1), np.asarray(yr), rtol=1e-5, atol=1e-5)
    del y0


# ------------------------------------------------------------------ gqa_decode
@pytest.mark.parametrize("b,hq,hkv,d,s", [
    (2, 8, 2, 64, 512), (1, 4, 4, 128, 1024), (3, 8, 1, 64, 2048),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_decode_sweep(b, hq, hkv, d, s, dtype):
    q = _arr((b, hq, d), dtype)
    k = _arr((b, hkv, s, d), dtype)
    v = _arr((b, hkv, s, d), dtype)
    lens = jnp.asarray(RNG.integers(s // 2, s + 1, size=b), jnp.int32)
    y = ops.gqa_decode(q, k, v, lens)
    yr = ref.gqa_decode(q, k, v, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                    rtol=tol, atol=tol)


def test_gqa_decode_respects_lengths():
    """KV beyond `length` must not affect the output."""
    b, hq, hkv, d, s = 1, 4, 2, 64, 512
    q = _arr((b, hq, d))
    k = _arr((b, hkv, s, d))
    v = _arr((b, hkv, s, d))
    lens = jnp.asarray([256], jnp.int32)
    y1 = ops.gqa_decode(q, k, v, lens)
    k2 = k.at[:, :, 300:].set(99.0)
    v2 = v.at[:, :, 300:].set(-99.0)
    y2 = ops.gqa_decode(q, k2, v2, lens)
    assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("s", [600, 1100])
def test_gqa_decode_ragged_cache_runs_the_kernel(s, monkeypatch):
    """S % block_s != 0 no longer falls back to the reference: 600 takes a
    dividing 200-wide block, 1100 (no multiple of 8 divides it) is padded
    and masked by ``lengths``.  Either way the Pallas kernel runs."""
    b, hq, hkv, d = 2, 8, 2, 64
    q = _arr((b, hq, d))
    k, v = _arr((b, hkv, s, d)), _arr((b, hkv, s, d))
    lens = jnp.asarray([s, s // 2 + 3], jnp.int32)
    want = np.asarray(ref.gqa_decode(q, k, v, lens))

    def no_fallback(*a, **kw):
        raise AssertionError("ops.gqa_decode fell back to ref.gqa_decode")

    monkeypatch.setattr(ref, "gqa_decode", no_fallback)
    assert "pallas_call" in str(jax.make_jaxpr(ops.gqa_decode)(q, k, v, lens))
    y = ops.gqa_decode(q, k, v, lens, block_s=512)
    assert_allclose(np.asarray(y), want, rtol=5e-4, atol=5e-4)


def test_interpret_mode_only_on_cpu():
    """Interpret mode is for the CPU; a TPU compiles; any other backend (a
    TPU that failed to come up and left JAX elsewhere) is an error, never a
    quiet interpreted run."""
    assert ops._interpret("cpu") is True
    assert ops._interpret("tpu") is False
    for backend in ("gpu", "cuda", "rocm"):
        with pytest.raises(RuntimeError, match=backend):
            ops._interpret(backend)


# ----------------------------------------------------------------- linear_scan
@pytest.mark.parametrize("b,h,t,dk,dv,chunk", [
    (2, 3, 128, 32, 64, 64), (1, 2, 256, 64, 64, 32), (2, 1, 192, 16, 16, 64),
])
def test_linear_scan_sweep(b, h, t, dk, dv, chunk):
    q, k = _arr((b, h, t, dk)), _arr((b, h, t, dk))
    v = _arr((b, h, t, dv))
    w = jnp.asarray(-np.abs(RNG.normal(size=(b, h, t, dk)) * 0.2), jnp.float32)
    y = ops.linear_scan(q, k, v, w, chunk=chunk)
    yr = ref.linear_scan(q, k, v, w)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-3, atol=3e-3)


def test_linear_scan_chunked_ref_matches_stepwise():
    """The chunked XLA path (dry-run) == the exact per-step recurrence."""
    b, h, t, dk, dv = 2, 2, 128, 32, 48
    q, k = _arr((b, h, t, dk)), _arr((b, h, t, dk))
    v = _arr((b, h, t, dv))
    w = jnp.asarray(-np.abs(RNG.normal(size=(b, h, t, dk)) * 0.3), jnp.float32)
    y1 = ref.linear_scan_chunked(q, k, v, w, chunk=32)
    y2 = ref.linear_scan(q, k, v, w)
    assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3, atol=2e-3)


def test_linear_scan_decay_semantics():
    """With -inf decay the state resets: output == per-step outer product."""
    b, h, t, dk, dv = 1, 1, 16, 8, 8
    q, k, v = _arr((b, h, t, dk)), _arr((b, h, t, dk)), _arr((b, h, t, dv))
    w = jnp.full((b, h, t, dk), -60.0)   # kills all history
    y = ops.linear_scan(q, k, v, w)
    expect = jnp.einsum("bhtd,bhtd->bht", q, k)[..., None] * v
    assert_allclose(np.asarray(y), np.asarray(expect), rtol=1e-4, atol=1e-4)
