"""Pallas BCS block-sparse matmul vs the pure-jnp oracle (interpret mode):
shape/dtype sweeps + zero-block skipping + epilogue fusion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # clean container: deterministic example sweep
    from _hypothesis_fallback import given, settings, st

from repro.core import bcs as BCS
from repro.core import regularity as R
from repro.kernels import ref
from repro.kernels.bsr_matmul import bsr_matmul
from repro.kernels import ops


def make_case(M, K, N, bk, bn, zero_frac, seed=0, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (K, N), jnp.float32)
    # kill whole blocks explicitly (the skip path under test)
    Kb, Nb = K // bk, N // bn
    keep = jax.random.uniform(k2, (Kb, Nb)) > zero_frac
    mask = jnp.repeat(jnp.repeat(keep, bk, 0), bn, 1).astype(jnp.float32)
    b = BCS.from_dense(np.asarray(w), np.asarray(mask), (bk, bn))
    vals, kidx, nnz = BCS.pad_to_uniform_csc(b)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K), jnp.float32)
    return (x.astype(dtype), vals.astype(dtype), kidx,
            w.astype(dtype), mask)


SHAPES = [(64, 128, 128, 64, 64), (128, 256, 384, 64, 128),
          (256, 128, 256, 128, 128), (32, 512, 128, 128, 128)]


@pytest.mark.parametrize("M,K,N,bk,bn", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_oracle(M, K, N, bk, bn, dtype):
    x, vals, kidx, w, mask = make_case(M, K, N, bk, bn, zero_frac=0.4,
                                       dtype=dtype)
    y_k = bsr_matmul(x, vals, kidx, bm=min(64, M), interpret=True)
    y_r = ref.bsr_matmul_ref(x, vals, kidx)
    y_m = ref.masked_matmul_ref(x, w, mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(y_r, np.float32),
                               np.asarray(y_m, np.float32),
                               rtol=tol, atol=tol)


def test_all_blocks_zero_column(self=None):
    """A fully-pruned block column must produce exactly zero output."""
    x, vals, kidx, w, mask = make_case(64, 128, 256, 64, 64, zero_frac=0.0)
    mask = mask.at[:, :64].set(0.0)
    b = BCS.from_dense(np.asarray(w), np.asarray(mask), (64, 64))
    vals, kidx, nnz = BCS.pad_to_uniform_csc(b)
    y = bsr_matmul(x, vals, kidx, bm=64, interpret=True)
    assert jnp.allclose(y[:, :64], 0.0)


@pytest.mark.parametrize("act", ["none", "relu", "silu"])
def test_epilogue_fusion(act):
    x, vals, kidx, w, mask = make_case(64, 128, 128, 64, 64, zero_frac=0.3)
    bias = jax.random.normal(jax.random.PRNGKey(9), (128,))
    y_k = bsr_matmul(x, vals, kidx, bias=bias, bm=64, act=act,
                     interpret=True)
    y_r = ref.bsr_matmul_ref(x, vals, kidx, bias=bias, act=act)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(mi=st.sampled_from([1, 2, 4]), ki=st.sampled_from([2, 3]),
       ni=st.sampled_from([2, 3]), zf=st.floats(0.0, 0.8),
       seed=st.integers(0, 20))
def test_kernel_property_sweep(mi, ki, ni, zf, seed):
    """Property: kernel == oracle for random grids/sparsities."""
    bk = bn = 64
    M, K, N = 64 * mi, bk * ki, bn * ni
    x, vals, kidx, w, mask = make_case(M, K, N, bk, bn, zf, seed)
    y_k = bsr_matmul(x, vals, kidx, bm=64, interpret=True)
    y_m = ref.masked_matmul_ref(x, w, mask)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=1e-3, atol=1e-3)


def test_ops_dispatch_dense_fallback():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 3, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    y = ops.sparse_linear(x, w=w)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.einsum("bsi,io->bso", x, w)),
                               rtol=1e-4, atol=1e-4)


def test_ops_pack_and_run():
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    m = R.make_mask(w, "block_row", block=(64, 64), rate=0.5)
    packed = ops.pack(w, m, (64, 64))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    y = ops.sparse_linear(x, packed=packed, bm=64)
    y_ref = ref.masked_matmul_ref(x, w, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)


def test_pallas_interpret_env_override(monkeypatch):
    """Interpret mode follows ``jax.default_backend()`` alone: no
    environment variable can put the kernels in interpret mode on a TPU,
    or force Mosaic lowering elsewhere."""
    from repro.kernels import bsr_matmul as BM

    for env in ("1", "false", ""):
        monkeypatch.setenv("PALLAS_INTERPRET", env)
        for backend, want in (("tpu", False), ("cpu", True), ("gpu", True)):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert BM._interpret_mode() is want
            assert BM._interpret_mode(interpret=not want) is (not want)
    monkeypatch.undo()
    assert BM._interpret_mode() == (jax.default_backend() != "tpu")


def test_refused_kernels_raise_on_tpu(monkeypatch):
    """A kernel the TPU compiler refuses raises on a TPU backend, whatever
    ``interpret`` asks for: it never falls back to the interpreter."""
    from repro.kernels import bsr_matmul as BM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert BM._interpret_mode(interpret=True) is True   # served kernel
    assert BM.refusal_here("bcs", (128, 128), (4096, 4096)) is None
    for kind in ("tap", "conv_implicit", "tap_implicit"):
        for interpret in (None, True):
            with pytest.raises(NotImplementedError, match="ROADMAP S2"):
                BM._interpret_mode(interpret, BM.refusal_here(kind))
    x, vals, kidx, _, _ = make_case(16, 128, 128, 128, 128, zero_frac=0.0)
    scales = jnp.ones(kidx.shape, jnp.float32)
    with pytest.raises(NotImplementedError, match="int8"):
        bsr_matmul(x, vals.astype(jnp.int8), kidx, scales=scales)


@pytest.mark.parametrize("block,shape,bad", [
    ((128, 128), (4096, 11008), None),
    ((128, 128), (11008, 4096), None),
    ((16, 16), (64, 128), "bk=16, bn=16"),
    ((128, 16), (4096, 512), "bn=16"),
    ((64, 16), (64, 16), None),          # whole-array blocks tile
])
def test_tpu_block_refusal(block, shape, bad):
    from repro.kernels import bsr_matmul as BM

    why = BM.tpu_refusal("bcs", block, shape, jnp.bfloat16)
    assert why is None if bad is None else bad in why
    assert "int8" in BM.tpu_refusal("bcs", block, shape, jnp.int8)
    assert BM.refusal_here("bcs", block, shape) is None   # not a TPU


def _per_block_loop(x, w, mask, bk, bn, bias=None, act="none"):
    """The packed kernel's arithmetic spelled out: for each block column,
    an fp32 accumulator that adds one (M, bk) @ (bk, bn) dot per live
    block, in ascending K-block order, then the epilogue."""
    K, N = w.shape
    cols = []
    for c in range(N // bn):
        acc = jnp.zeros((x.shape[0], bn), jnp.float32)
        for k in range(K // bk):
            blk = (slice(k * bk, (k + 1) * bk), slice(c * bn, (c + 1) * bn))
            if mask[blk].any():
                acc = acc + jnp.dot(x[:, blk[0]], w[blk],
                                    preferred_element_type=jnp.float32)
        if bias is not None:
            acc = acc + bias[c * bn:(c + 1) * bn].astype(jnp.float32)
        if act == "silu":
            acc = acc * jax.nn.sigmoid(acc)
        cols.append(acc.astype(x.dtype))
    return jnp.concatenate(cols, axis=1)


@pytest.mark.parametrize("n_bins", [1, 4], ids=["one_bin", "reordered"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "bias_silu"])
@pytest.mark.parametrize("M", [8, 16, 129, 1024])
def test_packed_bitwise_per_block_loop(M, fused, n_bins):
    """One grid step per (M tile, block column) gives, bit for bit, the
    per-block accumulation: over M tiles (1024 rows run as two 512-row
    tiles, 129 as two padded ones), over degree bins, through the fused
    bias + silu, and in a column with no live block."""
    bk = bn = 128
    K, N = 4 * bk, 6 * bn
    rng = np.random.default_rng(M)
    keep = rng.random((K // bk, N // bn)) < 0.5
    keep[:, 2] = False                                   # empty column
    keep[:, 4] = True                                    # dense column
    mask = np.kron(keep, np.ones((bk, bn), bool))
    w = jnp.asarray(rng.standard_normal((K, N)) * mask, jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    bias = (jnp.asarray(rng.standard_normal(N), jnp.bfloat16) if fused
            else None)
    act = "silu" if fused else "none"
    lay = ops.pack(np.asarray(w), mask, (bk, bn), reorder=n_bins > 1,
                   n_bins=n_bins, use_cache=False)
    assert len(lay.values) == n_bins
    y = ops.sparse_linear(x, packed=lay, bias=bias, act=act,
                          interpret=True)
    want = _per_block_loop(x, w, mask, bk, bn, bias, act)
    assert y.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("K", [4096, 14336, 32768])
@pytest.mark.parametrize("M", [16, 4096, 8192])
def test_m_tile_within_vmem_budget(M, K):
    """The M tile is a function of the shapes: decode runs one 16-row
    tile, prefill the largest of 512/256/128 whose pipelined blocks, with
    every block of the column live, fit the VMEM budget; the launch's
    VMEM limit stays inside the v5e's 128 MiB."""
    from repro.kernels import bsr_matmul as BM

    L = K // BM.LANE
    bm, Mp = BM._m_tile(M, BM.m_tile(M, K, L), jnp.bfloat16)
    want = {16: 16}.get(M, 256 if K == 32768 else 512)
    assert (bm, Mp % bm) == (want, 0)
    need = BM.vmem_bytes(bm, K, L, BM.LANE, BM.LANE)
    assert need <= BM.VMEM_BUDGET
    assert need + BM._VMEM_HEADROOM <= 128 * 2**20
    if bm < 512 and M >= 512:
        # the next larger tile would not have fit
        assert BM.vmem_bytes(2 * bm, K, L, BM.LANE, BM.LANE) > BM.VMEM_BUDGET


@pytest.mark.parametrize("n_bins", [1, 2], ids=["one_bin", "reordered"])
def test_layer_scan_reads_stacked_values(n_bins):
    """A layer scan hands each packed layout to its body as views of the
    layer stack (``LayerSlice``), so no layer's weights are sliced out;
    the launches read the stack in place, bit for bit as the layer's own
    layout, while every other leaf is sliced as before."""
    from repro.core.packed import LayerSlice
    from repro.models.transformer import maybe_scan

    bk = bn = 128
    K, N, n_layers = 2 * bk, 3 * bn, 3
    rng = np.random.default_rng(n_bins)
    keep = rng.random((K // bk, N // bn)) < 0.6
    keep[:, 0] = True
    mask = np.kron(keep, np.ones((bk, bn), bool))
    lays = [ops.pack(np.asarray(jnp.asarray(rng.standard_normal((K, N))
                                            * mask, jnp.bfloat16)),
                     mask, (bk, bn), reorder=n_bins > 1, n_bins=n_bins,
                     use_cache=False) for _ in range(n_layers)]
    stack = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *lays)
    x = jnp.asarray(rng.standard_normal((16, K)), jnp.bfloat16)
    views = []

    def body(c, p):
        views.append(all(isinstance(v, LayerSlice) for v in p["w"].values))
        return c, (p["i"], ops.sparse_linear(x, packed=p["w"],
                                             interpret=True))

    _, (idx, ys) = maybe_scan(body, None, {"w": stack,
                                           "i": jnp.arange(n_layers) * 7})
    assert views == [True]
    np.testing.assert_array_equal(np.asarray(idx), np.arange(n_layers) * 7)
    for layer, lay in enumerate(lays):
        want = ops.sparse_linear(x, packed=lay, interpret=True)
        np.testing.assert_array_equal(np.asarray(ys[layer], np.float32),
                                      np.asarray(want, np.float32))
