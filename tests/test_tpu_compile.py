"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX, and it compiles for a topology
that is described rather than attached.  Interpret mode hides Mosaic's
tiling rules; these compiles do not.  Covered, at Yi-9B's published widths
in bf16:

  * ``bsr_matmul_packed`` on the served projection shapes (wq, wk, gate/up,
    down) at decode M=8 and prefill M=512, and at Mistral-7B-v0.1's
    widths at prefill M=4096 and 8192, with and without the fused
    bias + silu epilogue;
  * the tensor-parallel ``bsr_matmul_sharded`` over a 4-chip mesh;
  * the engine's whole served step (``serve.engine._jit_serving_step``)
    and its admission prefill, for the depth-cut config ``chip_smoke.py``
    serves, and the step again at tp=4 under ``make_dist``;
  * the off-path kernels the compiler refuses today, each a strict xfail
    that holds only if the compile fails with Mosaic's own message
    (``bsr_matmul.tpu_refusal`` gives the reason).

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library: under
pytest-xdist only the worker given this file loads it.  Keep these tests
in this one file for the same reason.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro import configs
from repro.core import bcs as BCS
from repro.core.packed import PackedLayout
from repro.distributed import sharding as SH
from repro.kernels import bsr_matmul as BM
from repro.kernels import ops
from repro.launch.serve import sparse_spec
from repro.models import transformer as T
from repro.serve import engine as E
from repro.serve import kvcache as KV

# chip_smoke.py's default depth cut of Yi-9B (every width as published)
CFG = configs.get("yi-9b").replace(n_layers=8)
BLOCK = sparse_spec(CFG)[0][1].block
# (K, N) of the served projections at Yi-9B widths
PROJ = {"wq": (4096, 4096), "wk": (4096, 512), "gate": (4096, 11008),
        "down": (11008, 4096)}
# ... and at Mistral-7B-v0.1's widths, which the benchmark serves
MISTRAL_PROJ = {"wq": (4096, 4096), "wk": (4096, 1024),
                "gate": (4096, 14336), "down": (14336, 4096)}
DENSITY = 0.4      # magnitude_block_masks at rate 0.6 keeps 40% of blocks


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture()
def on_tpu(monkeypatch):
    """What the code sees on the chip: a TPU default backend, so every
    ``interpret=None`` kernel lowers through Mosaic."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _placed(tree, sharding):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _layout(K, N, sharding, *, lead=(), n_shards=0, int8=False):
    """Abstract reordered PackedLayout of a (K, N) weight at ``DENSITY``:
    4 degree bins padded to a common degree, leaves on ``sharding``."""
    bk, bn = BLOCK
    Kb, Nb = K // bk, N // bn
    sh = (n_shards,) if n_shards else ()
    L = max(1, round(DENSITY * Kb))
    bins = BCS.bin_bounds(Nb // max(1, n_shards), 4)
    vdt = jnp.int8 if int8 else jnp.bfloat16
    lay = PackedLayout(
        values=tuple(_sds(lead + sh + (b - a, L, bk, bn), vdt, sharding)
                     for a, b in bins),
        k_idx=tuple(_sds(lead + sh + (b - a, L), jnp.int32, sharding)
                    for a, b in bins),
        nnz=_sds(lead + sh + (Nb // max(1, n_shards),), jnp.int32, sharding),
        perm=_sds(lead + sh + (Nb // max(1, n_shards),), jnp.int32,
                  sharding),
        inv_perm=_sds(lead + (Nb,), jnp.int32, sharding),
        scales=(tuple(_sds(lead + sh + (b - a, L), jnp.float32, sharding)
                      for a, b in bins) if int8 else None),
        block=BLOCK, shape=(K, N), n_shards=n_shards)
    return lay


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# -- the served kernel -------------------------------------------------------

# (K, N) and M of each case: Yi-9B at decode M=8 and prefill M=512, then
# Mistral-7B-v0.1 at the 4096- and 8192-token admission prefills, where
# the (bm, K) x tile at K = 14336 (down) needs the raised VMEM limit
MATMUL_CASES = (
    [pytest.param(PROJ[p], M, id=f"{p}-{M}")
     for p in sorted(PROJ) for M in (8, 512)]
    + [pytest.param(MISTRAL_PROJ[p], M, id=f"mistral-{p}-{M}")
       for p in sorted(MISTRAL_PROJ) for M in (4096, 8192)])


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "bias_silu"])
@pytest.mark.parametrize("shape,M", MATMUL_CASES)
def test_served_matmul_lowers(one_chip, shape, M, fused):
    K, N = shape
    x = _sds((M, K), jnp.bfloat16, one_chip)
    bias = _sds((N,), jnp.bfloat16, one_chip) if fused else None
    compiled = _compile(
        lambda x, lay, b: BM.bsr_matmul_packed(
            x, lay, bias=b, act="silu" if fused else "none",
            interpret=False),
        x, _layout(K, N, one_chip), bias)
    assert "tpu_custom_call" in compiled.as_text()


def _tp_layout(K, N, mesh, lead=()):
    """``_layout`` as ``CompileSpec(tp=4)`` + ``shard_packed_tree`` place
    it on ``mesh``: column-sharded 4 ways where 4 divides the block
    columns (else replicated, as Yi's 86-block gate/up)."""
    lay = _layout(K, N, None, lead=lead,
                  n_shards=4 if (N // BLOCK[1]) % 4 == 0 else 0)
    specs = SH.layout_partition_specs(lay)
    is_spec = lambda s: isinstance(s, PartitionSpec)  # noqa: E731
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(lay),
        [_sds(a.shape, a.dtype, NamedSharding(mesh, s))
         for a, s in zip(jax.tree_util.tree_leaves(lay),
                         jax.tree_util.tree_leaves(specs, is_leaf=is_spec))])


@pytest.mark.parametrize("M", [8, 512])
def test_sharded_matmul_lowers(mesh4, M):
    """The tensor-parallel matmul over 4 chips: column shards split over
    the "model" axis, one shard's launches per chip (``shard_map``), x
    replicated, the merge lowered by GSPMD."""
    K, N = PROJ["wq"]
    lay = _tp_layout(K, N, mesh4)
    x = _sds((M, K), jnp.bfloat16, NamedSharding(mesh4, PartitionSpec()))
    def sharded(x, lay):
        with BM.traced_on(mesh4, "model"):
            return BM.bsr_matmul_sharded(x, lay, interpret=False)

    compiled = _compile(sharded, x, lay)
    assert "tpu_custom_call" in compiled.as_text()
    # each chip holds a quarter of the packed values, not all of them
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    values = sum(np.prod(v.shape) * 2 for v in lay.values)
    assert per_chip < M * K * 2 + values / 2


# -- the served programs ------------------------------------------------------

def _served_params(sharding, mesh=None):
    """Shapes of ``compile_model``'s output for CFG under ``sparse_spec``:
    every attention and FFN projection packed, dense "w" dropped.  With a
    ``mesh``, the layouts are those of ``CompileSpec(tp=4)`` placed by
    ``shard_packed_tree``, and the other leaves replicated."""
    p = _placed(jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0),
                                                 CFG)), sharding)
    for group, names in (("attn", ("wq", "wk", "wv", "wo")),
                         ("ffn", ("gate", "up", "down"))):
        for name in names:
            K, N = p["layers"][group][name]["w"].shape[-2:]
            lead = (CFG.n_layers,)
            p["layers"][group][name] = {"packed": (
                _tp_layout(K, N, mesh, lead) if mesh is not None
                else _layout(K, N, sharding, lead=lead))}
    return p


def _step_lowered(params, sharding, dist=None, n_slots=8, seq_cap=512):
    cache = _placed(jax.eval_shape(
        lambda: KV.init_slots(None, CFG, n_slots, seq_cap)), sharding)
    col = _sds((n_slots, 1), jnp.int32, sharding)
    return E._jit_serving_step(CFG, dist).lower(
        params, col, cache, col, _sds((n_slots,), jnp.int32, sharding))


def _weight_slices(text):
    """Instructions that copy a layer's (..., 128, 128) weight bins out of
    the layer stack: none, since the launches read the stack in place."""
    return re.findall(r"%dynamic[-_]slice\S* = bf16\[[\d,]*,128,128\]", text)


def test_served_step_lowers(one_chip, on_tpu, monkeypatch):
    """The engine's batched decode step (8 slots), the program every served
    token runs, compiles with the Pallas kernels in it."""
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())
    compiled = _step_lowered(_served_params(one_chip), one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert not _weight_slices(compiled.as_text())


def test_served_step_tp4_lowers(mesh4, on_tpu, monkeypatch):
    """The same step at tp=4 under ``make_dist`` on the (1, 4) mesh, as
    ``chip_smoke.py --four-chips`` serves it: the kernels run per chip and
    the compiler inserts the collectives."""
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())
    rep = NamedSharding(mesh4, PartitionSpec())
    compiled = _step_lowered(_served_params(rep, mesh4), rep,
                             SH.make_dist(mesh4, CFG, 8)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_served_prefill_lowers(one_chip, on_tpu, monkeypatch):
    """The admission prefill (B=1, a 512-token prompt)."""
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())
    compiled = E._jit_prefill(CFG, None).lower(
        _served_params(one_chip), _sds((1, 512), jnp.int32, one_chip),
        None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert not _weight_slices(compiled.as_text())


# -- off-path kernels the compiler refuses today ------------------------------

class RefusedByMosaic(Exception):
    """The TPU compiler refused a kernel with the expected message."""


def _refused(kind):
    return pytest.mark.xfail(strict=True, raises=RefusedByMosaic,
                             reason=BM.tpu_refusal(*kind))


def _expect_refusal(compile_fn, message):
    """Run ``compile_fn``; it must fail with Mosaic's ``message``, which is
    re-raised as ``RefusedByMosaic`` for the strict xfail.  Any other error
    (a bug in the case itself) or a clean compile fails the test."""
    with pytest.raises(Exception, match=re.escape(message)) as err:
        compile_fn()
    raise RefusedByMosaic(str(err.value)[:500])


_TILING = ("The Pallas TPU lowering currently requires that the last two "
           "dimensions of your block shape are divisible by 8 and 128 "
           "respectively, or be equal to the respective dimensions of the "
           "overall array.")


def _conv_case(rng_seed=0, C=128, P=128, k=3):
    """A ResNet-style 3x3 conv (28x28x128 -> 128), 56% of taps pruned."""
    rng = np.random.default_rng(rng_seed)
    w = rng.standard_normal((P, C, k, k)).astype(np.float32)
    return w, rng.random(w.shape) < 0.44


@_refused(("bcs", BLOCK, PROJ["wq"], jnp.int8))
def test_int8_matmul_lowers(one_chip):
    K, N = PROJ["wq"]
    x, lay = _sds((8, K), jnp.bfloat16, one_chip), _layout(K, N, one_chip,
                                                           int8=True)
    _expect_refusal(lambda: _compile(
        lambda x, lay: BM.bsr_matmul_packed(x, lay, interpret=False),
        x, lay), _TILING)


@_refused(("tap",))
def test_tap_gather_conv_lowers(one_chip):
    w, mask = _conv_case()
    tap = _placed(ops.pack_taps(w, mask, n_bins=1, use_cache=False),
                  one_chip)
    x = _sds((784, tap.alive.shape[0]), jnp.bfloat16, one_chip)
    _expect_refusal(lambda: _compile(
        lambda x, t: BM.tap_gather_conv(x, t.values[0], t.t_idx[0],
                                        interpret=False), x, tap), _TILING)


@_refused(("conv_implicit",))
def test_bsr_conv2d_implicit_lowers(one_chip):
    w, _ = _conv_case()
    mask = np.kron(np.random.default_rng(1).random((1, 1, 3, 3)) < 0.6,
                   np.ones((128, 128, 1, 1), bool))
    lay = _placed(ops.pack(BCS.conv_lower(w), BCS.conv_lower(mask), BLOCK,
                           reorder=True, conv=(3, 3, 128), use_cache=False),
                  one_chip)
    x = _sds((1, 28, 28, 128), jnp.bfloat16, one_chip)
    _expect_refusal(lambda: _compile(
        lambda x, lay: BM.bsr_conv2d_implicit(x, lay, kh=3, kw=3,
                                              interpret=False), x, lay),
        "Only 2D gather is supported")


@_refused(("tap_implicit",))
def test_tap_gather_conv_implicit_lowers(one_chip):
    w, mask = _conv_case()
    tap = _placed(ops.pack_taps(w, mask, n_bins=1, use_cache=False),
                  one_chip)
    x = _sds((1, 28, 28, 128), jnp.bfloat16, one_chip)
    _expect_refusal(lambda: _compile(
        lambda x, t: BM.tap_gather_conv_implicit(x, t, kh=3, kw=3,
                                                 interpret=False), x, tap),
        _TILING)
