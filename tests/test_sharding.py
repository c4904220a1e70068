"""Tensor-parallel PackedLayout/TapLayout sharding tests.

Runs the REAL sharded path on CPU — conftest fakes 8 host devices via
``--xla_force_host_platform_device_count`` — and locks down:

  * tp=1/2/4 parity vs the single-device oracle on every packed producer
    (linear fp32/int8, MoE expert stacks, materialized conv, pattern
    conv).  Sharding never touches a BCS column's accumulation order, so
    those asserts are BIT-identity; tap layouts agree to fp32 rounding.
  * degree-balanced shard assignment: max/mean executed-L on skewed
    fixtures stays within the modeled LPT bound (and the BENCH_shard
    gate's 1.15).
  * NamedSharding placement of registered pytree leaves on a real
    multi-device mesh, under jit.
  * artifact round-trip of sharded layouts through the AOT store.
  * ``core.validate`` rejecting every cross-shard invariant violation
    with the matching LayoutError subclass.
  * ServingEngine greedy decode on a tp=2 local mesh == N independent
    ``generate`` calls, and the batched step still traces exactly once.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import bcs as BCS
from repro.core import reweighted as RW
from repro.core import validate as V
from repro.distributed import sharding as SH
from repro.kernels import ops
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import sparse_spec
from repro.models import transformer as T
from repro.serve import engine as E
from repro.serve.compile import CompileSpec, compile_model
from repro.serve.engine import ServingEngine, generate
from repro.train.trainer import apply_masks

SHARDS = (1, 2, 4)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _block_fixture(seed=0, K=64, N=128, bk=8, bn=8, keep=0.5):
    rng = _rng(seed)
    w = rng.standard_normal((K, N)).astype(np.float32)
    mask = np.kron(rng.random((K // bk, N // bn)) < keep,
                   np.ones((bk, bn), bool))
    return w, mask, (bk, bn)


def _skewed_block_fixture(seed=0, K=128, N=256, bk=8, bn=8):
    """Column-block degrees drawn heavily skewed: a few dense columns, a
    long sparse tail — the worst case for contiguous shard assignment."""
    rng = _rng(seed)
    Kb, Nb = K // bk, N // bn
    mb = np.zeros((Kb, Nb), bool)
    for j in range(Nb):
        deg = Kb if j % 8 == 0 else 1 + int(rng.integers(0, 3))
        mb[rng.permutation(Kb)[:deg], j] = True
    w = rng.standard_normal((K, N)).astype(np.float32)
    return w, np.kron(mb, np.ones((bk, bn), bool)), (bk, bn)


def _conv_fixture(seed=0, P=16, Q=8, k=3):
    rng = _rng(seed)
    w = rng.standard_normal((P, Q, k, k)).astype(np.float32)
    mask = rng.random((P, Q, k, k)) < 0.4
    mask[0] = True
    return w, mask


def _lm(arch, **over):
    cfg = configs.get(arch, smoke=True)
    if over:
        cfg = cfg.replace(**over)
    return T.init_lm(jax.random.PRNGKey(0), cfg), cfg


# -- parity vs the single-device oracle, every packed producer ---------------

class TestShardedParity:
    @pytest.mark.parametrize("S", SHARDS)
    def test_linear_bit_identical(self, S):
        """Sharded sparse_linear == unsharded oracle, bitwise: per-column
        accumulation order is untouched by the shard split."""
        w, mask, block = _block_fixture()
        x = jnp.asarray(_rng(1).standard_normal((4, w.shape[0])),
                        jnp.float32)
        bias = jnp.asarray(_rng(2).standard_normal(w.shape[1]), jnp.float32)
        ref = ops.sparse_linear(
            x, packed=ops.pack(w, mask, block, reorder=True),
            bias=bias, act="silu")
        got = ops.sparse_linear(
            x, packed=ops.pack(w, mask, block, n_shards=S),
            bias=bias, act="silu")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("S", (2, 4))
    def test_linear_int8_bit_identical(self, S):
        """The quantized value path shards too: int8 values + fp32 scale
        leaves carry the shard axis, outputs stay bit-identical."""
        w, mask, block = _block_fixture(seed=3)
        x = jnp.asarray(_rng(4).standard_normal((3, w.shape[0])),
                        jnp.float32)
        ref = ops.sparse_linear(
            x, packed=ops.pack(w, mask, block, reorder=True,
                               value_dtype="int8"))
        got = ops.sparse_linear(
            x, packed=ops.pack(w, mask, block, n_shards=S,
                               value_dtype="int8"))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("S", (2, 4))
    def test_conv_bit_identical(self, S):
        """Materialized sparse conv (im2col GEMM) over a sharded layout;
        sharded layouts never take the implicit kernel."""
        w, mask = _conv_fixture()
        wl = BCS.conv_lower(w)
        ml = BCS.conv_lower(mask)
        gemm_block, _ = BCS.conv_gemm_block((4, 4), w.shape)
        x = jnp.asarray(_rng(5).standard_normal((2, 10, 10, w.shape[1])),
                        jnp.float32)
        kh, kw = w.shape[2], w.shape[3]
        conv = (kh, kw, w.shape[1])
        ref = ops.sparse_conv2d(
            x, ops.pack(wl, ml, gemm_block, reorder=True, conv=conv),
            kh=kh, kw=kw, implicit=False)
        got = ops.sparse_conv2d(
            x, ops.pack(wl, ml, gemm_block, n_shards=S, conv=conv),
            kh=kh, kw=kw)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("S", (2, 4))
    def test_pattern_conv_bit_identical(self, S):
        """Pattern (tap-gather) conv over a sharded TapLayout.  Shards pad
        their filters to other tap degrees than the unsharded layout, so
        each filter's single dot has another reduction length: the same
        conv to fp32 rounding, not bitwise (unlike the BCS layouts, whose
        padding blocks add exact zeros after each column's sum)."""
        w, mask = _conv_fixture(seed=6)
        x = jnp.asarray(_rng(7).standard_normal((2, 9, 9, w.shape[1])),
                        jnp.float32)
        kh, kw = w.shape[2], w.shape[3]
        ref = ops.sparse_conv2d_pattern(x, ops.pack_taps(w, mask),
                                        kh=kh, kw=kw)
        got = ops.sparse_conv2d_pattern(
            x, ops.pack_taps(w, mask, n_shards=S), kh=kh, kw=kw)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("S", (2, 4))
    def test_moe_expert_stack_sharded_free(self, S):
        """MoE expert layouts shard along the leading expert axis (never
        block columns): placing them with expert_layout_specs on a real
        mesh leaves sparse_expert_linear bit-identical under jit."""
        rng = _rng(8)
        E_, din, dout, bk = 4, 32, 48, 8
        w = rng.standard_normal((E_, din, dout)).astype(np.float32)
        mb = rng.random((E_, din // bk, dout // bk)) < 0.5
        mask = np.kron(mb, np.ones((bk, bk), bool))
        from repro.serve.compile import _pack_stacked
        packed, _ = _pack_stacked(w, mask, (bk, bk))
        assert packed.n_shards == 0
        x = jnp.asarray(rng.standard_normal((E_, 5, din)), jnp.float32)
        ref = ops.sparse_expert_linear(x, packed)
        mesh = make_local_mesh(tp=S)
        shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s),
            SH.expert_layout_specs(packed),
            is_leaf=lambda p: isinstance(p, jax.sharding.PartitionSpec))
        placed = jax.device_put(packed, shardings)
        got = jax.jit(lambda xx: ops.sparse_expert_linear(xx, placed))(x)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("S", SHARDS)
    def test_to_dense_roundtrip(self, S):
        """Sharded layouts still reconstruct the masked dense weight
        exactly — shard-major storage + global perm lose nothing."""
        w, mask, block = _block_fixture(seed=9)
        pl = ops.pack(w, mask, block, n_shards=S)
        np.testing.assert_array_equal(np.asarray(pl.to_dense()), w * mask)
        wc, mc = _conv_fixture(seed=10)
        tl = ops.pack_taps(wc, mc, n_shards=S)
        np.testing.assert_array_equal(
            np.asarray(tl.to_dense()),
            BCS.conv_lower(wc) * BCS.conv_lower(mc))

    def test_column_sharding_never_reaches_expert_kernel(self):
        w, mask, block = _block_fixture()
        pk = ops.pack(w, mask, block, n_shards=2)
        x = jnp.zeros((2, 3, w.shape[0]))
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.stack([a, a]), pk)
        with pytest.raises(AssertionError, match="expert"):
            ops.sparse_expert_linear(x, stacked)


# -- degree-balanced shard assignment ----------------------------------------

class TestShardBalance:
    @pytest.mark.parametrize("S", (2, 4))
    def test_skewed_fixture_within_gate(self, S):
        """On the skewed fixture the LPT assignment keeps the straggler
        factor (max/mean executed blocks per independently-padded shard)
        within the BENCH_shard gate."""
        w, mask, block = _skewed_block_fixture()
        pl = ops.pack(w, mask, block, n_shards=S)
        assert pl.shard_balance <= 1.15, pl.shard_balance

    @pytest.mark.parametrize("S", (2, 4))
    def test_lpt_load_bound(self, S):
        """Raw per-shard nnz load obeys the LPT bound: max load <= mean
        load + the heaviest single column (greedy puts each column on the
        lightest open shard)."""
        w, mask, block = _skewed_block_fixture(seed=11)
        bk, bn = block
        mb = mask[::bk, ::bn]
        cnt = mb.sum(axis=0).astype(np.int64)
        assign = BCS.shard_columns(cnt, S)
        loads = cnt[assign].sum(axis=1)
        assert loads.max() <= loads.mean() + cnt.max()

    @pytest.mark.parametrize("S", (2, 4))
    def test_beats_contiguous_assignment(self, S):
        """Degree-balanced assignment is never worse than naive contiguous
        column chunks on the skewed fixture."""
        w, mask, block = _skewed_block_fixture(seed=12)
        pl = ops.pack(w, mask, block, n_shards=S)
        bk, bn = block
        cnt = mask[::bk, ::bn].sum(axis=0)
        Nb = cnt.shape[0]
        naive = cnt.reshape(S, Nb // S).sum(axis=1)
        naive_ratio = naive.max() / naive.mean()
        assert pl.shard_balance <= naive_ratio + 1e-9

    def test_shard_columns_rejects_bad_counts(self):
        with pytest.raises(ValueError, match="divide"):
            BCS.shard_columns(np.ones(10, np.int64), 3)
        with pytest.raises(ValueError, match=">= 1"):
            BCS.shard_columns(np.ones(10, np.int64), 0)

    def test_equal_shard_widths(self):
        """Capacity-exact LPT: every shard owns exactly Nb/S columns (the
        stacking + NamedSharding invariant)."""
        w, mask, block = _skewed_block_fixture(seed=13)
        for S in (2, 4):
            pl = ops.pack(w, mask, block, n_shards=S)
            assert np.asarray(pl.perm).shape == (S, pl.Nb // S)
            flat = np.sort(np.asarray(pl.perm).reshape(-1))
            np.testing.assert_array_equal(flat, np.arange(pl.Nb))


# -- mesh + NamedSharding placement ------------------------------------------

class TestMeshPlacement:
    def test_make_local_mesh_tp(self):
        mesh = make_local_mesh(tp=4)
        assert mesh.shape == {"data": 1, "model": 4}
        assert make_local_mesh().shape == {"data": 1, "model": 1}
        with pytest.raises(ValueError, match=">= 1"):
            make_local_mesh(tp=0)
        with pytest.raises(ValueError, match="devices"):
            make_local_mesh(tp=jax.device_count() + 1)

    @pytest.mark.parametrize("S", (2, 4))
    def test_placement_under_jit_bit_identical(self, S):
        """device_put with layout_shardings really splits the shard axis
        across S devices; jitted sparse_linear on the placed layout stays
        bit-identical to the single-device oracle."""
        w, mask, block = _block_fixture(seed=14)
        ref = ops.sparse_linear(
            jnp.eye(w.shape[0]), packed=ops.pack(w, mask, block,
                                                 reorder=True))
        pk = ops.pack(w, mask, block, n_shards=S)
        mesh = make_local_mesh(tp=S)
        placed = jax.device_put(pk, SH.layout_shardings(pk, mesh))
        assert len(placed.values[0].sharding.device_set) == S
        assert placed.inv_perm.sharding.is_fully_replicated
        got = jax.jit(
            lambda x: ops.sparse_linear(x, packed=placed))(
                jnp.eye(w.shape[0]))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_partition_specs_shapes(self):
        """The spec tree maps exactly the shard stack dim to "model"."""
        w, mask, block = _block_fixture(seed=15)
        pk = ops.pack(w, mask, block, n_shards=2)
        specs = SH.layout_partition_specs(pk)
        P = jax.sharding.PartitionSpec
        assert specs.values[0] == P("model", None, None, None, None)
        assert specs.k_idx[0] == P("model", None, None)
        assert specs.nnz == P("model", None)
        assert specs.perm == P("model", None)
        assert specs.inv_perm == P()
        unsh = ops.pack(w, mask, block, reorder=True)
        for s in jax.tree_util.tree_leaves(
                SH.layout_partition_specs(unsh),
                is_leaf=lambda x: isinstance(x, P)):
            assert s == P()

    def test_shard_packed_tree_walks_params(self):
        w, mask, block = _block_fixture(seed=16)
        tree = {"blk": {"ffn": {"gate": {
            "w": jnp.asarray(w),
            "packed": ops.pack(w, mask, block, n_shards=2)}}}}
        mesh = make_local_mesh(tp=2)
        out = SH.shard_packed_tree(tree, mesh)
        pk = out["blk"]["ffn"]["gate"]["packed"]
        assert len(pk.values[0].sharding.device_set) == 2
        # non-layout leaves untouched
        assert out["blk"]["ffn"]["gate"]["w"] is tree["blk"]["ffn"]["gate"]["w"]


# -- artifact round-trip ------------------------------------------------------

class TestShardedArtifacts:
    def test_roundtrip_preserves_shards(self, tmp_path):
        """Sharded layouts survive the AOT store: the warm start carries
        n_shards and decodes bit-identically, with zero repacking."""
        spec_map = [(r"ffn/(gate|up)/w", RW.SchemeChoice("block", (16, 16)))]
        params = {"blk": {"ffn": {
            "gate": {"w": jax.random.normal(jax.random.PRNGKey(0),
                                            (64, 96), jnp.float32)},
            "up": {"w": jax.random.normal(jax.random.PRNGKey(1),
                                          (64, 96), jnp.float32)}}}}
        masks = RW.random_block_masks(params, spec_map, (16, 16),
                                      keep_prob=0.4)
        pm = apply_masks(params, masks)
        cs = CompileSpec(tp=2)
        e1, r1 = compile_model(pm, masks, spec_map, spec=cs,
                               artifact_dir=tmp_path)
        ops.clear_pack_cache()
        misses = ops.pack_cache_stats()["misses"]
        e2, r2 = compile_model(pm, masks, spec_map, spec=cs,
                               artifact_dir=tmp_path)
        assert ops.pack_cache_stats()["misses"] == misses
        pk1 = e1["blk"]["ffn"]["gate"]["packed"]
        pk2 = e2["blk"]["ffn"]["gate"]["packed"]
        assert pk1.n_shards == pk2.n_shards == 2
        V.validate_tree(e2)
        x = jax.random.normal(jax.random.PRNGKey(2), (3, 64))
        np.testing.assert_array_equal(
            np.asarray(ops.sparse_linear(x, packed=pk1)),
            np.asarray(ops.sparse_linear(x, packed=pk2)))

    def test_tp_in_model_digest(self):
        """CompileSpec.tp is digest-covered: a tp=1 artifact never warm
        starts a tp=2 compile."""
        assert CompileSpec(tp=1).digest_fields() != \
            CompileSpec(tp=2).digest_fields()
        assert CompileSpec(tp=2) == CompileSpec(tp=2)


# -- cross-shard invariant rejection -----------------------------------------

class TestValidateSharded:
    @pytest.fixture()
    def packed(self):
        w, mask, block = _block_fixture(seed=17)
        return ops.pack(w, mask, block, n_shards=2, use_cache=False)

    @pytest.fixture()
    def tap(self):
        w, mask = _conv_fixture(seed=18)
        return ops.pack_taps(w, mask, n_shards=2, use_cache=False)

    def _expect(self, layout, err, **repl):
        with pytest.raises(err):
            V.validate_layout(dataclasses.replace(layout, **repl))

    def test_sharded_layouts_validate(self, packed, tap):
        V.validate_layout(packed)
        V.validate_layout(tap)

    def test_nondividing_shard_count(self, packed, tap):
        self._expect(packed, V.LayoutGeometryError, n_shards=3)
        self._expect(tap, V.LayoutGeometryError, n_shards=7)

    def test_missing_shard_axis_on_values(self, packed, tap):
        self._expect(packed, V.LayoutStructureError,
                     values=tuple(v[0] for v in packed.values))
        self._expect(tap, V.LayoutStructureError,
                     values=tuple(v[0] for v in tap.values))

    def test_nnz_without_shard_axes(self, packed, tap):
        self._expect(packed, V.LayoutStructureError,
                     nnz=packed.nnz.reshape(-1))
        self._expect(tap, V.LayoutStructureError, nnz=tap.nnz.reshape(-1))

    def test_sharded_requires_perm(self, packed, tap):
        self._expect(packed, V.LayoutPermutationError,
                     perm=None, inv_perm=None)
        self._expect(tap, V.LayoutPermutationError, perm=None,
                     inv_perm=None)

    def test_flat_perm_rejected(self, packed):
        self._expect(packed, V.LayoutStructureError,
                     perm=packed.perm.reshape(-1))

    def test_cross_shard_duplicate_column(self, packed, tap):
        """One shard claiming another's column — the corruption that would
        silently scramble merge_shards — is a permutation violation."""
        for layout in (packed, tap):
            p = np.asarray(layout.perm).copy()
            p[0, 0] = p[1, 0]
            self._expect(layout, V.LayoutPermutationError,
                         perm=jnp.asarray(p))

    def test_inconsistent_inv_perm(self, packed):
        ip = np.asarray(packed.inv_perm).copy()
        ip[0], ip[1] = ip[1], ip[0]
        self._expect(packed, V.LayoutPermutationError,
                     inv_perm=jnp.asarray(ip))

    def test_wrong_shard_count_aux(self, packed):
        """Aux shard count disagreeing with the actual leaf shard axis."""
        self._expect(packed, V.LayoutError, n_shards=4)

    def test_validate_tree_finds_sharded_layouts(self, packed):
        tree = {"a": {"packed": packed},
                "b": {"packed": dataclasses.replace(
                    packed, nnz=packed.nnz.reshape(-1))}}
        with pytest.raises(V.LayoutStructureError, match="b"):
            V.validate_tree(tree)
        assert V.validate_tree({"a": {"packed": packed}}) == 1


# -- serving on a tp=2 local mesh --------------------------------------------

def _compiled_tp2(family):
    arch = {"dense": "yi-9b", "moe": "mixtral-8x7b",
            "hybrid": "hymba-1.5b"}[family]
    params, cfg = _lm(arch)
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params = apply_masks(params, masks)
    params, rep = compile_model(params, masks, sparse_spec(cfg),
                                spec=CompileSpec(keep_dense=False, tp=2))
    assert any(r.get("shards") == 2 for r in rep.packed)
    # MoE expert stacks must stay column-unsharded (expert axis shards)
    for r in rep.packed:
        if "moe" in r["path"].split("/"):
            assert r.get("shards") is None
    mesh = make_local_mesh(tp=2)
    dist = SH.make_dist(mesh, cfg, 2)
    return SH.shard_packed_tree(params, mesh), cfg, dist


class TestEngineTensorParallel:
    @pytest.mark.parametrize("family", ["dense", "moe", "hybrid"])
    def test_engine_matches_generate_tp2(self, family):
        """Greedy engine decode with sharded packed params on the tp=2
        mesh == N independent generate calls (same dist)."""
        params, cfg, dist = _compiled_tp2(family)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab, size=n).tolist()
                   for n in (8, 5)]
        eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32, dist=dist)
        rids = [eng.submit(p, 4) for p in prompts]
        eng.run()
        for rid, p in zip(rids, prompts):
            want = np.asarray(
                generate(params, cfg, jnp.asarray([p], jnp.int32), 4,
                         dist=dist))[0].tolist()
            assert eng.requests[rid].status == "finished"
            assert eng.requests[rid].tokens == want

    def test_engine_step_traces_once_sharded(self, monkeypatch):
        """Admission/eviction/slot reuse never retrace the SHARDED batched
        decode step."""
        params, cfg, dist = _compiled_tp2("dense")
        traces = []

        def counting(fn):
            def wrapped(*a, **kw):
                traces.append(1)
                return fn(*a, **kw)
            return wrapped

        monkeypatch.setattr(T, "decode_step_ragged",
                            counting(T.decode_step_ragged))
        E._JIT_CACHE.clear()
        eng = ServingEngine(params, cfg, n_slots=2, seq_cap=32, dist=dist)
        rng = np.random.RandomState(1)
        for i, n in enumerate((8, 5, 12)):
            eng.submit(rng.randint(1, cfg.vocab, size=n).tolist(), 4,
                       arrival=i)
        eng.run()
        assert eng.stats["finished"] == 3
        assert len(traces) == 1
