"""Pattern/connectivity CONV layers through the tap-gather path:
``pattern_lower`` round-trips, packed-vs-masked-dense parity on both tiny
conv archs (incl. connectivity pruning and the 5x5 kernel), reorder
parity through ``sparse_conv2d_pattern``, the compile_model routing
(a pattern pick compiles to a sparse producer, never the logged dense
fallback), and the mapper -> compile regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcs as BCS
from repro.core import mapper_rule as MR
from repro.core import regularity as R
from repro.core import reweighted as RW
from repro.kernels import ops
from repro.models import convnet as C
from repro.serve.compile import (CompileSpec, compile_model,
                                 compiled_summary)
from repro.train.trainer import apply_masks

PATTERN_SPEC = [(r"(^|/)(c|pw|dw)\d+/w",
                 RW.SchemeChoice("pattern", connectivity=0.5))]

# Two tap layouts of one weight that pad a filter to different tap degrees
# contract it in dots of different length, and XLA may then sum in another
# order: the same conv to fp32 rounding (a few ulp of outputs of order 1),
# not bitwise.  Observed differences stay below 4e-6.
TAP_RTOL = 1e-5


def pattern_case(P, Q, kh=3, kw=3, connectivity=0.0, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (P, Q, kh, kw),
                          jnp.float32) * 0.1
    if (kh, kw) == (3, 3):
        mask = R.pattern_mask(w, connectivity_rate=connectivity)
    else:
        mask = R.connectivity_mask(w, rate=connectivity)
    return w * mask, mask


def dense_conv(wm, x, stride):
    kernel = wm.transpose(2, 3, 1, 0)            # (kh,kw,Q,P)
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# -- pattern_lower: round-trip + structure -----------------------------------

@pytest.mark.parametrize("connectivity,group,n_bins,reorder", [
    (0.0, 1, 4, True),
    (0.5, 1, 4, True),
    (0.5, 1, 1, True),
    (0.5, 4, 4, True),
    (0.5, 1, 4, False),
])
def test_pattern_lower_round_trip(connectivity, group, n_bins, reorder):
    """TapLayout.to_dense reconstructs exactly the lowered masked weight."""
    wm, mask = pattern_case(16, 8, connectivity=connectivity, seed=2)
    tap = BCS.pattern_lower(wm, mask, group=group, n_bins=n_bins,
                            reorder=reorder)
    np.testing.assert_array_equal(tap.to_dense(),
                                  BCS.conv_lower(np.asarray(wm)))


def test_pattern_lower_savings_are_executed_taps():
    """4-of-9 patterns without connectivity: every filter keeps exactly
    4*Q taps, so executed savings equal the exact 1 - 4/9 (no padding)."""
    wm, mask = pattern_case(16, 8, seed=1)
    tap = BCS.pattern_lower(wm, mask)
    assert tap.flops_saved == pytest.approx(1 - 4 / 9)
    assert tap.padding_overhead == pytest.approx(1.0)


def test_pattern_lower_drops_globally_dead_rows():
    """A channel pruned in EVERY filter leaves the alive band entirely —
    its taps are never gathered into the kernel input."""
    wm, mask = pattern_case(8, 8, seed=3)
    mask = np.array(mask)
    mask[:, 2] = 0.0                              # kill channel 2 everywhere
    wm = np.asarray(wm) * mask
    tap = BCS.pattern_lower(wm, mask)
    K = tap.shape[0]
    dead = {(t * 8 + 2) for t in range(9)}        # rows (i*Kw+j)*Q + q, q=2
    assert set(np.asarray(tap.alive).tolist()).isdisjoint(dead)
    assert tap.n_alive <= K - 9


# -- tap-gather kernel: parity vs the masked lax.conv oracle -----------------

@pytest.mark.parametrize("P,Q,kh,kw,stride,conn", [
    (32, 16, 3, 3, 1, 0.0),      # pure 4-of-9 patterns
    (32, 16, 3, 3, 2, 0.5),      # patterns + connectivity, stride 2
    (64, 32, 5, 5, 2, 0.5),      # non-3x3: connectivity-only, stride 2
    (32, 3, 3, 3, 1, 0.0),       # 3-channel stem (block-untileable)
])
def test_sparse_conv2d_pattern_matches_dense_conv(P, Q, kh, kw, stride,
                                                  conn):
    wm, mask = pattern_case(P, Q, kh, kw, connectivity=conn)
    tap = ops.pack_taps(wm, mask, n_bins=4)
    assert tap.flops_saved > 0.3                  # real executed-tap savings
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, Q), jnp.float32)
    y = ops.sparse_conv2d_pattern(x, tap, kh=kh, kw=kw, stride=stride)
    y_ref = dense_conv(wm, x, stride)
    assert y.shape == y_ref.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_bins", [1, 2, 4])
def test_sparse_conv2d_pattern_reorder_bit_identity(n_bins):
    """Degree-binned tap layouts compute the same conv as the unreordered
    layout — the epilogue gather relabels filters.  Bitwise equality holds
    only while each filter keeps its padded tap degree: binning pads a
    filter to its bin's max instead of the global max, so its single dot
    has another reduction length and XLA may sum in another order, so the
    outputs agree to fp32 rounding (``TAP_RTOL``).  One bin pads every
    filter to the global max, as the unreordered layout does: bitwise."""
    wm, mask = pattern_case(64, 32, connectivity=0.5, seed=3)
    plain = ops.pack_taps(wm, mask, reorder=False)
    reord = ops.pack_taps(wm, mask, reorder=True, n_bins=n_bins)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 9, 32), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(5), (64,), jnp.float32)
    y0 = ops.sparse_conv2d_pattern(x, plain, kh=3, kw=3, stride=2, bias=b,
                                   act="relu")
    y1 = ops.sparse_conv2d_pattern(x, reord, kh=3, kw=3, stride=2, bias=b,
                                   act="relu")
    if n_bins == 1:
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    else:
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=TAP_RTOL, atol=TAP_RTOL)
    assert reord.L_effective <= plain.L_max


@pytest.mark.parametrize("P,Q,kh,kw,stride,conn", [
    (32, 16, 3, 3, 1, 0.0),      # pure 4-of-9 patterns
    (32, 16, 3, 3, 2, 0.5),      # patterns + connectivity, stride 2
    (64, 32, 5, 5, 2, 0.5),      # non-3x3: connectivity-only, stride 2
])
def test_implicit_tap_gather_parity(P, Q, kh, kw, stride, conn):
    """Implicit tap-gather (straight off the padded feature map — no
    patch tensor, no alive band) matches the materialized tap path within
    fp32 tolerance and the masked ``lax.conv`` oracle."""
    wm, mask = pattern_case(P, Q, kh, kw, connectivity=conn)
    tap = ops.pack_taps(wm, mask)
    assert tap.k_full is not None                 # pack-time implicit aux
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 11, 9, Q), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (P,), jnp.float32)
    y_imp = ops.sparse_conv2d_pattern(x, tap, kh=kh, kw=kw, stride=stride,
                                      bias=b, act="relu", implicit=True)
    y_mat = ops.sparse_conv2d_pattern(x, tap, kh=kh, kw=kw, stride=stride,
                                      bias=b, act="relu", implicit=False)
    np.testing.assert_allclose(np.asarray(y_imp), np.asarray(y_mat),
                               rtol=1e-5, atol=1e-5)
    y_ref = jax.nn.relu(dense_conv(wm, x, stride) + b)
    np.testing.assert_allclose(np.asarray(y_imp), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_implicit_tap_gather_legacy_layout_without_k_full():
    """Layouts packed before the ``k_full`` aux existed still run
    implicit: ``bin_k_full`` reconstructs ``alive[t_idx]`` on the fly."""
    import dataclasses

    wm, mask = pattern_case(32, 16, connectivity=0.5)
    tap = ops.pack_taps(wm, mask, use_cache=False)
    legacy = dataclasses.replace(tap, k_full=None)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 10, 10, 16),
                          jnp.float32)
    y = ops.sparse_conv2d_pattern(x, tap, kh=3, kw=3, implicit=True)
    y_legacy = ops.sparse_conv2d_pattern(x, legacy, kh=3, kw=3,
                                         implicit=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_legacy))


def test_pack_taps_default_bins_shrink_connectivity_padding():
    """The raised default (8 bins) must price connectivity-bearing tap
    layouts at strictly less padding than the old 4-bin default — the
    ROADMAP measurement this PR locks in."""
    wm, mask = pattern_case(128, 64, connectivity=0.5, seed=9)
    b4 = ops.pack_taps(wm, mask, n_bins=4)
    b8 = ops.pack_taps(wm, mask)                  # default
    assert b8.n_bins == 8
    assert b8.padding_overhead < b4.padding_overhead
    # the same conv regardless of binning: 4 and 8 bins pad filters to
    # different tap degrees, so each filter's dot has another reduction
    # length — equal to fp32 rounding, not bitwise (see TAP_RTOL)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 8, 8, 64), jnp.float32)
    y4 = ops.sparse_conv2d_pattern(x, b4, kh=3, kw=3)
    y8 = ops.sparse_conv2d_pattern(x, b8, kh=3, kw=3)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y8),
                               rtol=TAP_RTOL, atol=TAP_RTOL)


def test_pack_taps_cache_key_separation():
    """A TapLayout and a PackedLayout of the same bytes never collide in
    the pack cache, and different tap knobs get distinct entries."""
    wm, mask = pattern_case(16, 8, connectivity=0.5)
    a = ops.pack_taps(wm, mask, n_bins=4)
    b = ops.pack_taps(wm, mask, n_bins=2)
    c = ops.pack_taps(wm, mask, n_bins=4)
    assert a is c and a is not b
    assert a.bin_degrees != b.bin_degrees or len(a.values) != len(b.values)


# -- compile_model: routing + whole-net parity -------------------------------

def _compiled_pattern_net(arch, seed=0):
    params = C.convnet_init(jax.random.PRNGKey(seed), arch,
                            dtype=jnp.float32)
    masks = RW.masks_for_spec(params, PATTERN_SPEC)
    pm = apply_masks(params, masks)
    exec_params, report = compile_model(pm, masks, PATTERN_SPEC)
    return pm, exec_params, report


@pytest.mark.parametrize("arch,expect_packed", [
    # every non-depthwise conv packs — including the 3-channel stem the
    # block producer cannot tile and the 1x1 / 5x5 connectivity layers
    (C.VGG_TINY, {"c1", "c2", "c3", "c4", "c5", "c6"}),
    (C.MOBILE_TINY, {"c1", "pw2", "pw3", "c4"}),
])
def test_pattern_net_packed_forward_parity(arch, expect_packed):
    pm, exec_params, report = _compiled_pattern_net(arch)
    packed = {r["path"].split("/")[0] for r in report if r["packed"]}
    assert packed == expect_packed, compiled_summary(report)
    assert all(r["kind"] == "pattern_conv" for r in report if r["packed"])
    x, _ = C.synthetic_images(jax.random.PRNGKey(2), 4)
    y_ref = C.convnet_apply(pm, x, arch)
    y = C.convnet_apply(exec_params, x, arch)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_pattern_net_depthwise_still_skips():
    """§5.2.4: a pattern choice on a depthwise conv skips with the logged
    reason, never tap-lowers."""
    _, exec_params, report = _compiled_pattern_net(C.MOBILE_TINY)
    by_name = {r["path"].split("/")[0]: r for r in report}
    for dw_name in ("dw2", "dw3"):
        assert not by_name[dw_name]["packed"]
        assert "depthwise" in by_name[dw_name]["reason"]
        assert "packed" not in exec_params[dw_name]


def test_pattern_net_drop_dense():
    """keep_dense=False works for tap layouts: packed layers lose "w" and
    the net still runs through the tap-gather kernel."""
    params = C.convnet_init(jax.random.PRNGKey(0), C.VGG_TINY,
                            dtype=jnp.float32)
    masks = RW.masks_for_spec(params, PATTERN_SPEC)
    pm = apply_masks(params, masks)
    exec_params, report = compile_model(
        pm, masks, PATTERN_SPEC, spec=CompileSpec(keep_dense=False))
    for r in report:
        name = r["path"].split("/")[0]
        assert ("w" in exec_params[name]) == (not r["packed"])
    x, _ = C.synthetic_images(jax.random.PRNGKey(1), 2)
    y_ref = C.convnet_apply(pm, x, C.VGG_TINY)
    y = C.convnet_apply(exec_params, x, C.VGG_TINY)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_pattern_on_non_conv_weight_skips():
    """pattern mapped onto a 2-D FC weight must skip, not tap-lower."""
    params = {"fc": {"w": jnp.ones((64, 64), jnp.float32)}}
    _, report = compile_model(
        params, None, [(r"fc/w", RW.SchemeChoice("pattern"))])
    assert not report[0]["packed"]
    assert "conv weight" in report[0]["reason"]


# -- mapper regression: a pattern pick compiles sparse, not dense ------------

def test_mapper_pattern_pick_compiles_to_sparse_producer():
    """Remark 1 end to end: the rule mapper's hard-dataset pattern pick
    must reach the tap-gather producer — pre-PR it fell through
    compile_model as the logged 'no block scheme mapped' dense fallback."""
    arch_specs = [("c2", 16, 32, 64, 3, 3, False),
                  ("c3", 16, 64, 64, 3, 3, False)]
    layers = MR.conv_layers(arch_specs)
    spec, rep = MR.map_rules(layers, dataset_hard=True)
    assert all(r["scheme"] == "pattern" for r in rep)
    params = {
        "c2": {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 32, 3, 3),
                                      jnp.float32) * 0.1},
        "c3": {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 64, 3, 3),
                                      jnp.float32) * 0.1},
    }
    masks = RW.masks_for_spec(params, spec)
    pm = apply_masks(params, masks)
    exec_params, report = compile_model(pm, masks, spec)
    assert all(r["packed"] for r in report), compiled_summary(report)
    assert all(r["kind"] == "pattern_conv" for r in report)
    assert all(r["flops_saved"] > 0.3 for r in report)
    from repro.core.packed import TapLayout
    assert isinstance(exec_params["c2"]["packed"], TapLayout)


def test_mapper_pattern_latency_uses_executed_cost():
    """The rule report prices a pattern pick at the executed-tap fraction
    (taps/9 x surviving kernels), not at the raw 4/9 density."""
    from repro.core.latency_model import matmul_latency, pattern_executed_frac
    convs = MR.conv_layers([("c1", 28, 64, 64, 3, 3, False)])
    _, rep = MR.map_rules(convs, dataset_hard=True)
    ld = convs[0]
    conn = 1 - 4 / 9
    frac = pattern_executed_frac(conn)
    want = matmul_latency(ld.M, ld.K, ld.N, scheme="pattern",
                          compression=1 / frac, executed_frac=frac)
    assert rep[0]["latency_s"] == pytest.approx(want)
    # executed cost is strictly below the raw-density pricing
    raw = matmul_latency(ld.M, ld.K, ld.N, scheme="pattern",
                         compression=9 / 4, executed_frac=4 / 9)
    assert rep[0]["latency_s"] < raw
