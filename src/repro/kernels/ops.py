"""Jit'd dispatch layer over the sparse executor paths.

``sparse_linear`` picks the execution strategy the compiler framework
would emit for a pruned layer:
  PackedLayout         -> Pallas bsr_matmul (skips pruned blocks; ragged
                          M is zero-padded inside the kernel wrapper, so
                          the packed path never falls back to dense)
  dense weight (+mask) -> masked-dense matmul (mask fused by XLA)

``sparse_expert_linear`` is the batched variant for MoE expert stacks: a
``jax.vmap`` of the packed kernel over the leading expert axis, so the
three expert GEMMs (gate/up/down) execute through the same sparse path as
every other projection.

``sparse_conv2d`` is the CONV consumer: im2col patch extraction (tap-major
(kh, kw, q) feature order, matching ``core.bcs.conv_lower``) flattens the
convolution to one GEMM that dispatches through the same
``bsr_matmul_packed`` — block-punched conv masks (paper §4.1.2) become
whole dead BCS blocks, so pruned taps are skipped, not multiplied by zero.
Stride/padding are handled in the patch extraction; bias + activation fuse
into the kernel epilogue exactly as for ``sparse_linear``.

``sparse_conv2d_pattern`` is the pattern/connectivity CONV consumer: the
same im2col patch extraction, restricted to the layout's ``alive`` band,
then the Pallas tap-gather kernel (``bsr_matmul.tap_gather_conv``) — each
output filter multiplies ONLY its surviving taps, so 4-of-9 pattern masks
and connectivity-pruned kernels execute sparsely instead of falling back
to masked-dense.

Both conv consumers take ``implicit=`` (default None = auto): the implicit
mode skips the patch extraction entirely and runs the implicit-GEMM
kernels (``bsr_conv2d_implicit`` / ``tap_gather_conv_implicit``), which
gather input rows inside the kernel — the ``B*Ho*Wo*Kh*Kw*C`` patch tensor
never exists in HBM.  Auto-selection is by patch-tensor size: implicit
when the patch would be a real blow-up (kh*kw > 1) at least
``_IMPLICIT_MIN_PATCH_BYTES`` big (and, for the BCS path, the packing
block never straddles kernel taps, i.e. bk | Cin).  The materialized path
stays the parity oracle — the two are bit-identical for the BCS path and
fp32-close for taps.

``pack`` / ``pack_taps`` are the host-side codegen steps: they convert a
pruned weight into a ``core.packed.PackedLayout`` (block schemes) or
``core.packed.TapLayout`` (pattern schemes) — the two interchange formats
every sparse consumer shares — optionally degree-sorted/binned
(``reorder``) so the padded column/tap degree L drops toward the mean.

Cache-key contract: results are memoized on a blake2b content digest of
(layout kind, w bytes, mask bytes, w shape+dtype, block-or-group, reorder,
n_bins, quantization spec).  ``pack``/``pack_taps`` take ``value_dtype``
("int8") + ``scale_granularity`` to emit quantized layouts
(``core.quant``): the float pack is produced (or fetched) first — so a
quantized pack warms/reuses the float entry — then quantized and cached
under its own key.  Every knob that changes the produced layout is part of
the key,
so reordered and unreordered packs, different bin counts, block shapes, or
tap-group sizes of the SAME weights can never collide; entries are evicted
LRU under both a count and a byte bound (configurable via
``configure_pack_cache`` / REPRO_PACK_CACHE_MAX{,_BYTES}, every eviction
logged, occupancy + hit/miss counters in ``pack_cache_stats``).  Cached
layouts are frozen — the same instance is handed to every caller."""
from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bcs as BCS
from repro.core import quant as QUANT
from repro.core.packed import PackedLayout
from repro.kernels.bsr_matmul import (bsr_conv2d_implicit, bsr_matmul_packed,
                                      conv_geometry, refusal_here,
                                      tap_gather_conv_implicit,
                                      tap_gather_conv_packed)
from repro.kernels import ref

# auto-selection floor for the implicit-GEMM conv mode: below this the
# patch tensor is too small for its HBM blow-up to matter and the
# materialized path's plain strided slices win on launch simplicity
_IMPLICIT_MIN_PATCH_BYTES = 1 << 20
# auto-selection ceiling: the implicit kernels pin one whole padded image
# in VMEM (x BlockSpec (1, Hp*Wp, C)), so auto never picks them when that
# block would not comfortably fit the ~16 MiB of a v5e core — explicit
# implicit=True can still force it (e.g. in interpret mode)
_IMPLICIT_MAX_IMAGE_BYTES = 8 << 20

_log = logging.getLogger("repro.kernels.ops")

_PACK_CACHE: OrderedDict = OrderedDict()
# entry cap and byte bound (values + k_idx + nnz), evicted LRU: a
# count-only bound would happily pin GBs of packed multi-MB projections
# for the process lifetime, and an unbounded cache in a long-lived serving
# process sweeping many layouts grows without bound.  Configurable via
# ``configure_pack_cache`` or the REPRO_PACK_CACHE_MAX{,_BYTES} env vars;
# every eviction is logged.
_PACK_CACHE_MAX = int(os.environ.get("REPRO_PACK_CACHE_MAX", "256"))
_PACK_CACHE_MAX_BYTES = int(
    os.environ.get("REPRO_PACK_CACHE_MAX_BYTES", str(256 << 20)))
_PACK_CACHE_BYTES = 0
_PACK_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _entry_bytes(layout: PackedLayout) -> int:
    leaves = jax.tree_util.tree_leaves(layout)
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)


def configure_pack_cache(max_entries=None, max_bytes=None) -> dict:
    """Set the pack-cache bounds (None keeps the current value), evicting
    down immediately if the new bounds are tighter.  Returns the active
    config merged with ``pack_cache_stats()``."""
    global _PACK_CACHE_MAX, _PACK_CACHE_MAX_BYTES
    if max_entries is not None:
        _PACK_CACHE_MAX = max(1, int(max_entries))
    if max_bytes is not None:
        _PACK_CACHE_MAX_BYTES = max(1, int(max_bytes))
    _evict_to_bounds()
    return {"max_entries": _PACK_CACHE_MAX,
            "max_bytes": _PACK_CACHE_MAX_BYTES, **pack_cache_stats()}


def pack_cache_stats() -> dict:
    """Current occupancy + lifetime hit/miss/eviction counters."""
    return {"entries": len(_PACK_CACHE), "bytes": _PACK_CACHE_BYTES,
            **_PACK_CACHE_STATS}


def _evict_to_bounds():
    """Evict LRU entries past the bounds, logging each (a serving process
    that evicts constantly needs a bigger cache — the log is the signal)."""
    global _PACK_CACHE_BYTES
    while (len(_PACK_CACHE) > _PACK_CACHE_MAX
           or _PACK_CACHE_BYTES > _PACK_CACHE_MAX_BYTES) \
            and len(_PACK_CACHE) > 1:
        key, evicted = _PACK_CACHE.popitem(last=False)
        eb = _entry_bytes(evicted)
        _PACK_CACHE_BYTES -= eb
        _PACK_CACHE_STATS["evictions"] += 1
        _log.info(
            "pack cache evict %s... (%.1f KiB) -> %d entr%s / %.1f MiB "
            "held (caps: %d entries / %.0f MiB)", key[:12], eb / 1024,
            len(_PACK_CACHE), "y" if len(_PACK_CACHE) == 1 else "ies",
            _PACK_CACHE_BYTES / 2**20, _PACK_CACHE_MAX,
            _PACK_CACHE_MAX_BYTES / 2**20)


def _digest(w: np.ndarray, mask: np.ndarray, block, reorder, n_bins,
            kind="bcs", conv=None, quant=None, n_shards=0) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(str((kind, w.shape, str(w.dtype), block, bool(reorder),
                  int(n_bins), conv, quant, int(n_shards))).encode())
    h.update(np.ascontiguousarray(w).tobytes())
    h.update(np.ascontiguousarray(mask).tobytes())
    return h.hexdigest()


def _cache_put(key, out):
    """Insert a packed layout, then evict LRU entries past the bounds."""
    global _PACK_CACHE_BYTES
    _PACK_CACHE[key] = out
    _PACK_CACHE_BYTES += _entry_bytes(out)
    _PACK_CACHE_STATS["misses"] += 1
    _evict_to_bounds()


def _quant_spec(value_dtype, scale_granularity):
    """Normalize the (value_dtype, scale_granularity) pair for the cache
    digest: None (float pack) or a ('int8', granularity) tuple."""
    if value_dtype is None:
        return None
    return (str(value_dtype), str(scale_granularity))


def pack(w, mask, block=(128, 128), *, reorder=False, n_bins=4, conv=None,
         value_dtype=None, scale_granularity="block",
         n_shards=0, use_cache=True) -> PackedLayout:
    """Host-side packing of a pruned weight into the kernel layout.

    Returns a ``PackedLayout``.  With ``reorder`` the block columns are
    degree-sorted and split into ``n_bins`` bins (see
    ``core.bcs.pack_csc_reordered``); without it the layout is a single bin
    in original column order, bit-identical to the historical uniform CSC
    arrays.  ``conv=(kh, kw, cin)`` marks an im2col-lowered conv weight:
    the static K-block -> (dy, dx, c0) offset table
    (``core.bcs.conv_tap_table``) is attached as ``conv_taps`` aux so the
    implicit-GEMM kernel can gather from the feature map directly; the
    geometry is part of the cache digest.  ``value_dtype="int8"`` quantizes
    the packed values symmetrically (``core.quant``) at
    ``scale_granularity`` ("block" or "out"), attaching the fp32 scale
    leaves — the float pack is produced (and cached) first, then quantized.
    ``n_shards > 0`` emits the tensor-parallel layout (degree-balanced
    column shards, see ``core.bcs.shard_columns``); sharding implies the
    degree-sorted producer regardless of ``reorder``, and the shard count
    is part of the cache digest.
    """
    w = np.asarray(w)
    mask = np.asarray(mask)
    qspec = _quant_spec(value_dtype, scale_granularity)
    key = (_digest(w, mask, tuple(block), reorder, n_bins, conv=conv,
                   quant=qspec, n_shards=n_shards)
           if use_cache else None)
    if key is not None and key in _PACK_CACHE:
        _PACK_CACHE.move_to_end(key)
        _PACK_CACHE_STATS["hits"] += 1
        return _PACK_CACHE[key]
    if value_dtype is not None:
        base = pack(w, mask, block, reorder=reorder, n_bins=n_bins,
                    conv=conv, n_shards=n_shards, use_cache=use_cache)
        out = QUANT.quantize_layout(base, value_dtype=value_dtype,
                                    scale_granularity=scale_granularity)
    elif n_shards:
        out = BCS.pack_csc_reordered(w, mask, block, n_bins=n_bins,
                                     n_shards=n_shards)
    elif reorder:
        out = BCS.pack_csc_reordered(w, mask, block, n_bins=n_bins)
    else:
        values, k_idx, nnz, _ = BCS.pack_csc(w, mask, block)
        out = PackedLayout(values=(values,), k_idx=(k_idx,), nnz=nnz,
                           block=tuple(block), shape=tuple(w.shape))
    if conv is not None and out.conv_taps is None:
        kh, kw, cin = conv
        out = dataclasses.replace(
            out, conv_taps=BCS.conv_tap_table(kh, kw, cin, block[0]))
    if key is not None:
        _cache_put(key, out)
    return out


def pack_taps(w, mask, *, group=1, reorder=True, n_bins=8,
              value_dtype=None, scale_granularity="block",
              n_shards=0, use_cache=True):
    """Host-side packing of a pattern/connectivity-pruned conv weight into
    the tap-gather layout.

    Returns a ``core.packed.TapLayout`` (see ``core.bcs.pattern_lower``):
    per-output-filter tap lists over the im2col band, degree-sorted into
    ``n_bins`` bins when ``reorder`` is set.  The default is 8 bins — on
    connectivity-bearing tap layouts the per-filter degrees spread widely,
    and the ROADMAP measurement shows 8 equal-size bins recover ~89% of
    the 1-bin -> ideal padding gap where 4 recover ~75% (pure pattern
    layouts have uniform degrees, so extra bins cost nothing).  Shares the
    pack cache (and its cache-key contract — the layout kind is part of
    the digest, so a TapLayout and a PackedLayout of the same weights
    never collide).  ``value_dtype="int8"`` quantizes the tap values
    (``core.quant``); prefer ``scale_granularity="out"`` for group=1
    layouts, where a per-slot scale would cost 4 bytes per stored value.
    ``n_shards > 0`` emits the tensor-parallel TapLayout (degree-balanced
    filter-group shards; implies ``reorder``).  Layouts of the same weight
    with other knobs compute the same conv, bit-identically only where
    each filter keeps its padded tap degree (see
    ``bsr_matmul.tap_gather_conv_packed``); otherwise to fp32 rounding."""
    w = np.asarray(w)
    mask = np.asarray(mask)
    qspec = _quant_spec(value_dtype, scale_granularity)
    key = (_digest(w, mask, (1, int(group)), reorder, n_bins, kind="taps",
                   quant=qspec, n_shards=n_shards)
           if use_cache else None)
    if key is not None and key in _PACK_CACHE:
        _PACK_CACHE.move_to_end(key)
        _PACK_CACHE_STATS["hits"] += 1
        return _PACK_CACHE[key]
    if value_dtype is not None:
        base = pack_taps(w, mask, group=group, reorder=reorder,
                         n_bins=n_bins, n_shards=n_shards,
                         use_cache=use_cache)
        out = QUANT.quantize_layout(base, value_dtype=value_dtype,
                                    scale_granularity=scale_granularity)
    else:
        out = BCS.pattern_lower(w, mask, group=group, n_bins=n_bins,
                                reorder=reorder or bool(n_shards),
                                n_shards=n_shards)
    if key is not None:
        _cache_put(key, out)
    return out


def clear_pack_cache():
    """Drop every memoized layout (test isolation / memory pressure)."""
    global _PACK_CACHE_BYTES
    _PACK_CACHE.clear()
    _PACK_CACHE_BYTES = 0


def sparse_linear(x, packed: PackedLayout | None = None, w=None, mask=None,
                  bias=None, act="none", bm=None, interpret=None, name=None):
    """x (..., K) -> (..., N) through whichever path applies.

    With ``packed`` (a PackedLayout) the Pallas BCS kernel always runs —
    one launch per degree bin, outputs gathered back to original column
    order (ragged leading dims are flattened; ragged M is padded inside
    ``bsr_matmul``), each launch named after ``name`` (see
    ``bsr_matmul``).  ``bm=None`` takes the M tile from the shapes
    (``bsr_matmul.m_tile``); ``interpret=None`` auto-detects the
    backend."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    if packed is not None:
        y = bsr_matmul_packed(x2, packed, bias=bias, bm=bm, act=act,
                              interpret=interpret, name=name)
    else:
        y = ref.masked_matmul_ref(
            x2, w, mask if mask is not None else jnp.ones_like(w),
            bias=bias, act=act)
    return y.reshape(*lead, y.shape[-1])


def im2col(x, kh, kw, stride=1, padding="SAME"):
    """x (B, H, W, C) -> patches (B, Ho, Wo, kh*kw*C).

    Feature order is tap-major, channel-minor — feature r = (i*kw + j)*C + c
    reads input channel c at kernel tap (i, j) — the exact row order of
    ``core.bcs.conv_lower``, so ``patches.reshape(-1, kh*kw*C) @ lowered_w``
    is the convolution.  The taps are a tiny unrolled loop (<= kh*kw slices)
    over one padded copy; XLA fuses the strided slices.  This is the
    MATERIALIZED path — it allocates the full ``B*Ho*Wo*kh*kw*C`` patch
    tensor; the implicit kernels fold this gather into their grid instead."""
    B, H, W, C = x.shape
    ph, pw, Ho, Wo = conv_geometry(H, W, kh, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    taps = [xp[:, i:i + stride * (Ho - 1) + 1:stride,
               j:j + stride * (Wo - 1) + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(taps, axis=-1) if len(taps) > 1 else taps[0]


def patch_bytes(x, kh, kw, stride=1, padding="SAME"):
    """HBM bytes the MATERIALIZED im2col path allocates for its patch
    tensor — what the implicit mode avoids (and what auto-selection and
    the benches' peak-memory accounting are based on)."""
    B, H, W, C = x.shape
    _, _, Ho, Wo = conv_geometry(H, W, kh, kw, stride, padding)
    return B * Ho * Wo * kh * kw * C * x.dtype.itemsize


def _pick_implicit(implicit, x, kh, kw, stride, padding, bk=None):
    """Resolve the ``implicit=`` tri-state: None auto-selects by
    patch-tensor size — implicit when the patch is a real blow-up
    (kh*kw > 1) of at least ``_IMPLICIT_MIN_PATCH_BYTES``, AND the padded
    image block the kernel pins in VMEM stays under
    ``_IMPLICIT_MAX_IMAGE_BYTES``.  The BCS path additionally needs its
    packing block inside one tap (bk | Cin); an explicit
    ``implicit=True`` asserts that instead of silently falling back.
    Auto never picks an implicit kernel the backend refuses
    (``bsr_matmul.refusal_here``: both, on a TPU today)."""
    B, H, W, C = x.shape
    if implicit is None:
        if bk is not None and C % bk:
            return False
        if refusal_here("conv_implicit" if bk is not None
                        else "tap_implicit"):
            return False
        ph, pw, _, _ = conv_geometry(H, W, kh, kw, stride, padding)
        image_bytes = ((H + ph[0] + ph[1]) * (W + pw[0] + pw[1]) * C
                       * x.dtype.itemsize)
        return (kh * kw > 1
                and image_bytes <= _IMPLICIT_MAX_IMAGE_BYTES
                and patch_bytes(x, kh, kw, stride, padding)
                >= _IMPLICIT_MIN_PATCH_BYTES)
    if implicit and bk is not None:
        assert C % bk == 0, (
            f"implicit conv needs bk={bk} | Cin={C} (K-blocks must not "
            f"straddle kernel taps)")
    return bool(implicit)


def sparse_conv2d(x, packed: PackedLayout, *, kh, kw, stride=1,
                  padding="SAME", bias=None, act="none", bm=None,
                  interpret=None, implicit=None):
    """x (B, H, W, Cin) * packed conv weight -> (B, Ho, Wo, Cout).

    ``packed`` is the PackedLayout of the im2col-lowered (Kh*Kw*Q, P) conv
    weight (``serve.compile.compile_model`` on a block-punched conv layer).
    The conv runs as ONE sparse GEMM: pruned kernel-position blocks are
    never read nor multiplied, and bias + activation fuse into the kernel
    epilogue.  ``implicit`` picks the x-operand strategy (None = auto by
    patch size, see ``_pick_implicit``): the materialized path extracts
    the full im2col patch tensor first; the implicit path
    (``bsr_conv2d_implicit``) gathers input rows inside the kernel and
    never allocates it — bit-identical outputs either way.  Depthwise
    convs are never packed (compile_model skips them with a logged
    reason), so this path only sees full convolutions."""
    B, H, W, C = x.shape
    assert packed.shape[0] == kh * kw * C, (
        f"layout K={packed.shape[0]} != kh*kw*Cin={kh * kw * C}")
    if packed.n_shards:
        # the implicit kernels are single-device (their epilogue gathers
        # per-launch); sharded conv layouts run the materialized GEMM,
        # whose bsr_matmul_packed dispatch handles the shard merge
        assert not implicit, "implicit conv does not support sharded layouts"
        implicit = False
    if _pick_implicit(implicit, x, kh, kw, stride, padding,
                      bk=packed.block[0]):
        return bsr_conv2d_implicit(x, packed, kh=kh, kw=kw, stride=stride,
                                   padding=padding, bias=bias,
                                   bm=bm or 128,
                                   act=act, interpret=interpret)
    patches = im2col(x, kh, kw, stride, padding)
    _, Ho, Wo, K = patches.shape
    y = bsr_matmul_packed(patches.reshape(B * Ho * Wo, K), packed,
                          bias=bias, bm=bm, act=act, interpret=interpret)
    return y.reshape(B, Ho, Wo, y.shape[-1])


def sparse_conv2d_pattern(x, tap, *, kh, kw, stride=1, padding="SAME",
                          bias=None, act="none", bm=128, interpret=None,
                          implicit=None):
    """x (B, H, W, Cin) * tap-lowered conv weight -> (B, Ho, Wo, Cout).

    ``tap`` is the ``core.packed.TapLayout`` of a pattern/connectivity-
    pruned conv layer (``serve.compile.compile_model`` routes 4-D
    ``pattern``-scheme masks here).  Materialized mode: im2col + the
    Pallas tap-gather kernel — the patch matrix is first gathered down to
    ``tap.alive`` (rows pruned in EVERY filter are never materialized),
    then each filter group contracts only its own surviving taps (one
    launch per degree bin), bias + activation fused in the kernel step.
    Implicit mode (``implicit=True`` or auto by patch size): the
    tap-gather runs straight off the padded feature map
    (``tap_gather_conv_implicit``) — neither the patch tensor nor the
    alive band is ever allocated.  Bit-parity oracle: the masked dense
    ``lax.conv`` kept in ``models.convnet``."""
    B, H, W, C = x.shape
    assert tap.shape[0] == kh * kw * C, (
        f"layout K={tap.shape[0]} != kh*kw*Cin={kh * kw * C}")
    if tap.n_shards:
        # sharded tap layouts run materialized (see sparse_conv2d)
        assert not implicit, "implicit conv does not support sharded layouts"
        implicit = False
    if _pick_implicit(implicit, x, kh, kw, stride, padding):
        return tap_gather_conv_implicit(x, tap, kh=kh, kw=kw, stride=stride,
                                        padding=padding, bias=bias, bm=bm,
                                        act=act, interpret=interpret)
    patches = im2col(x, kh, kw, stride, padding)
    _, Ho, Wo, K = patches.shape
    band = patches.reshape(B * Ho * Wo, K)
    if tap.n_alive < K:
        # nonzero() is sorted, so a full-size alive index is exactly
        # arange(K): only gather when rows are actually dead everywhere
        band = jnp.take(band, tap.alive, axis=1)
    y = tap_gather_conv_packed(band, tap, bias=bias, bm=bm, act=act,
                               interpret=interpret)
    return y.reshape(B, Ho, Wo, y.shape[-1])


def sparse_expert_linear(x, packed: PackedLayout, bias=None, act="none",
                         bm=None, interpret=None):
    """Batched per-expert sparse GEMM: x (E, M, K) -> (E, M, N).

    ``packed`` carries a leading expert axis on every leaf (values
    (E, nb_b, L_b, bk, bn), perm (E, Nb), ...) — exactly what
    ``serve.compile._pack_stacked`` emits for MoE expert weights.  The
    packed kernel is ``jax.vmap``-ed over that axis, so all experts run as
    one batched launch per bin instead of E Python-level calls.

    Expert layouts are never column-sharded: under tensor parallelism the
    EXPERT axis is the shard axis (``distributed.sharding`` attaches the
    mesh "model" ``NamedSharding`` to the leading leaf dim for free), so
    a column-sharded expert layout here is a compile bug."""
    assert packed.n_shards == 0, (
        "MoE expert layouts shard along the expert axis, not block "
        "columns; serve.compile must exempt moe/ paths from CompileSpec.tp")

    def _fn(xe, le, be=None):
        return bsr_matmul_packed(xe, le, bias=be, bm=bm, act=act,
                                 interpret=interpret)

    if bias is not None:
        return jax.vmap(_fn)(x, packed, bias)
    return jax.vmap(lambda xe, le: _fn(xe, le))(x, packed)


def flops_saved(packed: PackedLayout) -> float:
    """Fraction of dense matmul FLOPs the kernel actually skips.

    The uniform CSC layout pads every block column of a bin to the bin's
    max degree, so the executed fraction is ``executed_blocks / (Kb*Nb)``
    — NOT the raw block density: imbalanced column degrees execute padding
    blocks.  Reordering/binning shrinks exactly this padding."""
    return packed.flops_saved


def padding_overhead(packed: PackedLayout) -> float:
    """Executed-block overhead of uniform padding vs ideal CSC."""
    return packed.padding_overhead
