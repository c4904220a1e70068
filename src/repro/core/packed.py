"""Interchange formats for sparse execution: PackedLayout and TapLayout.

Every sparse consumer in the repo (``serve.compile.compile_model``,
``kernels.ops``, ``kernels.bsr_matmul``, ``models.layers.linear``, the
batched MoE expert path in ``models.moe`` and the conv paths in
``models.convnet``) produces/consumes one of these two objects instead of
ad-hoc ``{"values", "k_idx"}`` dicts.  Both are registered pytrees, so
layouts live inside param trees, survive ``jax.jit``/``lax.scan`` over
stacked layer axes (leaves may carry leading stack dims; geometry is static
aux data), and new consumers become layout *producers*, not new dict
formats.

``PackedLayout`` (paper §4.3 Fig 4, CSC orientation — see ``core.bcs``) is
the block-sparse format: the dense weight is (K, N); each block COLUMN j
(output tile) stores the list of surviving K-block indices.  With *row
reordering for load balance* (the paper's Fig 4 reorder step), block
columns are sorted by degree and split into ``n_bins`` contiguous bins,
each padded only to its OWN max degree — so the executed column degree
drops toward the mean instead of every column paying the global max.
``perm``/``inv_perm`` carry the (inverse) permutation; the executor gathers
outputs back to original column order (bit-identical results, since
per-column accumulation order is untouched).

``TapLayout`` is the fine-grained sibling for pattern/connectivity-pruned
convolutions (paper §2.1.1 / PatDNN, PCONV): pattern masks carry no block
structure — each (filter, channel) kernel keeps its own 4-of-9 tap set —
so the skippable unit is a single row ("tap") of the im2col band, not a
(bk, bn) block.  ``core.bcs.pattern_lower`` builds it; the Pallas
``kernels.bsr_matmul.tap_gather_conv`` kernel consumes it.  The two layouts
share the same structural conventions (per-bin leaf tuples, degree
sort + binning, perm/inv_perm over the output axis, fused-epilogue bias
helpers), so ``serve.compile`` and the model dispatch treat "packed" as one
concept and pick the executor by layout type.

Quantized values (``core.quant``): either layout may carry its values as
symmetric-scale int8 with an extra per-bin ``scales`` leaf tuple (fp32).
Scale granularity is encoded in the scale shapes (see the dataclass docs);
the kernels dequantize in-kernel before the fp32-accumulated dot, so the
executed result equals the dequantized dense reference.  All-zero groups
store scale 0 (nothing to recover).  ``to_dense`` on a quantized layout
returns the DEQUANTIZED dense weight — the parity oracle for the int8
kernel paths.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np
import jax
import jax.numpy as jnp


def _dequant(values, scale):
    """Host-side dequantize of one bin: int8 values * fp32 scale, the scale
    right-padded with broadcast axes up to the values rank (so every scale
    granularity — per-block, per-column, per-tap-slot, per-filter —
    broadcasts the same way).  Identity when ``scale`` is None."""
    if scale is None:
        return values
    v = np.asarray(values)
    s = np.asarray(scale, np.float32)
    s = s.reshape(s.shape + (1,) * (v.ndim - s.ndim))
    return v.astype(np.float32) * s


# frozen: ops.pack hands out the SAME cached instance to every caller, so a
# mutable layout would let one consumer corrupt the pack cache for all.
# eq=False: the generated __eq__ would compare jax array leaves (ambiguous
# truth value); identity comparison is the meaningful one for layouts.
@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True, eq=False)
class PackedLayout:
    """Uniform-padded BCS/CSC layout, optionally degree-sorted and binned.

    Array leaves (may carry leading stack dims ``...`` = layers / experts):
      values   : tuple of per-bin arrays (..., nb_b, L_b, bk, bn)
      k_idx    : tuple of per-bin arrays (..., nb_b, L_b) int32
      nnz      : (..., Nb) int32 live K-blocks per column, in LAYOUT order
      perm     : (..., Nb) int32 layout position -> original block column,
                 or None when the layout is in original column order
      inv_perm : (..., Nb) int32 original block column -> layout position,
                 or None (identity)
      scales   : None for float values; for int8 values, a tuple of
                 per-bin fp32 arrays — (..., nb_b, L_b) with one symmetric
                 scale per stored block ("block" granularity) or
                 (..., nb_b) with one per block column ("out") — the rank
                 relative to ``values`` encodes the granularity.  All-zero
                 blocks store scale 0.

    Static aux data (hashable; part of the jit cache key):
      block : (bk, bn)
      shape : (K, N) of one dense weight slice
      conv_taps : None for plain GEMM layouts; for im2col-lowered conv
                  layouts, a tuple of (dy, dx, c0) per K-block (built by
                  ``core.bcs.conv_tap_table`` at pack time) — the static
                  offset table ``kernels.bsr_matmul.bsr_conv2d_implicit``
                  uses to gather its x tile straight from the padded
                  feature map instead of a materialized patch tensor.
      n_shards : 0 for a single-device layout.  When S > 0 the layout is
                 tensor-parallel over block COLUMNS: every per-bin leaf
                 carries a shard axis as the LAST stack dim — ``values[b]``
                 is (..., S, nb_b, L_b, bk, bn), ``nnz`` is (..., S, Nb_s)
                 with Nb_s = Nb / S — so layer scans still slice axis 0
                 and the per-layer slice is shard-major for ``jax.vmap`` /
                 ``NamedSharding`` over the mesh "model" axis.  ``perm``
                 becomes (..., S, Nb_s) holding ORIGINAL column ids (the
                 flattened last two axes are a permutation of range(Nb));
                 ``inv_perm`` stays flat (..., Nb) mapping original column
                 -> shard-major layout position, consumed by
                 ``merge_shards``.  Built by ``core.bcs.pack_csc_reordered``
                 with its degree-balanced ``shard_columns`` assignment.

    Padding slots (column degree below the bin max) carry ``k_idx`` 0 and
    all-zero values, so they multiply to nothing; ``nnz`` records the true
    per-column degree for stats and ``to_dense``.
    """

    values: tuple
    k_idx: tuple
    nnz: object
    perm: object = None
    inv_perm: object = None
    block: tuple = (128, 128)
    shape: tuple = (0, 0)
    conv_taps: tuple = None
    scales: tuple = None
    n_shards: int = 0

    # -- pytree protocol -----------------------------------------------------

    def tree_flatten(self):
        """Flatten into (array leaves, static aux) for jax pytree traversal."""
        children = (self.values, self.k_idx, self.nnz, self.perm,
                    self.inv_perm, self.scales)
        return children, (self.block, self.shape, self.conv_taps,
                          self.n_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild a layout from ``tree_flatten`` output (jax protocol)."""
        values, k_idx, nnz, perm, inv_perm, scales = children
        block, shape, conv_taps, n_shards = aux
        return cls(values=values, k_idx=k_idx, nnz=nnz, perm=perm,
                   inv_perm=inv_perm, block=block, shape=shape,
                   conv_taps=conv_taps, scales=scales, n_shards=n_shards)

    # -- static geometry (no device sync) ------------------------------------

    @property
    def Kb(self) -> int:
        """Number of block rows (K // bk)."""
        return self.shape[0] // self.block[0]

    @property
    def Nb(self) -> int:
        """Number of block columns (N // bn)."""
        return self.shape[1] // self.block[1]

    @property
    def n_bins(self) -> int:
        """Number of degree bins (1 for an unreordered layout)."""
        return len(self.values)

    @property
    def Nb_shard(self) -> int:
        """Block columns per shard (= Nb when unsharded)."""
        return self.Nb // max(1, self.n_shards)

    @property
    def bin_sizes(self) -> tuple:
        """Block columns per bin (per shard on a sharded layout)."""
        return tuple(v.shape[-4] for v in self.values)

    @property
    def bin_degrees(self) -> tuple:
        """Padded column degree L_b of each bin."""
        return tuple(v.shape[-3] for v in self.values)

    @property
    def L_max(self) -> int:
        """Worst padded column degree across bins — what every column would
        pay without reordering/binning."""
        return max(self.bin_degrees)

    @property
    def executed_blocks(self) -> int:
        """Blocks the kernel actually multiplies per dense-weight slice:
        sum over bins of nb_b * L_b (padding included), times the shard
        count on a sharded layout (each shard pads to the cross-shard bin
        max, so per-shard padded work is identical by construction)."""
        per_shard = sum(s * d
                        for s, d in zip(self.bin_sizes, self.bin_degrees))
        return per_shard * max(1, self.n_shards)

    @property
    def L_effective(self) -> float:
        """Mean executed column degree under the binned layout; equals
        ``L_max`` for a single unreordered bin."""
        return self.executed_blocks / max(self.Nb, 1)

    @property
    def flops_saved(self) -> float:
        """Fraction of dense matmul FLOPs the kernel skips.  The padded
        layout executes ``executed_blocks`` of Kb*Nb — NOT the raw block
        density: imbalanced column degrees execute padding blocks."""
        return max(0.0, 1.0 - self.executed_blocks / (self.Kb * self.Nb))

    @property
    def value_dtype(self) -> str:
        """Dtype name of the stored values ("int8" on quantized layouts)."""
        return jnp.asarray(self.values[0]).dtype.name

    def bin_scales(self) -> tuple:
        """Per-bin scale arrays, or a tuple of Nones on float layouts —
        what the packed kernel wrappers zip alongside ``values``."""
        if self.scales is None:
            return (None,) * self.n_bins
        return self.scales

    def shard_index_leaves(self) -> tuple:
        """The per-bin index leaves the kernel launch consumes next to
        ``values`` (``k_idx`` here, ``t_idx`` on TapLayout) — lets
        ``kernels.bsr_matmul._sharded_launch`` drive both layouts."""
        return self.k_idx

    # -- data-dependent stats (host sync; report/test time only) -------------

    @property
    def nnzb(self) -> int:
        """Surviving blocks per dense-weight slice (mean over stack dims)."""
        n = np.asarray(self.nnz)
        # trailing layout axes ((Nb,) or (S, Nb_s)) flatten to Nb either way
        per_slice = n.reshape(-1, self.Nb).sum(axis=1)
        return int(round(float(per_slice.mean())))

    @property
    def shard_balance(self) -> float:
        """max/mean executed blocks per shard were each shard padded to its
        OWN bin maxima — the straggler factor ``core.bcs.shard_columns``
        minimizes.  1.0 on unsharded layouts and under perfect balance."""
        if not self.n_shards:
            return 1.0
        from repro.core import bcs
        return bcs.shard_balance(self.nnz, self.bin_sizes)

    @property
    def density(self) -> float:
        """Surviving-block fraction of the Kb x Nb block grid."""
        return self.nnzb / (self.Kb * self.Nb)

    @property
    def padding_overhead(self) -> float:
        """Executed-block overhead of padding vs ideal CSC."""
        return self.executed_blocks / max(self.nnzb, 1)

    # -- helpers -------------------------------------------------------------

    def unpermute_cols(self, y):
        """Gather a (..., M, N) output from layout column order back to the
        original column order (identity when the layout is unreordered).
        Sharded layouts merge per-shard outputs via ``merge_shards``
        instead (the inverse permutation there spans shards)."""
        assert not self.n_shards, "sharded layouts merge via merge_shards"
        if self.inv_perm is None:
            return y
        bn = self.block[1]
        yb = y.reshape(y.shape[:-1] + (self.Nb, bn))
        yb = jnp.take(yb, self.inv_perm, axis=-2)
        return yb.reshape(y.shape)

    def merge_shards(self, y):
        """Merge shard-local outputs (S, ..., M, N/S) — shard axis LEADING,
        as ``jax.vmap`` over the shard axis produces — into the original
        column order (..., M, N).  The flat ``inv_perm`` already maps each
        original column to its shard-major layout position, so one gather
        is both the cross-shard concat and the un-reorder; under jit with
        sharded operands GSPMD turns it into the all-gather epilogue."""
        assert self.n_shards, "merge_shards needs a sharded layout"
        bn = self.block[1]
        y = jnp.moveaxis(y, 0, -2)                  # (..., M, S, N/S)
        yb = y.reshape(y.shape[:-2] + (self.Nb, bn))
        yb = jnp.take(yb, self.inv_perm, axis=-2)
        return yb.reshape(y.shape[:-2] + (self.Nb * bn,))

    def permute_bias(self, bias):
        """Gather a (N,) bias into layout column order for fused epilogues.
        Returns (N,) on unsharded layouts, (S, N/S) on sharded ones."""
        if bias is None or self.perm is None:
            return bias
        bn = self.block[1]
        bb = bias.reshape(self.Nb, bn)
        pb = jnp.take(bb, self.perm, axis=0)        # (Nb, bn) | (S, Nb_s, bn)
        return pb.reshape(pb.shape[:-2] + (-1,))

    def bin_bias(self, bias):
        """Per-bin (nb_b * bn,) bias slices in layout order (or Nones);
        sharded layouts get (S, nb_b * bn) slices (vmap-ready)."""
        if bias is None:
            return (None,) * self.n_bins
        bn = self.block[1]
        pb = self.permute_bias(bias)
        pb = pb.reshape(pb.shape[:-1] + (-1, bn))   # (Nb, bn) | (S, Nb_s, bn)
        out, start = [], 0
        for s in self.bin_sizes:
            sl = pb[..., start:start + s, :]
            out.append(sl.reshape(sl.shape[:-2] + (s * bn,)))
            start += s
        return tuple(out)

    def to_dense(self):
        """Reconstruct the dense (K, N) weight (single-slice layouts only) —
        the test/debug oracle for round-trip identity.  Quantized layouts
        reconstruct the DEQUANTIZED weight (values * scales), which is what
        the in-kernel dequant path must match."""
        S = max(1, self.n_shards)
        want = 4 + (1 if self.n_shards else 0)
        assert self.values[0].ndim == want, \
            "to_dense needs an unstacked layout"
        K, N = self.shape
        bk, bn = self.block
        Kb, Nb = self.Kb, self.Nb
        dense = np.zeros((Kb, Nb, bk, bn),
                         np.float32 if self.scales is not None
                         else np.asarray(self.values[0]).dtype)
        perm = (np.asarray(self.perm).reshape(S, -1)
                if self.perm is not None
                else np.arange(Nb).reshape(S, -1))
        nnz = np.asarray(self.nnz).reshape(S, -1)
        for sh in range(S):
            col = 0
            for vals, kidx, sc in zip(self.values, self.k_idx,
                                      self.bin_scales()):
                vals = np.asarray(_dequant(vals, sc))
                kidx = np.asarray(kidx)
                if self.n_shards:
                    vals, kidx = vals[sh], kidx[sh]
                for j in range(vals.shape[0]):
                    oj = int(perm[sh, col + j])
                    for l in range(int(nnz[sh, col + j])):
                        dense[int(kidx[j, l]), oj] += vals[j, l]
                col += vals.shape[0]
        return dense.transpose(0, 2, 1, 3).reshape(K, N)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True, eq=False)
class LayerSlice:
    """One degree bin's values of a whole layer stack, ``stack``
    (n_layers, nb_b, L_b, bk, bn), standing for its slice at layer
    ``index`` (an int32 scalar).  A layer scan puts it in a layout in place
    of the sliced bin (``layer_views``) and the kernel reads the stack at
    that layer itself, so the layer's weights are never copied out of the
    stack (``kernels.bsr_matmul.bsr_matmul``'s ``layer``)."""

    stack: object
    index: object

    @property
    def shape(self) -> tuple:
        """The layer's bin shape, (nb_b, L_b, bk, bn)."""
        return self.stack.shape[1:]

    def tree_flatten(self):
        """Flatten into (array leaves, no aux) for jax pytree traversal."""
        return (self.stack, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from ``tree_flatten`` output (jax protocol)."""
        return cls(*children)


def _layer_stacked(node) -> bool:
    """A single-device layout stacked over layers alone: values
    (n_layers, nb_b, L_b, bk, bn).  Expert and sharded layouts keep their
    extra axes in the slice and are sliced as before."""
    return (isinstance(node, PackedLayout) and not node.n_shards
            and node.values[0].ndim == 5)


def layer_views(xs):
    """Prepare the per-layer params ``xs`` of a ``lax.scan`` so that no
    layout's weights are sliced: ``(xs', view)``, where ``xs'`` holds each
    layer-stacked layout with its value bins replaced by the layer indices
    ``arange(n_layers)``, and ``view(x_i)``, applied to the scan's slice of
    ``xs'``, puts back every bin as a ``LayerSlice`` of the whole stack at
    that layer (the identity where ``xs`` holds no such layout)."""
    is_layout = lambda n: isinstance(n, PackedLayout)  # noqa: E731

    def indices(n):
        if not _layer_stacked(n):
            return n
        return replace(n, values=tuple(jnp.arange(v.shape[0], dtype=jnp.int32)
                                       for v in n.values))

    def put_back(n, full):
        if not _layer_stacked(full):
            return n
        return replace(n, values=tuple(LayerSlice(v, i) for v, i in
                                       zip(full.values, n.values)))

    def view(x_i):
        return jax.tree_util.tree_map(put_back, x_i, xs, is_leaf=is_layout)

    return jax.tree_util.tree_map(indices, xs, is_leaf=is_layout), view


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True, eq=False)
class TapLayout:
    """Per-filter tap lists over the im2col band — the pattern-conv layout.

    Built by ``core.bcs.pattern_lower`` from a 4-D pattern/connectivity conv
    mask; consumed by ``kernels.bsr_matmul.tap_gather_conv`` via
    ``kernels.ops.sparse_conv2d_pattern``.  The dense object it represents
    is the im2col-lowered conv weight (K, P) with K = Kh*Kw*Q rows ("taps":
    input channel q at kernel position (i, j)) and P output filters.  Each
    GROUP of ``group`` consecutive filters stores the list of taps any of
    its filters survives at; the kernel gathers exactly those rows of the
    patch matrix and contracts them in one step — pruned taps are never
    multiplied, and rows dead for EVERY filter (``alive`` excludes them)
    are never even materialized in the gathered band.

    Array leaves (single-slice — conv layers are not stacked):
      values   : tuple of per-bin arrays (G_b, L_b, group) — the weight of
                 each filter in the group at tap slot l (zero when that
                 filter prunes the tap, and on padding slots)
      t_idx    : tuple of per-bin arrays (G_b, L_b) int32 — tap slot ->
                 row of the ALIVE band (position in ``alive``, not the full
                 K-row band); padding slots point at row 0 with zero values
      k_full   : tuple of per-bin arrays (G_b, L_b) int32 — tap slot ->
                 row of the FULL im2col band (``alive[t_idx]``, i.e.
                 tap*C + channel), precomputed at pack time; the implicit
                 kernel (``tap_gather_conv_implicit``) decomposes it into
                 (dy, dx, c) input offsets so taps gather straight from the
                 padded feature map.  None on legacy layouts (reconstructed
                 on the fly from ``alive``/``t_idx``).
      nnz      : (G,) int32 true tap-degree per group, in LAYOUT order
      alive    : (R,) int32 rows of the full im2col band live for at least
                 one group — the host-side gather that builds the kernel's
                 input band
      perm     : (G,) int32 layout position -> original filter group, or
                 None when unreordered
      inv_perm : (G,) int32 original filter group -> layout position
      scales   : None for float values; for int8 values, a tuple of
                 per-bin fp32 arrays — (G_b, L_b) with one symmetric scale
                 per tap slot ("block" granularity) or (G_b, 1, group)
                 with one per filter ("out") — the rank encodes the
                 granularity.  All-zero slots store scale 0.

    Static aux data (hashable; part of the jit cache key):
      group : filters per tap-list (1 = exact per-filter taps; larger
              groups widen the output tile but store the tap UNION, which
              erodes savings because patterns differ per kernel)
      shape : (K, P) of the lowered dense weight
      n_shards : 0 for single-device; when S > 0 the filter groups are
                 tensor-parallel exactly like ``PackedLayout`` block
                 columns — per-bin leaves gain a leading shard axis
                 ((S, G_b, L_b, group) values), ``nnz``/``perm`` become
                 (S, G_s), ``inv_perm`` stays flat (G,), and ``alive``
                 stays GLOBAL (every shard gathers the same input band).

    Degree sort + binning mirror ``PackedLayout``: groups are sorted by
    tap-degree and each bin padded to its own max, so connectivity-pruned
    filters (fewer taps) don't pay the densest filter's degree.
    """

    values: tuple
    t_idx: tuple
    nnz: object
    alive: object
    perm: object = None
    inv_perm: object = None
    group: int = 1
    shape: tuple = (0, 0)
    k_full: tuple = None
    scales: tuple = None
    n_shards: int = 0

    # -- pytree protocol -----------------------------------------------------

    def tree_flatten(self):
        """Flatten into (array leaves, static aux) for jax pytree traversal."""
        children = (self.values, self.t_idx, self.nnz, self.alive,
                    self.perm, self.inv_perm, self.k_full, self.scales)
        return children, (self.group, self.shape, self.n_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild a layout from ``tree_flatten`` output (jax protocol)."""
        values, t_idx, nnz, alive, perm, inv_perm, k_full, scales = children
        group, shape, n_shards = aux
        return cls(values=values, t_idx=t_idx, nnz=nnz, alive=alive,
                   perm=perm, inv_perm=inv_perm, group=group, shape=shape,
                   k_full=k_full, scales=scales, n_shards=n_shards)

    # -- static geometry (no device sync) ------------------------------------

    @property
    def n_groups(self) -> int:
        """Number of filter groups (P // group)."""
        return self.shape[1] // self.group

    @property
    def n_alive(self) -> int:
        """Rows of the im2col band live for at least one group."""
        return self.alive.shape[-1]

    @property
    def n_bins(self) -> int:
        """Number of degree bins (1 for an unreordered layout)."""
        return len(self.values)

    @property
    def bin_sizes(self) -> tuple:
        """Filter groups per bin."""
        return tuple(v.shape[-3] for v in self.values)

    @property
    def bin_degrees(self) -> tuple:
        """Padded tap degree L_b of each bin."""
        return tuple(v.shape[-2] for v in self.values)

    @property
    def L_max(self) -> int:
        """Worst padded tap degree across bins."""
        return max(self.bin_degrees)

    @property
    def n_groups_shard(self) -> int:
        """Filter groups per shard (= n_groups when unsharded)."""
        return self.n_groups // max(1, self.n_shards)

    @property
    def executed_taps(self) -> int:
        """Tap slots the kernel gathers+multiplies (padding included):
        sum over bins of G_b * L_b, times the shard count on a sharded
        layout (bins pad to the cross-shard max, so shards match)."""
        per_shard = sum(s * d
                        for s, d in zip(self.bin_sizes, self.bin_degrees))
        return per_shard * max(1, self.n_shards)

    @property
    def L_effective(self) -> float:
        """Mean executed tap degree under the binned layout."""
        return self.executed_taps / max(self.n_groups, 1)

    @property
    def flops_saved(self) -> float:
        """Fraction of dense conv-GEMM FLOPs the tap-gather kernel skips:
        1 - executed/(K * n_groups), padding included — the executed-tap
        analogue of ``PackedLayout.flops_saved`` (NOT the raw mask
        density)."""
        K = self.shape[0]
        return max(0.0, 1.0 - self.executed_taps / (K * self.n_groups))

    @property
    def value_dtype(self) -> str:
        """Dtype name of the stored values ("int8" on quantized layouts)."""
        return jnp.asarray(self.values[0]).dtype.name

    def bin_scales(self) -> tuple:
        """Per-bin scale arrays, or a tuple of Nones on float layouts —
        what the tap kernel wrappers zip alongside ``values``."""
        if self.scales is None:
            return (None,) * self.n_bins
        return self.scales

    def shard_index_leaves(self) -> tuple:
        """Per-bin index leaves for the generic sharded kernel driver
        (``t_idx`` — see ``PackedLayout.shard_index_leaves``)."""
        return self.t_idx

    # -- data-dependent stats (host sync; report/test time only) -------------

    @property
    def nnz_taps(self) -> int:
        """True surviving tap-list entries (union over each group)."""
        return int(np.asarray(self.nnz).sum())

    @property
    def density(self) -> float:
        """Surviving tap-list fraction of the K x n_groups tap grid."""
        return self.nnz_taps / (self.shape[0] * self.n_groups)

    @property
    def padding_overhead(self) -> float:
        """Executed-tap overhead of bin padding vs exact tap lists."""
        return self.executed_taps / max(self.nnz_taps, 1)

    @property
    def shard_balance(self) -> float:
        """max/mean executed taps per shard were each shard padded to its
        own bin maxima (1.0 on unsharded layouts) — see
        ``PackedLayout.shard_balance``."""
        if not self.n_shards:
            return 1.0
        from repro.core import bcs
        return bcs.shard_balance(self.nnz, self.bin_sizes)

    # -- helpers -------------------------------------------------------------

    def unpermute_cols(self, y):
        """Gather a (..., M, P) output from layout group order back to the
        original filter order (identity when unreordered).  Sharded
        layouts merge per-shard outputs via ``merge_shards`` instead."""
        assert not self.n_shards, "sharded layouts merge via merge_shards"
        if self.inv_perm is None:
            return y
        yb = y.reshape(y.shape[:-1] + (self.n_groups, self.group))
        yb = jnp.take(yb, self.inv_perm, axis=-2)
        return yb.reshape(y.shape)

    def merge_shards(self, y):
        """Merge shard-local outputs (S, ..., M, P/S) — shard axis LEADING —
        into original filter order (..., M, P); one gather through the flat
        ``inv_perm`` is both the concat and the un-reorder (see
        ``PackedLayout.merge_shards``)."""
        assert self.n_shards, "merge_shards needs a sharded layout"
        y = jnp.moveaxis(y, 0, -2)              # (..., M, S, P/S)
        yb = y.reshape(y.shape[:-2] + (self.n_groups, self.group))
        yb = jnp.take(yb, self.inv_perm, axis=-2)
        return yb.reshape(y.shape[:-2] + (self.n_groups * self.group,))

    def permute_bias(self, bias):
        """Gather a (P,) bias into layout group order for fused epilogues.
        Returns (P,) unsharded, (S, P/S) sharded."""
        if bias is None or self.perm is None:
            return bias
        bb = bias.reshape(self.n_groups, self.group)
        pb = jnp.take(bb, self.perm, axis=0)
        return pb.reshape(pb.shape[:-2] + (-1,))

    def bin_bias(self, bias):
        """Per-bin (G_b * group,) bias slices in layout order (or Nones);
        (S, G_b * group) on sharded layouts (vmap-ready)."""
        if bias is None:
            return (None,) * self.n_bins
        pb = self.permute_bias(bias)
        pb = pb.reshape(pb.shape[:-1] + (-1, self.group))
        out, start = [], 0
        for s in self.bin_sizes:
            sl = pb[..., start:start + s, :]
            out.append(sl.reshape(sl.shape[:-2] + (s * self.group,)))
            start += s
        return tuple(out)

    def bin_k_full(self):
        """Per-bin (G_b, L_b) FULL-band row ids (tap*C + channel) for the
        implicit kernel — the precomputed ``k_full`` when present, else
        reconstructed as ``alive[t_idx]`` (trace-safe gather) on legacy
        layouts packed before the aux existed."""
        if self.k_full is not None:
            return self.k_full
        return tuple(jnp.take(self.alive, t, axis=0) for t in self.t_idx)

    def to_dense(self):
        """Reconstruct the dense lowered (K, P) weight — the round-trip
        oracle: must equal ``core.bcs.conv_lower(w * mask)`` (dequantized
        values * scales on a quantized layout)."""
        K, P = self.shape
        S = max(1, self.n_shards)
        dense = np.zeros((K, P),
                         np.float32 if self.scales is not None
                         else np.asarray(self.values[0]).dtype)
        alive = np.asarray(self.alive)
        perm = (np.asarray(self.perm).reshape(S, -1)
                if self.perm is not None
                else np.arange(self.n_groups).reshape(S, -1))
        nnz = np.asarray(self.nnz).reshape(S, -1)
        for sh in range(S):
            col = 0
            for vals, tidx, sc in zip(self.values, self.t_idx,
                                      self.bin_scales()):
                vals = np.asarray(_dequant(vals, sc))
                tidx = np.asarray(tidx)
                if self.n_shards:
                    vals, tidx = vals[sh], tidx[sh]
                for g in range(vals.shape[0]):
                    og = int(perm[sh, col + g])
                    sl = slice(og * self.group, (og + 1) * self.group)
                    for l in range(int(nnz[sh, col + g])):
                        dense[alive[int(tidx[g, l])], sl] += vals[g, l]
                col += vals.shape[0]
        return dense


# Degraded-mode sentinel: installed in place of a layout that failed
# ``core.validate`` so the model dispatch provably CANNOT launch a sparse
# kernel on it (an accidental ``packed is not None`` consumer would crash
# on the missing leaves, not mis-execute).  No array leaves — the whole
# record is static aux, so it hashes into the jit cache key and a
# degrade/un-degrade flip retraces instead of reusing a stale executable.
@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DegradedLayer:
    """Marker left behind by ``serve.compile.degrade_invalid_layers`` where
    a packed layout failed validation: the layer executes masked-dense
    (the zeros are baked into its retained dense ``w``) instead of the
    sparse kernel — a slower but never-wrong fallback.

    ``path`` is the layer that degraded, ``code`` the ``LayoutError``
    failure class, ``detail`` the human-readable reason (all strings, all
    static).
    """

    path: str
    code: str
    detail: str

    def tree_flatten(self):
        """No array children — the marker is pure static aux (jax protocol)."""
        return (), (self.path, self.code, self.detail)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild the marker from ``tree_flatten`` output (jax protocol)."""
        del children
        return cls(*aux)
