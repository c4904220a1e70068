"""Pallas TPU kernel: BCS block-sparse matmul  y = x @ W_sparse.

The TPU executor for the paper's compiler contribution (§4.3): the grid
iterates ONLY over surviving weight blocks — pruned blocks are never read
from HBM nor multiplied.  The block-column index array is scalar-prefetched
(SMEM) and drives the in-kernel slice of x each live block multiplies, the
TPU analogue of PatDNN-style sparsity-baked codegen.

Layout (from repro.core.bcs.pad_to_uniform_csc):
  values (Nb, L, bk, bn)  surviving blocks per output column, zero-padded
  k_idx  (Nb, L) int32    K-block index each slot reads from
Grid: (M/bm, Nb) — one step per (M tile, block column).  The (bm, K) x
tile's block index depends on the M tile alone, so it is fetched once per
M tile and stays in VMEM across the columns; each step fetches its whole
(L, bk, bn) weight column in one DMA and runs its L live blocks in order
(unrolled), slicing each block's (bm, bk) x columns out of the resident
tile at ``k_idx`` (scalar-prefetched) into an fp32 VMEM accumulator.
Equal trip counts per column = the load-balance analogue of the paper's
row reordering.  ``bm`` follows the shapes (``m_tile``): the largest of
512/256/128 whose pipelined blocks fit ``VMEM_BUDGET`` once M reaches
512, else M itself rounded to the sublane tile (decode).  Epilogue
(bias + activation) fuses into the step's store (layer-fusion analogue,
§A.1).

Accumulation is always fp32 (``preferred_element_type`` on the MXU dot +
fp32 VMEM scratch); bf16 inputs therefore take the mixed-precision path —
bf16 reads, fp32 accumulate, one rounding on the final store.

Ragged M is handled here: M is zero-padded up to the next ``bm`` multiple
before the grid launch and the pad rows are sliced off the output, so
callers never silently fall back to a dense matmul.

``bsr_matmul`` is the raw single-bin launch; consumers go through
``bsr_matmul_packed``, which takes a ``core.packed.PackedLayout`` — the
repo-wide interchange format — runs one launch per degree bin (row
reordering/binning: each bin is padded only to its own max column degree)
and gathers outputs back to original column order in the epilogue.

``tap_gather_conv`` (bottom of this file) is the second kernel: the
executor for pattern/connectivity-pruned convolutions, consuming the
``core.packed.TapLayout`` sibling format.  Per-kernel pattern masks have
no block structure, so a (1, group) block would make each BCS dot a single
tap; the tap kernel instead keeps the alive im2col band VMEM-resident and
gathers each output filter's surviving taps in one (M tile, filter group)
step.

``bsr_conv2d_implicit`` / ``tap_gather_conv_implicit`` are the
implicit-GEMM conv variants of both: instead of consuming a pre-extracted
``(B*Ho*Wo, Kh*Kw*C)`` patch matrix (a ~Kh*Kw-fold HBM blow-up of the
activations), the grid grows a batch dimension, the x BlockSpec index_map
selects the current image of the PADDED feature map (revisited across the
block/tap steps, so it is fetched once per image), and each step gathers
the rows it needs in-kernel from a tap -> (dy, dx, c) offset table riding
in SMEM — the patch tensor never exists in HBM.  Same fp32 accumulation,
degree-bin launches, and fused bias/act epilogues as the materialized
kernels, which stay as the parity oracle.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.core import bcs as BCS
from repro.core.packed import LayerSlice


def _kernel(k_idx, x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, act):
    """One (M tile, block column) step: the column's live blocks in order
    l = 0 .. L-1, each a (bm, bk) @ (bk, bn) dot into the fp32
    accumulator, then the epilogue.  The loop over l is unrolled (L is
    static), so the scheduler overlaps one block's loads with the
    previous block's dot; a ``fori_loop`` took 1.4x as long on the v5e."""
    j = pl.program_id(1)
    _, _, n_l, bk, _ = w_ref.shape
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for l in range(n_l):
        k0 = pl.multiple_of(k_idx[j, l] * bk, bk)
        w = w_ref[0, 0, l]
        if s_ref is not None:
            # int8 path: dequantize in-kernel (one fp32 scale per stored
            # block or per block column) BEFORE the dot, so accumulation
            # stays fp32 and the result equals the dequantized dense
            # reference
            w = w.astype(jnp.float32) * s_ref[0, l if s_ref.shape[1] > 1
                                              else 0]
        acc_ref[...] += jnp.dot(x_ref[:, pl.ds(k0, bk)], w,
                                preferred_element_type=jnp.float32)
    out = acc_ref[...]
    if b_ref is not None:
        out = out + b_ref[0].astype(jnp.float32)
    if act == "silu":
        out = out * jax.nn.sigmoid(out)
    elif act == "relu":
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


# TPU vreg lane width: Mosaic needs the minor dim of every block to be a
# multiple of it (or the whole array dim), and the second-minor a multiple
# of 8 (f32) / 16 (bf16) — ``_m_tile`` keeps bm on that grid.
LANE = 128

# Kernels the TPU compiler refuses today, with Mosaic's reason (each one is
# a strict xfail in tests/test_tpu_compile.py; ROADMAP S2).
_REFUSED = {
    "int8": "int8 bsr_matmul: its (1, L) scale BlockSpec is neither "
            "(8, 128)-aligned nor the full scale array",
    "tap": "tap_gather_conv: its (bm, group) output block is not "
           "lane-aligned at the packed group=1",
    "conv_implicit": "bsr_conv2d_implicit: its in-kernel jnp.take is a "
                     "1-D gather, and Mosaic lowers only 2-D gathers",
    "tap_implicit": "tap_gather_conv_implicit: its (1, bm, group) output "
                    "block is not lane-aligned at the packed group=1",
}


def tpu_refusal(kind, block=None, shape=None, value_dtype=None):
    """Why the TPU compiler refuses ``kind``'s kernel, or None if it lowers.

    ``kind`` is "bcs" (``bsr_matmul`` over a (bk, bn) ``block``-ed layout
    of a (K, N) weight ``shape`` holding ``value_dtype`` values), "tap",
    "conv_implicit" or "tap_implicit".  ``bsr_matmul`` slices (bm, bk) x
    blocks out of its (bm, K) tile and tiles the bias/output as
    (1 | bm, bn): Mosaic needs bk and bn to be multiples of ``LANE`` or
    the whole K / N, and refuses the int8 path's (1, L) scale blocks."""
    if kind != "bcs":
        return _REFUSED[kind]
    if value_dtype is not None and jnp.dtype(value_dtype) == jnp.int8:
        return _REFUSED["int8"]
    if block is None:
        return None
    bad = [f"{name}={b}" for name, b, full in (("bk", block[0], shape[0]),
                                               ("bn", block[1], shape[1]))
           if b % LANE and b != full]
    if not bad:
        return None
    return (f"block {tuple(block)} of a {tuple(shape)} weight: "
            f"{', '.join(bad)} is neither a multiple of the {LANE}-lane "
            "tile nor the full dimension, so Mosaic cannot tile it")


def refusal_here(kind, block=None, shape=None, value_dtype=None):
    """``tpu_refusal`` on a TPU backend; None on any other, where the
    interpreter runs every kernel."""
    if jax.default_backend() != "tpu":
        return None
    return tpu_refusal(kind, block, shape, value_dtype)


def _interpret_mode(interpret=None, refusal=None) -> bool:
    """Resolve a kernel's ``interpret`` flag: ``None`` means interpret mode
    off the TPU and Mosaic lowering on it — the backend alone decides.  A
    ``refusal`` (``refusal_here``'s answer) raises instead: a kernel the
    TPU compiler refuses never falls back to the interpreter there."""
    if refusal:
        raise NotImplementedError(
            f"{refusal}, so it does not run on TPU (ROADMAP S2)")
    return (jax.default_backend() != "tpu") if interpret is None \
        else interpret


_SHARD_AXIS = contextvars.ContextVar("shard_axis", default=None)


@contextlib.contextmanager
def traced_on(mesh, shard_axis):
    """Trace the kernels for a program over ``mesh`` (the serving engine
    does, for its ``Dist``): every launch is placed per device, and
    column-sharded layouts split over the mesh axis ``shard_axis``."""
    token = _SHARD_AXIS.set(shard_axis)
    try:
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            yield
    finally:
        _SHARD_AXIS.reset(token)


def _device_mesh():
    """The multi-device abstract mesh of ``traced_on``, or None — also None
    inside a ``shard_map`` body, whose axes are manual.  Mosaic kernels
    cannot be partitioned automatically, so in a program over several
    devices every launch is placed per device explicitly."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return None
    return mesh


def _same_pads(size, k, s):
    """XLA 'SAME' padding for one spatial dim: output ceil(size/s)."""
    out = -(-size // s)
    pad = max((out - 1) * s + k - size, 0)
    return pad // 2, pad - pad // 2


def conv_geometry(H, W, kh, kw, stride=1, padding="SAME"):
    """Conv output/padding geometry shared by ``kernels.ops.im2col`` and
    the implicit kernels: ((ph0, ph1), (pw0, pw1), Ho, Wo)."""
    if padding == "SAME":
        ph, pw = _same_pads(H, kh, stride), _same_pads(W, kw, stride)
    elif padding == "VALID":
        ph = pw = (0, 0)
    else:
        raise ValueError(padding)
    Ho = (H + ph[0] + ph[1] - kh) // stride + 1
    Wo = (W + pw[0] + pw[1] - kw) // stride + 1
    if Ho < 1 or Wo < 1:
        raise ValueError(
            f"kernel ({kh}, {kw}) does not fit the ({H}, {W}) feature map "
            f"under {padding} padding (output would be {Ho}x{Wo})")
    return ph, pw, Ho, Wo


def _m_tile(M, bm, dtype):
    """Pick the M tile: split M over the minimum number of bm-sized tiles,
    then shrink the tile to the aligned ceiling of the per-tile share so
    zero-padding stays under one alignment unit (M=129 with bm=128 runs
    2x72 rows, not 2x128).  Alignment is the Mosaic second-minor minimum:
    8 rows for f32, 16 for bf16; decode arrives with M = batch < both."""
    align = 8 if dtype == jnp.float32 else 16
    n_tiles = -(-M // bm) if M > bm else 1
    per_tile = -(-M // n_tiles)
    bm = min(bm, ((per_tile + align - 1) // align) * align)
    return bm, ((M + bm - 1) // bm) * bm


# VMEM of one bsr_matmul launch.  The v5e has 128 MiB; the tile choice
# keeps the pipelined blocks within VMEM_BUDGET, and the launch lets
# Mosaic use what they take plus _VMEM_HEADROOM for its own scratch.
VMEM_BUDGET = 64 * 2**20
_VMEM_HEADROOM = 16 * 2**20
# M tiles tried, largest first, once M reaches the first of them; below
# it the tile is M itself (rounded up to the sublane tile by _m_tile)
_BM_CHOICES = (512, 256, 128)


def vmem_bytes(bm, K, L, bk, bn, itemsize=2, w_itemsize=2, out_itemsize=2):
    """VMEM the pipelined blocks of one ``bsr_matmul`` launch take: the
    (bm, K) x tile, the (L, bk, bn) weight column and the (bm, bn) output
    tile, each double-buffered, and the fp32 accumulator."""
    return (2 * bm * K * itemsize + 2 * L * bk * bn * w_itemsize
            + 2 * bm * bn * out_itemsize + bm * bn * 4)


def m_tile(M, K, L=1, bk=LANE, bn=LANE, itemsize=2, w_itemsize=2,
           out_itemsize=2):
    """The M tile ``bsr_matmul`` takes for an (M, K) x and an (L, bk, bn)
    weight column when it is given none: at M >= 512 the largest of
    ``_BM_CHOICES`` whose ``vmem_bytes`` fit ``VMEM_BUDGET`` (the
    smallest if none does), else 128 — which ``_m_tile`` then cuts to M
    (decode, M = 16, runs one 16-row tile)."""
    if M >= _BM_CHOICES[0]:
        for bm in _BM_CHOICES:
            if vmem_bytes(bm, K, L, bk, bn, itemsize, w_itemsize,
                          out_itemsize) <= VMEM_BUDGET:
                return bm
    return _BM_CHOICES[-1]


@functools.partial(jax.jit, static_argnames=("bm", "act", "interpret",
                                             "out_dtype", "name"))
def bsr_matmul(x, values, k_idx, bias=None, scales=None, layer=0, *,
               bm=None, act="none", interpret=None, out_dtype=None,
               name=None):
    """x (M, K) @ BCS-sparse W (K, N) -> (M, N).

    values (Nb, L, bk, bn), or a layer stack (n_layers, Nb, L, bk, bn) of
    which the launch reads layer ``layer`` (an int32 scalar) in place
    (``core.packed.LayerSlice``); k_idx (Nb, L) int32.  ``scales`` rides
    along
    for int8 values (``core.quant``): fp32, (Nb, L) per-block or (Nb,)
    per-block-column, dequantized in-kernel before the fp32-accumulated
    dot (int8 does not lower for TPU yet, see ``tpu_refusal``).
    ``bm=None`` takes the M tile from the shapes (``m_tile``).
    ``interpret=None`` follows the backend (Pallas lowering on TPU,
    interpreter elsewhere).  ``out_dtype`` defaults to x.dtype; pass
    jnp.float32 to keep the fp32 accumulator precision on a bf16 input.
    ``name`` (a projection's, e.g. "wq") names the launch
    ``bsr_matmul_<name>`` in the compiled program and so in a profile;
    without it the launch is ``bsr_matmul``."""
    M, K = x.shape
    # unstacked values are a stack of one, read at layer 0
    values = values.reshape((-1,) + values.shape[-4:])
    _, Nb, L, bk, bn = values.shape
    N = Nb * bn
    interpret = _interpret_mode(interpret, refusal_here(
        "bcs", (bk, bn), (K, N), values.dtype))
    assert K % bk == 0, (K, bk)
    if out_dtype is None:
        out_dtype = x.dtype
    sizes = (x.dtype.itemsize, values.dtype.itemsize,
             jnp.dtype(out_dtype).itemsize)
    if bm is None:
        bm = m_tile(M, K, L, bk, bn, *sizes)
    bm, Mp = _m_tile(M, bm, x.dtype)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))

    grid = (Mp // bm, Nb)
    in_specs = [
        pl.BlockSpec((bm, K), lambda i, j, *_: (i, 0)),
        pl.BlockSpec((1, 1, L, bk, bn),
                     lambda i, j, kidx, lyr: (lyr[0], j, 0, 0, 0)),
    ]
    args = [x, values]
    if scales is not None:
        # per-block scales ride as a (1, L) row per column, per-column
        # scales as a (1, 1) block
        sc = scales if scales.ndim == 2 else scales[:, None]
        in_specs.append(pl.BlockSpec((1, sc.shape[1]),
                                     lambda i, j, *_: (j, 0)))
        args.append(sc)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, *_: (0, j)))
        args.append(bias.reshape(1, N))
    has_s, has_b = scales is not None, bias is not None

    def kern(k_idx_ref, layer_ref, x_ref, w_ref, *rest):
        rest = list(rest)
        s_ref = rest.pop(0) if has_s else None
        b_ref = rest.pop(0) if has_b else None
        o_ref, acc_ref = rest
        _kernel(k_idx_ref, x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref,
                act=act)

    y = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(bm, K, L, bk, bn, *sizes)
            + _VMEM_HEADROOM),
        interpret=interpret,
        name=f"bsr_matmul_{name}" if name else None,
    )(k_idx, jnp.reshape(layer, (1,)).astype(jnp.int32), *args)
    return y[:M] if Mp != M else y


def bsr_matmul_packed(x, layout, bias=None, *, bm=None, act="none",
                      interpret=None, out_dtype=None, name=None):
    """x (M, K) @ PackedLayout W (K, N) -> (M, N).

    One ``bsr_matmul`` launch per degree bin — each bin's columns are padded
    only to the bin max, so a reordered layout executes
    ``layout.executed_blocks`` < Nb * L_max blocks.  Bias and activation
    fuse into each bin's epilogue (bias is gathered into layout column
    order first); the final column gather restores the original output
    order.  Per-column accumulation order is identical to the single-bin
    kernel (padding blocks add exact zeros at the END of each column's
    sequential accumulation), so reordered and unreordered results
    are bit-identical.
    Quantized layouts (int8 values, ``core.quant``) thread each bin's
    ``scales`` leaf into the launch for in-kernel dequantization.

    Tensor-parallel layouts (``layout.n_shards > 0``) dispatch to
    ``bsr_matmul_sharded`` — callers never need to care which they hold.
    Every bin's launch carries the same ``name`` (see ``bsr_matmul``).
    """
    if layout.n_shards:
        return bsr_matmul_sharded(x, layout, bias=bias, bm=bm, act=act,
                                  interpret=interpret, out_dtype=out_dtype,
                                  name=name)
    if _device_mesh() is not None:
        # a replicated layout in a multi-device program: every device runs
        # the whole launch on the replicated operands
        call = functools.partial(bsr_matmul_packed, bm=bm, act=act,
                                 interpret=interpret, out_dtype=out_dtype,
                                 name=name)
        return jax.shard_map(call, in_specs=P(), out_specs=P(),
                             check_vma=False)(x, layout, bias)
    outs = []
    for vals_b, kidx_b, sc_b, bias_b in zip(layout.values, layout.k_idx,
                                            layout.bin_scales(),
                                            layout.bin_bias(bias)):
        layer = 0
        if isinstance(vals_b, LayerSlice):
            vals_b, layer = vals_b.stack, vals_b.index
        outs.append(bsr_matmul(x, vals_b, kidx_b, bias=bias_b, scales=sc_b,
                               layer=layer, bm=bm, act=act,
                               interpret=interpret, out_dtype=out_dtype,
                               name=name))
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)
    return layout.unpermute_cols(y)


def _sharded_launch(x, layout, bias, launch):
    """Shared shard-parallel dispatch: the per-bin ``launch`` over each shard
    of every per-bin leaf (values/indices/scales/bias, shard axis leading),
    then ``layout.merge_shards`` — one gather that is both the cross-shard
    concat and the column un-reorder.  ``x`` is replicated to every shard.

    Under a multi-device mesh (``traced_on``) the shards map onto its
    shard axis through ``shard_map``, one shard's launches per device, and GSPMD turns the
    merge into the all-gather epilogue.  Without one, ``jax.vmap`` runs the
    shards as a batched launch (interpret mode places it by GSPMD on the
    CPU; Mosaic kernels cannot be partitioned that way).  BCS layouts stay
    bit-identical to unsharded (per-column accumulation order is
    untouched); tap layouts agree to fp32 rounding only, see
    ``tap_gather_conv_packed``."""
    operands = {"values": layout.values, "idx": layout.shard_index_leaves()}
    if layout.scales is not None:
        operands["scales"] = layout.scales
    if bias is not None:
        operands["bias"] = layout.bin_bias(bias)
    n_bins = layout.n_bins

    def shard_fn(xx, op):
        outs = []
        for b in range(n_bins):
            outs.append(launch(
                xx, op["values"][b], op["idx"][b],
                op["bias"][b] if "bias" in op else None,
                op["scales"][b] if "scales" in op else None))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    mesh = _device_mesh()
    if mesh is None:
        y = jax.vmap(lambda op: shard_fn(x, op))(operands)
    else:
        axis = _SHARD_AXIS.get()
        if axis is None or mesh.shape.get(axis) != layout.n_shards:
            raise ValueError(
                f"a layout with {layout.n_shards} column shards needs a "
                f"shard axis of that size, got {axis!r} on "
                f"{dict(mesh.shape)}")

        def one_device(xx, op):
            op = jax.tree_util.tree_map(lambda a: a[0], op)
            return shard_fn(xx, op)[None]

        y = jax.shard_map(one_device, in_specs=(P(), P(axis)),
                          out_specs=P(axis), check_vma=False)(
                              x, operands)
    return layout.merge_shards(y)


def bsr_matmul_sharded(x, layout, bias=None, *, bm=None, act="none",
                       interpret=None, out_dtype=None, name=None):
    """x (M, K) @ tensor-parallel PackedLayout (K, N) -> (M, N).

    Each shard runs the same per-bin ``bsr_matmul`` launches as
    ``bsr_matmul_packed`` over its own degree-balanced column slice
    (weights column-split, x replicated); outputs merge through the flat
    ``inv_perm`` gather.  See ``_sharded_launch`` for the vmap/GSPMD
    mechanics."""
    def launch(xx, vals, kidx, bias_b, sc_b):
        return bsr_matmul(xx, vals, kidx, bias=bias_b, scales=sc_b, bm=bm,
                          act=act, interpret=interpret, out_dtype=out_dtype,
                          name=name)
    return _sharded_launch(x, layout, bias, launch)


# ---------------------------------------------------------------------------
# Tap-gather kernel: pattern/connectivity-pruned convs (PatDNN/PCONV style)
# ---------------------------------------------------------------------------

def _tap_kernel(t_idx, x_ref, w_ref, s_ref, b_ref, o_ref, *, act):
    """One grid step per (M tile, filter group): gather this group's
    surviving taps from the VMEM-resident alive band and contract them in a
    single dot — no cross-step accumulator, epilogue fused into the same
    step."""
    j = pl.program_id(1)
    taps = t_idx[j]                                     # (L,) int32, SMEM
    g = jnp.take(x_ref[...], taps, axis=1)              # (bm, L)
    w = w_ref[0]
    if s_ref is not None:
        # int8 path: per-slot scales arrive as (1, L), per-filter scales as
        # (1, 1, group) — dequantize before the dot (fp32 accumulation)
        s = s_ref[...]
        w = w.astype(jnp.float32) * (s[0][:, None] if s.ndim == 2
                                     else s[0, 0][None, :])
    out = jnp.dot(g, w, preferred_element_type=jnp.float32)
    if b_ref is not None:
        out = out + b_ref[0].astype(jnp.float32)
    if act == "silu":
        out = out * jax.nn.sigmoid(out)
    elif act == "relu":
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "act", "interpret", "out_dtype"))
def tap_gather_conv(x, values, t_idx, bias=None, scales=None, *, bm=128,
                    act="none", interpret=None, out_dtype=None):
    """x (M, R) alive im2col band @ per-group tap lists -> (M, G*group).

    The executor for pattern/connectivity-pruned convolutions (one launch
    per ``core.packed.TapLayout`` degree bin): ``values`` (G, L, group)
    holds each filter group's surviving-tap weights, ``t_idx`` (G, L) the
    band row each slot reads.  Where the BCS kernel's grid pays one step
    per (bk, bn) BLOCK — a full grid step per single tap at the (1, group)
    granularity pattern masks force — this kernel keeps the whole alive
    band (bm, R) resident in VMEM and gathers each group's taps inside ONE
    step, so the grid is (M/bm, G) regardless of tap count.  Pruned weight
    taps are never stored nor multiplied; band rows dead for every filter
    never reach the kernel at all (``TapLayout.alive`` excludes them from
    the host-side patch gather).  Padding slots read row 0 with zero
    values.  Bias + activation fuse into the same step (there is no
    cross-step accumulator to epilogue).

    The in-kernel gather runs on the VPU (per-filter tap sets defeat MXU
    tiling — the §5.2.4-style trade-off ``core.latency_model`` now prices);
    like ``bsr_matmul``, ``interpret=None`` follows the backend and
    ragged M is padded here, never silently densified.  Mosaic refuses
    this kernel today (``tpu_refusal``), so it raises on a TPU."""
    interpret = _interpret_mode(interpret, refusal_here("tap"))
    M, R = x.shape
    G, L, gp = values.shape
    bm, Mp = _m_tile(M, bm, x.dtype)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out_dtype = out_dtype or x.dtype
    N = G * gp

    grid = (Mp // bm, G)
    in_specs = [
        pl.BlockSpec((bm, R), lambda i, j, tidx: (i, 0)),
        pl.BlockSpec((1, L, gp), lambda i, j, tidx: (j, 0, 0)),
    ]
    args = [x, values]
    if scales is not None:
        # per-slot (G, L) scales ride as a (1, L) row per group; per-filter
        # (G, 1, gp) scales as a (1, 1, gp) slab — rank picks the form
        if scales.ndim == 2:
            in_specs.append(pl.BlockSpec((1, L), lambda i, j, tidx: (j, 0)))
        else:
            in_specs.append(
                pl.BlockSpec((1, 1, gp), lambda i, j, tidx: (j, 0, 0)))
        args.append(scales)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, gp), lambda i, j, tidx: (0, j)))
        args.append(bias.reshape(1, N))
    has_s, has_b = scales is not None, bias is not None

    def kern(t_idx_ref, x_ref, w_ref, *rest):
        rest = list(rest)
        s_ref = rest.pop(0) if has_s else None
        b_ref = rest.pop(0) if has_b else None
        _tap_kernel(t_idx_ref, x_ref, w_ref, s_ref, b_ref, rest[0], act=act)

    y = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, gp), lambda i, j, tidx: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        interpret=interpret,
    )(t_idx, *args)
    return y[:M] if Mp != M else y


def tap_gather_conv_packed(x, layout, bias=None, *, bm=128, act="none",
                           interpret=None, out_dtype=None):
    """x (M, R) alive band @ TapLayout -> (M, P), original filter order.

    One ``tap_gather_conv`` launch per degree bin (each bin padded only to
    its own max tap degree), outputs concatenated over bins and gathered
    back through ``inv_perm`` — the TapLayout mirror of
    ``bsr_matmul_packed``, including the quantized-scales plumbing and the
    tensor-parallel dispatch (``layout.n_shards > 0`` routes to
    ``tap_gather_conv_sharded``).

    Unlike the BCS kernel, each filter's taps contract in ONE dot of
    length ``L_b`` (its bin's padded tap degree).  Two tap layouts of the
    same weight (other bin counts, reordered or not, sharded or not) give
    bit-identical outputs only where every filter keeps the same ``L_b``;
    otherwise the dot's reduction length changes, and with it XLA's
    summation order, so results agree to fp32 rounding (a few ulp), not
    bitwise."""
    if layout.n_shards:
        return tap_gather_conv_sharded(x, layout, bias=bias, bm=bm, act=act,
                                       interpret=interpret,
                                       out_dtype=out_dtype)
    outs = []
    for vals_b, tidx_b, sc_b, bias_b in zip(layout.values, layout.t_idx,
                                            layout.bin_scales(),
                                            layout.bin_bias(bias)):
        outs.append(tap_gather_conv(x, vals_b, tidx_b, bias=bias_b,
                                    scales=sc_b, bm=bm, act=act,
                                    interpret=interpret,
                                    out_dtype=out_dtype))
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)
    return layout.unpermute_cols(y)


def tap_gather_conv_sharded(x, layout, bias=None, *, bm=128, act="none",
                            interpret=None, out_dtype=None):
    """x (M, R) alive band @ tensor-parallel TapLayout -> (M, P).

    The tap mirror of ``bsr_matmul_sharded``: the alive band is GLOBAL
    (``layout.alive`` is replicated — every shard gathers from the same
    rows), each shard contracts its own degree-balanced filter groups, and
    ``merge_shards`` restores original filter order."""
    def launch(xx, vals, tidx, bias_b, sc_b):
        return tap_gather_conv(xx, vals, tidx, bias=bias_b, scales=sc_b,
                               bm=bm, act=act, interpret=interpret,
                               out_dtype=out_dtype)
    return _sharded_launch(x, layout, bias, launch)


# ---------------------------------------------------------------------------
# Implicit-GEMM conv kernels: im2col folded into the grid — the patch
# tensor (B*Ho*Wo, Kh*Kw*C) is never materialized in HBM.
# ---------------------------------------------------------------------------

def _out_positions(i, bm, geom):
    """In-kernel output-position decode for M tile ``i``: the (bm, 1)
    top-left input offsets (row index into the padded, flattened image) of
    this tile's output positions.  M-pad rows clamp to the last valid
    position — their gathers read a real pixel and are sliced off after the
    launch."""
    _, Wp, Ho, Wo, s = geom
    m = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    m = jnp.minimum(m, Ho * Wo - 1)
    return (m // Wo) * (s * Wp) + (m % Wo) * s


def _conv_kernel(tap_ref, x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *,
                 n_l, act, geom):
    """Implicit BCS conv step: the x tile (bm, bk) is gathered from the
    VMEM-resident padded image — slot (j, l)'s SMEM entry carries this
    K-block's (dy*Wp + dx, c0) offsets, so the gather lands on input
    channel slice [c0, c0+bk) at kernel tap (dy, dx) for each of the tile's
    bm output positions.  Accumulation/epilogue mirror ``_kernel``."""
    i, j, l = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, _ = acc_ref.shape
    bk = w_ref.shape[2]
    C = x_ref.shape[2]
    rows = _out_positions(i, bm, geom) + tap_ref[j, l, 0]        # (bm, 1)
    cols = tap_ref[j, l, 1] + jax.lax.broadcasted_iota(jnp.int32, (bm, bk),
                                                       1)
    g = jnp.take(x_ref[...].reshape(-1), rows * C + cols, axis=0)
    w = w_ref[0, 0]
    if s_ref is not None:
        w = w.astype(jnp.float32) * s_ref[0, 0]
    acc_ref[...] += jnp.dot(g, w, preferred_element_type=jnp.float32)

    @pl.when(l == n_l - 1)
    def _store():
        out = acc_ref[...]
        if b_ref is not None:
            out = out + b_ref[0].astype(jnp.float32)
        if act == "silu":
            out = out * jax.nn.sigmoid(out)
        elif act == "relu":
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("geom", "bm", "act",
                                             "interpret", "out_dtype"))
def _conv_implicit_bin(xp, values, taps, bias=None, scales=None, *, geom,
                       bm=128, act="none", interpret=None, out_dtype=None):
    """One degree bin of the implicit BCS conv: xp (B, Hp*Wp, C) padded
    flattened images, values (Nb, L, bk, bn), taps (Nb, L, 2) int32 per-slot
    (dy*Wp + dx, c0) offsets (scalar-prefetched).  Grid (B, M/bm, Nb, L):
    the x BlockSpec pins the whole current image in VMEM (index depends on
    b only, so it is fetched once per image, not per block step) and each
    step gathers its (bm, bk) tile in-kernel — no patch tensor, no HBM
    re-read per block.  Refused by Mosaic today (``tpu_refusal``)."""
    interpret = _interpret_mode(interpret, refusal_here("conv_implicit"))
    Hp, Wp, Ho, Wo, _ = geom
    B, _, C = xp.shape
    Nb, L, bk, bn = values.shape
    N = Nb * bn
    bm, Mp = _m_tile(Ho * Wo, bm, xp.dtype)
    out_dtype = out_dtype or xp.dtype

    grid = (B, Mp // bm, Nb, L)
    in_specs = [
        pl.BlockSpec((1, Hp * Wp, C), lambda b, i, j, l, taps: (b, 0, 0)),
        pl.BlockSpec((1, 1, bk, bn), lambda b, i, j, l, taps: (j, l, 0, 0)),
    ]
    args = [xp, values]
    if scales is not None:
        sc = scales if scales.ndim == 2 else scales[:, None]
        idx = ((lambda b, i, j, l, taps: (j, l)) if sc.shape[1] == L
               else (lambda b, i, j, l, taps: (j, 0)))
        in_specs.append(pl.BlockSpec((1, 1), idx))
        args.append(sc)
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, bn), lambda b, i, j, l, taps: (0, j)))
        args.append(bias.reshape(1, N))
    has_s, has_b = scales is not None, bias is not None

    def kern(tap_ref, x_ref, w_ref, *rest):
        rest = list(rest)
        s_ref = rest.pop(0) if has_s else None
        b_ref = rest.pop(0) if has_b else None
        o_ref, acc_ref = rest
        _conv_kernel(tap_ref, x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref,
                     n_l=L, act=act, geom=geom)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, j, l, taps: (b, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Mp, N), out_dtype),
        interpret=interpret,
    )(taps, *args)


def bsr_conv2d_implicit(x, layout, *, kh, kw, stride=1, padding="SAME",
                        bias=None, bm=128, act="none", interpret=None,
                        out_dtype=None):
    """x (B, H, W, C) * im2col-lowered PackedLayout -> (B, Ho, Wo, N),
    without ever materializing the patch tensor.

    The implicit mirror of ``bsr_matmul_packed`` over extracted patches:
    one ``_conv_implicit_bin`` launch per degree bin, bias + activation
    fused per bin, outputs gathered back to original filter order.  HBM
    holds only the zero-padded feature map (the halo copy, ~activation
    sized) instead of the Kh*Kw-fold patch blow-up; the kernel derives each
    K-block's input offsets from the layout's static ``conv_taps`` table
    (``core.bcs.conv_tap_table``, attached at pack time — derived on the
    fly for layouts packed without it).  Bit-identical to the materialized
    path: the gathered tiles equal the im2col rows, and per-column
    accumulation order is untouched."""
    B, H, W, C = x.shape
    assert layout.shape[0] == kh * kw * C, (
        f"layout K={layout.shape[0]} != kh*kw*Cin={kh * kw * C}")
    taps = layout.conv_taps or BCS.conv_tap_table(kh, kw, C,
                                                  layout.block[0])
    ph, pw, Ho, Wo = conv_geometry(H, W, kh, kw, stride, padding)
    Hp, Wp = H + ph[0] + ph[1], W + pw[0] + pw[1]
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0))).reshape(B, Hp * Wp, C)
    off_t = jnp.asarray([dy * Wp + dx for dy, dx, _ in taps], jnp.int32)
    c0_t = jnp.asarray([c0 for _, _, c0 in taps], jnp.int32)
    geom = (Hp, Wp, Ho, Wo, stride)
    outs = []
    for vals_b, kidx_b, sc_b, bias_b in zip(layout.values, layout.k_idx,
                                            layout.bin_scales(),
                                            layout.bin_bias(bias)):
        slot = jnp.stack([jnp.take(off_t, kidx_b),
                          jnp.take(c0_t, kidx_b)], axis=-1)
        outs.append(_conv_implicit_bin(xp, vals_b, slot, bias=bias_b,
                                       scales=sc_b, geom=geom, bm=bm,
                                       act=act, interpret=interpret,
                                       out_dtype=out_dtype))
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)
    y = layout.unpermute_cols(y)
    return y[:, :Ho * Wo].reshape(B, Ho, Wo, y.shape[-1])


def _tap_conv_kernel(tap_ref, x_ref, w_ref, s_ref, b_ref, o_ref, *, act,
                     geom):
    """Implicit tap-gather step: like ``_tap_kernel`` but the (bm, L) tap
    matrix is gathered straight from the VMEM-resident padded image —
    group j's SMEM row carries each tap slot's (dy*Wp + dx, c) offsets, so
    the alive im2col band is never built on the host either."""
    i, j = pl.program_id(1), pl.program_id(2)
    bm = o_ref.shape[1]
    C = x_ref.shape[2]
    base = _out_positions(i, bm, geom)                           # (bm, 1)
    flat = (base + tap_ref[j, :, 0][None, :]) * C + tap_ref[j, :, 1][None, :]
    g = jnp.take(x_ref[...].reshape(-1), flat, axis=0)           # (bm, L)
    w = w_ref[0]
    if s_ref is not None:
        s = s_ref[...]
        w = w.astype(jnp.float32) * (s[0][:, None] if s.ndim == 2
                                     else s[0, 0][None, :])
    out = jnp.dot(g, w, preferred_element_type=jnp.float32)
    if b_ref is not None:
        out = out + b_ref[0].astype(jnp.float32)
    if act == "silu":
        out = out * jax.nn.sigmoid(out)
    elif act == "relu":
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("geom", "bm", "act",
                                             "interpret", "out_dtype"))
def _tap_implicit_bin(xp, values, taps, bias=None, scales=None, *, geom,
                      bm=128, act="none", interpret=None, out_dtype=None):
    """One degree bin of the implicit tap-gather conv: xp (B, Hp*Wp, C),
    values (G, L, group), taps (G, L, 2) int32 per-slot (dy*Wp + dx, c)
    offsets.  Grid (B, M/bm, G), no cross-step accumulator — epilogue fused
    into the single step, exactly like ``tap_gather_conv``.  Refused by
    Mosaic today (``tpu_refusal``)."""
    interpret = _interpret_mode(interpret, refusal_here("tap_implicit"))
    Hp, Wp, Ho, Wo, _ = geom
    B, _, C = xp.shape
    G, L, gp = values.shape
    N = G * gp
    bm, Mp = _m_tile(Ho * Wo, bm, xp.dtype)
    out_dtype = out_dtype or xp.dtype

    grid = (B, Mp // bm, G)
    in_specs = [
        pl.BlockSpec((1, Hp * Wp, C), lambda b, i, j, taps: (b, 0, 0)),
        pl.BlockSpec((1, L, gp), lambda b, i, j, taps: (j, 0, 0)),
    ]
    args = [xp, values]
    if scales is not None:
        if scales.ndim == 2:
            in_specs.append(
                pl.BlockSpec((1, L), lambda b, i, j, taps: (j, 0)))
        else:
            in_specs.append(
                pl.BlockSpec((1, 1, gp), lambda b, i, j, taps: (j, 0, 0)))
        args.append(scales)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, gp), lambda b, i, j, taps: (0, j)))
        args.append(bias.reshape(1, N))
    has_s, has_b = scales is not None, bias is not None

    def kern(tap_ref, x_ref, w_ref, *rest):
        rest = list(rest)
        s_ref = rest.pop(0) if has_s else None
        b_ref = rest.pop(0) if has_b else None
        _tap_conv_kernel(tap_ref, x_ref, w_ref, s_ref, b_ref, rest[0],
                         act=act, geom=geom)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bm, gp),
                                   lambda b, i, j, taps: (b, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Mp, N), out_dtype),
        interpret=interpret,
    )(taps, *args)


def tap_gather_conv_implicit(x, layout, *, kh, kw, stride=1, padding="SAME",
                             bias=None, bm=128, act="none", interpret=None,
                             out_dtype=None):
    """x (B, H, W, C) * TapLayout -> (B, Ho, Wo, P) implicit tap-gather:
    neither the patch tensor NOR the alive band is materialized in HBM.

    The implicit mirror of ``tap_gather_conv_packed``: one launch per
    degree bin, each filter group gathering its surviving taps straight
    from the padded feature map via the layout's ``k_full`` full-band row
    ids (``alive[t_idx]``, precomputed at pack time by
    ``core.bcs.pattern_lower``; reconstructed on the fly for legacy
    layouts).  Padding slots point at alive[0] with zero values, so they
    gather a real pixel and contribute nothing."""
    B, H, W, C = x.shape
    assert layout.shape[0] == kh * kw * C, (
        f"layout K={layout.shape[0]} != kh*kw*Cin={kh * kw * C}")
    ph, pw, Ho, Wo = conv_geometry(H, W, kh, kw, stride, padding)
    Hp, Wp = H + ph[0] + ph[1], W + pw[0] + pw[1]
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0))).reshape(B, Hp * Wp, C)
    geom = (Hp, Wp, Ho, Wo, stride)
    outs = []
    for vals_b, kf_b, sc_b, bias_b in zip(layout.values,
                                          layout.bin_k_full(),
                                          layout.bin_scales(),
                                          layout.bin_bias(bias)):
        t = kf_b // C
        slot = jnp.stack([(t // kw) * Wp + t % kw, kf_b % C],
                         axis=-1).astype(jnp.int32)
        outs.append(_tap_implicit_bin(xp, vals_b, slot, bias=bias_b,
                                      scales=sc_b, geom=geom, bm=bm,
                                      act=act, interpret=interpret,
                                      out_dtype=out_dtype))
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)
    y = layout.unpermute_cols(y)
    return y[:, :Ho * Wo].reshape(B, Ho, Wo, y.shape[-1])
