"""Model "codegen" (paper §4.3): pruning masks + per-layer scheme mapping
-> packed execution params.

``compile_model`` is the compiler step the paper describes between pruning
and deployment: given trained params, the {0,1} mask tree, and the
per-layer scheme mapping produced by ``core.mapper_rule``/``mapper_search``,
it packs every block-pruned layer into a ``core.packed.PackedLayout``
— the single interchange format shared by every sparse consumer — and
installs it as ``params[...]["packed"]`` so ``models.layers.linear``
(attention qkv/out, FFN gate/up/down, SSM in/out projections), the batched
MoE expert path in ``models.moe``, and the conv path in
``models.convnet``/``kernels.ops.sparse_conv2d`` dispatch through the
Pallas block-sparse kernel — PatDNN-style sparsity baked into the executed
code, adapted to TPU tiles.

Layer kinds are detected structurally (``_layer_kind``): block-punched
4-D (P, Q, Kh, Kw) conv weights are im2col-lowered before packing
(``core.bcs.conv_lower``), pattern/connectivity 4-D conv masks are
tap-lowered into a ``core.packed.TapLayout`` (``core.bcs.pattern_lower``)
for the Pallas tap-gather kernel — a pattern pick no longer falls back to
masked-dense — depthwise convs are skipped with a logged reason (§5.2.4),
and everything else packs as a (possibly stacked) GEMM.

Row reordering for load balance (Fig 4) happens here by default
(``reorder=True``): block columns are degree-sorted and binned before
padding, so the executed column degree drops from the max toward the mean
(the report carries ``L`` -> ``L_reordered`` and the gain per layer).

Layer stacks are scanned over a stacked layer axis (MoE expert weights add
an expert axis), so per-layer layouts are padded to common per-bin column
degrees and stacked — one pallas_call per projection *kind* and bin, not
per layer.  Packing itself is vectorized + content-cached (see
``kernels.ops.pack``); a second compile of the same weights is free.

The compile knobs live in one frozen ``CompileSpec`` — the primary
``compile_model(params, masks, mapping, spec=...)`` signature — and the
spec (not ad-hoc kwarg tuples) feeds both the pack-cache keys and the
artifact ``model_digest``, so equivalent invocations hit the same cache
entries however they were spelled.  The old keyword pile
(``keep_dense=``, ``reorder=``, ...) still works as a deprecation shim
that builds a spec.  ``spec.value_dtype="int8"`` turns on the quantized
value path (``core.quant``): packed values are stored int8 with fp32
scale leaves and the Pallas kernels dequantize in-kernel; a per-layer
``SchemeChoice.value_dtype`` (the mapper's precision pick) overrides the
spec default.

The per-layer outcome is returned as a typed ``CompileReport`` (one
``LayerReport`` per visited layer: kind, scheme, L -> L_reordered,
executed fraction, value dtype, or the skip reason), serialized verbatim
into the artifact manifest; ``compiled_summary`` renders it.  Reports
keep a dict-style item protocol, so existing ``row["path"]`` consumers
keep working.
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np
import jax.numpy as jnp

from repro.core import bcs as BCS
from repro.core import quant as QUANT
from repro.core import reweighted as RW
from repro.core.packed import PackedLayout
from repro.kernels import bsr_matmul as BM
from repro.kernels import ops

# schemes the sparse executors can exploit: FC block schemes pack the
# weight as-is; block_punched (the paper's CONV scheme) packs the
# im2col-lowered weight into whole dead BCS blocks; pattern (incl.
# connectivity pruning) carries no block structure and tap-lowers into a
# TapLayout for the tap-gather kernel — see _layer_kind below.
BLOCK_SCHEMES = ("block", "block_row", "block_col")
CONV_SCHEMES = ("block_punched",)
PATTERN_SCHEMES = ("pattern",)
PACKABLE_SCHEMES = BLOCK_SCHEMES + CONV_SCHEMES + PATTERN_SCHEMES

# value dtypes the packed executors can serve (None = keep float values)
VALUE_DTYPES = (None, "int8")


@dataclasses.dataclass(frozen=True)
class CompileSpec:
    """All ``compile_model`` knobs in one frozen, hashable value.

    keep_dense : keep "w" next to "packed" (dense fallback / debugging);
        False drops it to halve serving weight memory.
    reorder : degree-sort + bin block columns before padding (paper Fig 4
        row reordering) so L drops toward the mean degree; outputs stay
        bit-identical (``core.bcs.pack_csc_reordered``).
    n_bins : number of degree bins when reordering.  None uses each
        producer's own default: 4 for block layouts, 8 for tap layouts.
    block_override : force one (bk, bn) packing block for every layer
        (otherwise each layer uses its mapped choice.block).
    min_saving : skip packing when the effective skipped-FLOP fraction is
        not above this.
    implicit : conv x-operand strategy hint for serving dispatch
        (None = auto by patch size, see ``kernels.ops._pick_implicit``).
        Recorded with the report; does not change the packed layouts.
    value_dtype : default serving precision for packed values — None keeps
        float, "int8" quantizes symmetrically with fp32 scale leaves
        (``core.quant``); a per-layer ``SchemeChoice.value_dtype``
        (the mapper's precision pick) overrides this default.
    scale_granularity : scale group for quantized BCS layouts — "block"
        (one fp32 per stored block) or "out" (one per block column).
        Tap layouts always quantize per-filter ("out"): their group=1
        slots hold single values, so a per-slot scale would cost 4 bytes
        per stored value.
    exclude : path substrings never packed (router/embeddings per §5.2.4).
    tp : tensor-parallel degree over the mesh "model" axis.  tp > 1
        column-shards every packed layout (degree-balanced LPT assignment,
        ``core.bcs.shard_columns``) so the shard-parallel kernel drivers
        split block columns across devices.  MoE expert layers under a
        ``moe/`` path are exempt — their expert stack axis already shards
        along "model" for free (``sparse_expert_linear`` asserts it).
        A layer whose column-block count tp does not divide falls back to
        the unsharded layout (reported per layer via ``shards``).

    ``digest_fields()`` is the spec's contribution to the pack-cache key
    and the artifact ``model_digest``: exactly the fields that change the
    produced layouts (``keep_dense`` and ``implicit`` are excluded — they
    only affect serving-time dispatch), so equivalent invocations digest
    identically however the spec was built.
    """
    keep_dense: bool = True
    reorder: bool = True
    n_bins: int | None = None
    block_override: tuple | None = None
    min_saving: float = 0.0
    implicit: bool | None = None
    value_dtype: str | None = None
    scale_granularity: str = "block"
    exclude: tuple = ("router", "embed", "head")
    tp: int = 1

    def __post_init__(self):
        """Validate + normalize (tuples for hashability, checked enums)."""
        if self.value_dtype not in VALUE_DTYPES:
            raise ValueError(f"value_dtype {self.value_dtype!r} not in "
                             f"{VALUE_DTYPES}")
        if self.scale_granularity not in QUANT.GRANULARITIES:
            raise ValueError(
                f"scale_granularity {self.scale_granularity!r} not in "
                f"{QUANT.GRANULARITIES}")
        if self.block_override is not None:
            bo = tuple(int(b) for b in self.block_override)
            if len(bo) != 2:
                raise ValueError(f"block_override must be (bk, bn), got "
                                 f"{self.block_override!r}")
            object.__setattr__(self, "block_override", bo)
        object.__setattr__(self, "exclude", tuple(self.exclude))
        if self.n_bins is not None:
            object.__setattr__(self, "n_bins", int(self.n_bins))
        if int(self.tp) < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        object.__setattr__(self, "tp", int(self.tp))

    def digest_fields(self) -> tuple:
        """The layout-determining fields, in a stable order — what the
        artifact ``model_digest`` hashes for the compile-knob part."""
        return (self.block_override, float(self.min_saving),
                bool(self.reorder), self.n_bins, tuple(self.exclude),
                self.value_dtype, str(self.scale_granularity),
                int(self.tp))

    def to_json(self) -> dict:
        """Plain-JSON form (manifest serialization)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "CompileSpec":
        """Rebuild from ``to_json`` output (lists back to tuples)."""
        d = dict(d)
        if d.get("block_override") is not None:
            d["block_override"] = tuple(d["block_override"])
        if d.get("exclude") is not None:
            d["exclude"] = tuple(d["exclude"])
        return cls(**d)


# LayerReport fields always present in the item protocol even when falsy
_ALWAYS_KEYS = ("path", "packed")


@dataclasses.dataclass(frozen=True)
class LayerReport:
    """One layer's line of the compile log, typed.

    ``packed`` rows carry the layout geometry and the load-balance lever
    (pre-reorder padded degree ``L`` -> post-reorder ``L_reordered`` of
    ``Kb`` column blocks), the executed fraction (``1 - flops_saved``),
    the mapped ``scheme`` and the served ``value_dtype`` (None = float).
    Skipped rows carry the ``reason``; rows whose layout later failed
    validation and was retired by ``degrade_invalid_layers`` carry
    ``degraded=True`` plus the failure reason.  A dict-style item protocol
    (``row["path"]``, ``row.get(...)``, ``"kind" in row`` — None fields
    read as absent) keeps the historical dict-row consumers working.
    """
    path: str
    packed: bool
    kind: str | None = None
    scheme: str | None = None
    reason: str | None = None
    block: tuple | None = None
    shape: tuple | None = None
    L: int | None = None
    Kb: int | None = None
    L_reordered: float | None = None
    reorder_gain: float | None = None
    density: float | None = None
    flops_saved: float | None = None
    layers: int | None = None
    value_dtype: str | None = None
    patch_b_per_pos: int | None = None
    shards: int | None = None
    degraded: bool | None = None

    @property
    def executed_frac(self) -> float | None:
        """Fraction of dense FLOPs the padded layout actually executes."""
        return None if self.flops_saved is None else 1.0 - self.flops_saved

    def __getitem__(self, key):
        """Dict-style field access; None-valued fields raise KeyError."""
        v = getattr(self, key, None) if not key.startswith("_") else None
        if v is None and key not in _ALWAYS_KEYS:
            raise KeyError(key)
        return v

    def get(self, key, default=None):
        """Dict-style ``get`` over the non-None fields."""
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key) -> bool:
        """Dict-style membership: a field is "present" when non-None."""
        return self.get(key) is not None or key in _ALWAYS_KEYS

    def to_json(self) -> dict:
        """Plain-JSON row: only the present (non-None) fields."""
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None or k in _ALWAYS_KEYS}

    @classmethod
    def from_json(cls, d: dict) -> "LayerReport":
        """Rebuild from ``to_json`` output (lists back to tuples)."""
        d = {k: v for k, v in d.items()
             if k in {f.name for f in dataclasses.fields(cls)}}
        for k in ("block", "shape"):
            if d.get(k) is not None:
                d[k] = tuple(d[k])
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class CompileReport:
    """The typed compile log ``compile_model`` returns (and the artifact
    manifest stores verbatim): one ``LayerReport`` per visited layer plus
    the ``CompileSpec`` that produced it.  Iterates/indexes like the
    historical list of rows."""
    rows: tuple = ()
    spec: CompileSpec | None = None

    def __iter__(self):
        """Iterate the per-layer rows."""
        return iter(self.rows)

    def __len__(self) -> int:
        """Number of per-layer rows."""
        return len(self.rows)

    def __getitem__(self, i):
        """Index the per-layer rows (int or slice)."""
        return self.rows[i]

    @property
    def packed(self) -> tuple:
        """The rows that produced a layout."""
        return tuple(r for r in self.rows if r.packed)

    @property
    def skipped(self) -> tuple:
        """The rows skipped with a reason."""
        return tuple(r for r in self.rows if not r.packed)

    def to_json(self) -> dict:
        """Manifest form: {"spec": ..., "layers": [row, ...]}."""
        return {"spec": self.spec.to_json() if self.spec else None,
                "layers": [r.to_json() for r in self.rows]}

    @classmethod
    def from_json(cls, d) -> "CompileReport":
        """Rebuild from ``to_json`` output — also accepts the historical
        bare list-of-row-dicts manifests."""
        if isinstance(d, dict):
            spec = (CompileSpec.from_json(d["spec"])
                    if d.get("spec") else None)
            rows = d.get("layers", ())
        else:
            spec, rows = None, d
        return cls(rows=tuple(LayerReport.from_json(r) for r in rows),
                   spec=spec)


def _layer_kind(w, scheme: str) -> str:
    """Structural layer-kind detection — what decides the layout producer,
    instead of path-name heuristics:

      conv         : 4-D (P, Q, Kh, Kw) weight mapped to a CONV block
                     scheme -> im2col BCS producer
      pattern_conv : 4-D conv weight mapped to the pattern scheme ->
                     tap-gather producer (per-kernel pattern masks carry no
                     block structure, so the skippable unit is a tap)
      depthwise    : conv with Q == 1 (never packed, §5.2.4)
      linear       : trailing (K, N) GEMM weight, any leading stack dims
                     (scanned layers, MoE experts, or both)

    The mapped scheme disambiguates rank-4 weights: a stacked MoE expert
    weight (L, E, K, N) is also 4-D, but the mapper only ever assigns
    ``block_punched``/``pattern`` to real conv weights (their groups are
    kernel positions), so scheme + rank identifies the producer."""
    if scheme in CONV_SCHEMES + PATTERN_SCHEMES:
        if getattr(w, "ndim", 0) != 4:
            return "bad_conv"
        if w.shape[1] == 1:
            return "depthwise"
        return "pattern_conv" if scheme in PATTERN_SCHEMES else "conv"
    return "linear"


def _stack_pad_L(arrays, Lb, axis=1):
    """Stack per-slice bin arrays after zero-padding ``axis`` (the column
    degree — 1 unsharded, 2 behind the shard axis) to ``Lb`` — padding
    slots keep k_idx 0 / zero values."""
    out = []
    for a in arrays:
        a = np.asarray(a)
        pad = Lb - a.shape[axis]
        if pad:
            shp = list(a.shape)
            shp[axis] = pad
            a = np.concatenate([a, np.zeros(shp, a.dtype)], axis)
        out.append(a)
    return np.stack(out)


def _pack_stacked(w, mask, block, *, reorder=True, n_bins=4,
                  value_dtype=None, scale_granularity="block", n_shards=0):
    """Pack (..., K, N) weights slice-by-slice, pad every slice's per-bin
    column degree to the stack max, and restack -> a scan/vmap-compatible
    ``PackedLayout`` whose leaves carry the leading stack dims (layers,
    experts, or both).  ``value_dtype="int8"`` quantizes the STACKED
    layout (one ``core.quant`` pass over the restacked leaves — the
    per-slice float packs stay cached as-is).  ``n_shards`` > 0 shards
    every slice's block columns tensor-parallel (degree-balanced LPT,
    ``core.bcs.shard_columns``); the shard axis stays the innermost stack
    dim on every per-bin leaf.

    Returns (PackedLayout, stats)."""
    w = np.asarray(w)
    mask = np.broadcast_to(np.asarray(mask), w.shape)
    lead = w.shape[:-2]
    K, N = w.shape[-2:]
    bk, bn = block
    Kb = K // bk
    S = int(n_shards)
    wf = w.reshape((-1, K, N))
    mf = mask.reshape((-1, K, N))
    layouts = [ops.pack(wf[i], mf[i], block, reorder=reorder, n_bins=n_bins,
                        n_shards=S)
               for i in range(wf.shape[0])]
    nb = layouts[0].n_bins                    # identical across slices
    shard = (S,) if S else ()
    deg_axis = 2 if S else 1                  # degree sits behind the shard
    values, k_idx = [], []
    for b in range(nb):
        Lb = max(l.bin_degrees[b] for l in layouts)
        values.append(jnp.asarray(_stack_pad_L(
            [l.values[b] for l in layouts], Lb, deg_axis).reshape(
                lead + shard + (-1, Lb, bk, bn))))
        k_idx.append(jnp.asarray(_stack_pad_L(
            [l.k_idx[b] for l in layouts], Lb, deg_axis).reshape(
                lead + shard + (-1, Lb))))

    def restack(get):
        a = np.stack([np.asarray(get(l)) for l in layouts])
        return jnp.asarray(a.reshape(lead + a.shape[1:]))

    nnz = restack(lambda l: l.nnz)
    has_perm = reorder or S
    perm = restack(lambda l: l.perm) if has_perm else None
    inv_perm = restack(lambda l: l.inv_perm) if has_perm else None
    stacked = PackedLayout(values=tuple(values), k_idx=tuple(k_idx),
                           nnz=nnz, perm=perm, inv_perm=inv_perm,
                           block=tuple(block), shape=(K, N), n_shards=S)
    if value_dtype is not None:
        stacked = QUANT.quantize_layout(
            stacked, value_dtype=value_dtype,
            scale_granularity=scale_granularity)
    # L: the padded max column degree (what every column pays without
    # reordering); L_reordered: mean executed degree under the binned
    # stacked layout.  Equal when reorder is off.
    L_pre = max(1, int(np.asarray(nnz).max()))
    L_eff = stacked.L_effective
    stats = {
        "block": tuple(block), "shape": (K, N), "L": L_pre, "Kb": Kb,
        "L_reordered": round(L_eff, 2),
        "reorder_gain": round(L_pre / max(L_eff, 1e-9), 2),
        "density": stacked.density,
        "flops_saved": stacked.flops_saved,
        "layers": int(np.prod(lead)) if lead else 1,
    }
    return stacked, stats


# the historical compile_model keyword pile, now a deprecation shim that
# builds a CompileSpec (same defaults)
_LEGACY_SPEC_KWARGS = ("block_override", "keep_dense", "min_saving",
                       "reorder", "n_bins", "exclude", "implicit",
                       "value_dtype", "scale_granularity")


def resolve_spec(spec=None, **legacy) -> CompileSpec:
    """Resolve the ``spec``-or-legacy-kwargs compile surface to one
    ``CompileSpec``: pass ``spec`` through, build one from the historical
    keywords (DeprecationWarning), reject mixing the two."""
    legacy = {k: v for k, v in legacy.items() if v is not None}
    bad = set(legacy) - set(_LEGACY_SPEC_KWARGS)
    if bad:
        raise TypeError(f"unknown compile_model argument(s): {sorted(bad)}")
    if spec is not None:
        if legacy:
            raise TypeError(
                f"pass spec= OR legacy keywords, not both (got spec and "
                f"{sorted(legacy)})")
        if not isinstance(spec, CompileSpec):
            raise TypeError(f"spec must be a CompileSpec, got "
                            f"{type(spec).__name__}")
        return spec
    if legacy:
        warnings.warn(
            "compile_model(keep_dense=..., reorder=..., ...) keywords are "
            "deprecated; pass spec=CompileSpec(...) instead",
            DeprecationWarning, stacklevel=3)
    return CompileSpec(**legacy)


def compile_model(params, masks=None, mapping=(), spec=None, *,
                  artifact_dir=None, **legacy):
    """Pack every block-pruned linear/conv layer of ``params`` for sparse
    execution.  Returns (exec_params, CompileReport).

    params   : model param tree (nested dicts; linear nodes hold "w").
    masks    : {0,1} mask tree matching ``params`` (scalar sentinels on
               unpruned leaves, as built by ``reweighted.masks_for_spec``).
               None derives masks from the zeros already baked into ``w``
               (i.e. params after ``trainer.apply_masks``).
    mapping  : PruneSpec [(path_regex, SchemeChoice)] from the mapper —
               only paths mapped to a packable scheme are packed (FC block
               schemes pack the weight as-is; ``block_punched`` conv
               layers pack the im2col-lowered weight; ``pattern`` conv
               layers tap-lower into a TapLayout for the tap-gather
               kernel).  A choice's ``value_dtype`` (the mapper's
               precision pick) overrides ``spec.value_dtype`` per layer.
    spec     : ``CompileSpec`` — the primary compile surface; see its
               docstring for every knob.  The historical keywords
               (``keep_dense=``, ``reorder=``, ``n_bins=``,
               ``block_override=``, ``min_saving=``, ``exclude=``, plus
               the new ``implicit=``/``value_dtype=``/
               ``scale_granularity=``) still work as a deprecation shim
               that builds an equivalent spec; mixing both is an error.
    artifact_dir : AOT artifact store (``serve.artifacts``).  When set,
               the model digest (weights + masks + mapping + spec digest
               fields) is looked up first: digest match -> checksum verify
               -> layout validation -> warm start with the stored layouts
               grafted on (no packing at all).  Digest mismatch, checksum
               failure, version skew, or invariant violation logs its
               structured reason and falls back to THIS fresh pack, whose
               result is then published crash-safely (tmp + atomic
               rename) for the next start.

    On a TPU backend, a layer whose layout the Pallas kernels cannot lower
    there (a block that is not lane-aligned, int8 values, tap layouts —
    see ``kernels.bsr_matmul.tpu_refusal``) raises ``ValueError`` here,
    before anything is packed: it is never packed and then interpreted.

    Every packed ``LayerReport`` carries the effective density, the
    pre-reorder padded column degree L, the post-reorder ``L_reordered``
    with its gain, the skipped-FLOP fraction, and the served value dtype;
    skipped rows carry the reason, so the report doubles as the compile
    log.
    """
    spec = resolve_spec(spec, **legacy)
    artifact_key = None
    if artifact_dir is not None:
        from repro.serve import artifacts as ART
        artifact_key = ART.model_digest(params, masks, mapping, spec=spec)
        warm = ART.load_grafted(artifact_dir, artifact_key, params,
                                keep_dense=spec.keep_dense)
        if warm is not None:
            return warm

    rows = []
    # per-producer bin defaults (None = use each producer's own): block
    # layouts 4, tap layouts 8 — see kernels.ops.pack_taps
    gemm_bins = 4 if spec.n_bins is None else spec.n_bins
    tap_bins = 8 if spec.n_bins is None else spec.n_bins
    reorder = spec.reorder

    def refuse_on_tpu(wpath, kind, *layout):
        """On a TPU backend, raise if its kernels cannot serve this layer."""
        why = BM.refusal_here(kind, *layout)
        if why:
            raise ValueError(f"{wpath} cannot be served on TPU: {why}")

    def walk(p, m, path):
        if not isinstance(p, dict):
            return p
        out = {k: walk(v, m.get(k) if isinstance(m, dict) else None,
                       f"{path}/{k}" if path else k)
               for k, v in p.items()}
        w = p.get("w")
        if w is None or isinstance(w, dict) or getattr(w, "ndim", 0) < 2:
            return out
        wpath = f"{path}/w" if path else "w"

        def skip(reason):
            rows.append(LayerReport(path=wpath, packed=False, reason=reason))
            return out

        if any(e in wpath for e in spec.exclude):
            return skip("excluded")
        choice = RW.match(list(mapping), wpath)
        if choice is None or choice.scheme not in PACKABLE_SCHEMES:
            return skip("no block scheme mapped")
        kind = _layer_kind(w, choice.scheme)
        if kind == "depthwise":
            return skip("depthwise conv never packed (§5.2.4)")
        if kind == "bad_conv":
            return skip(f"{choice.scheme} needs a (P, Q, Kh, Kw) conv "
                        f"weight, got shape {tuple(w.shape)}")
        mask = m.get("w") if isinstance(m, dict) else None
        if masks is None:
            mask = np.asarray(w) != 0
        elif mask is None or getattr(mask, "ndim", 0) == 0:
            return skip("no mask (layer not pruned)")
        block = tuple(spec.block_override or choice.block)
        # per-layer precision: the mapper's pick wins over the spec default
        vdt = getattr(choice, "value_dtype", None) or spec.value_dtype
        if vdt not in VALUE_DTYPES:
            return skip(f"unsupported value_dtype {vdt!r}")
        # tensor-parallel column sharding: MoE expert stacks are exempt
        # (their leading expert axis shards along "model" for free —
        # sparse_expert_linear asserts column sharding never reaches it);
        # a layer whose column count tp does not divide falls back to the
        # unsharded layout, recorded via the report's ``shards`` field.
        shards = 0 if "moe" in wpath.split("/") else (
            spec.tp if spec.tp > 1 else 0)
        if kind == "pattern_conv":
            # tap producer: pattern/connectivity masks carry no block
            # structure (every kernel keeps its own tap set), so the layer
            # lowers to per-filter tap lists over the im2col band and
            # executes through the tap-gather kernel — the scheme the
            # mapper picked for accuracy now runs sparsely instead of
            # silently falling back to masked-dense.  Quantized taps always
            # use per-filter ("out") scales — group=1 slots hold single
            # values, so per-slot scales would cost 4 bytes per value.
            refuse_on_tpu(wpath, "tap")
            if shards and w.shape[0] % shards:
                shards = 0                      # tp does not divide filters
            tap = ops.pack_taps(w, mask, reorder=reorder, n_bins=tap_bins,
                                value_dtype=vdt, scale_granularity="out",
                                n_shards=shards)
            P, Q, Kh, Kw = w.shape
            stats = {
                "block": (1, tap.group), "shape": tap.shape,
                "L": tap.L_max, "Kb": tap.shape[0],
                "L_reordered": round(tap.L_effective, 2),
                "reorder_gain": round(
                    tap.L_max / max(tap.L_effective, 1e-9), 2),
                "density": tap.density,
                "flops_saved": tap.flops_saved,
                "layers": 1,
                # implicit-GEMM accounting: patch bytes the materialized
                # path would allocate PER OUTPUT POSITION (total = B*Ho*Wo
                # of these), which the implicit tap kernel never touches
                "patch_b_per_pos": Kh * Kw * Q * w.dtype.itemsize,
            }
            packed = tap
        elif kind == "conv":
            # im2col producer: lower weight AND mask to the GEMM the conv
            # executes as (kernels.ops.sparse_conv2d), then reuse the one
            # packing pipeline.  The kernel-block choice (bp filters, bq
            # channels) becomes GEMM block (bq, bp) — see bcs.conv_lower.
            gemm_block, why = BCS.conv_gemm_block(block, w.shape)
            if gemm_block is None:
                return skip(why)
            P, Q, Kh, Kw = w.shape
            refuse_on_tpu(wpath, "bcs", gemm_block, (Kh * Kw * Q, P), vdt)
            wl = BCS.conv_lower(w)
            ml = BCS.conv_lower(np.broadcast_to(np.asarray(mask), w.shape))
            if shards and (wl.shape[-1] // gemm_block[1]) % shards:
                shards = 0                  # tp does not divide Nb
            packed, stats = _pack_stacked(
                wl, ml, gemm_block, reorder=reorder, n_bins=gemm_bins,
                value_dtype=vdt, scale_granularity=spec.scale_granularity,
                n_shards=shards)
            # attach the static tap-offset table so the implicit-GEMM
            # kernel can gather from the feature map without a patch tensor
            packed = dataclasses.replace(
                packed,
                conv_taps=BCS.conv_tap_table(Kh, Kw, Q, gemm_block[0]))
            stats["patch_b_per_pos"] = Kh * Kw * Q * w.dtype.itemsize
        else:
            K, N = w.shape[-2:]
            if K % block[0] or N % block[1]:
                return skip(f"block {block} does not divide ({K}, {N})")
            refuse_on_tpu(wpath, "bcs", block, (K, N), vdt)
            if shards and (N // block[1]) % shards:
                shards = 0                  # tp does not divide Nb
            packed, stats = _pack_stacked(
                w, mask, block, reorder=reorder, n_bins=gemm_bins,
                value_dtype=vdt, scale_granularity=spec.scale_granularity,
                n_shards=shards)
        if stats["flops_saved"] <= spec.min_saving:
            return skip(f"no effective saving (L={stats['L']} of "
                        f"Kb={stats['Kb']} column blocks survive)")
        out["packed"] = packed
        if not spec.keep_dense:
            del out["w"]
        rows.append(LayerReport(path=wpath, packed=True, kind=kind,
                                scheme=choice.scheme, value_dtype=vdt,
                                shards=shards or None, **stats))
        return out

    exec_params = walk(params, masks, "")
    report = CompileReport(rows=tuple(rows), spec=spec)
    if artifact_key is not None:
        # publish for the next (replica) start; best-effort — an
        # unwritable store must never fail the compile itself
        try:
            ART.save_artifact(artifact_dir, artifact_key, exec_params,
                              report)
        except OSError as e:
            import logging
            logging.getLogger("repro.serve.artifacts").warning(
                "could not publish artifact to %s: %s", artifact_dir, e)
    return exec_params, report


def compiled_summary(report) -> str:
    """One-line-per-layer compile log, including the load-balance lever
    (pre-reorder L -> post-reorder effective L and the gain), the served
    value dtype for quantized layers, and, for conv layers, the im2col
    patch bytes per output position the implicit-GEMM path avoids
    allocating (total avoided = B*Ho*Wo of these).  Accepts a typed
    ``CompileReport`` or the historical list of row dicts."""
    lines = []
    for r in report:
        if r["packed"]:
            line = (
                f"  pack {r['path']:<28s} [{r.get('kind', 'linear')}] "
                f"block={r['block']} "
                f"density={r['density']:.2f} "
                f"L={r['L']}->{r['L_reordered']}/{r['Kb']} "
                f"(reorder_gain={r['reorder_gain']:.2f}x) "
                f"flops_saved={r['flops_saved']:.2f}")
            if r.get("value_dtype"):
                line += f" values={r['value_dtype']}"
            if r.get("shards"):
                line += f" tp={r['shards']}"
            if "patch_b_per_pos" in r:
                line += f" implicit_avoids={r['patch_b_per_pos']}B/pos"
            if r.get("degraded"):
                line += " [DEGRADED -> masked-dense]"
            lines.append(line)
        else:
            lines.append(f"  skip {r['path']:<28s} ({r['reason']})")
    return "\n".join(lines)


def degrade_invalid_layers(exec_params, report=None):
    """Runtime/graft guard: validate every packed layout of an exec-param
    tree and retire any failure to the masked-dense ``DegradedLayer``
    path — that layer alone executes as a dense einsum over its retained
    ``w`` (pruning zeros baked in), every other layer keeps its sparse
    kernel.  Never silent: each degradation logs a structured warning
    and, when a ``CompileReport`` is passed, its matching row is
    re-emitted with ``degraded=True`` and the failure reason.

    Layouts are valid by construction out of ``compile_model`` and fully
    re-validated on artifact graft, so this guard exists for corruption
    that happens AFTER those checks: bit rot in process memory, a buggy
    external layout producer, a chaos-harness injection
    (``repro.testing.faults``).  ``serve.engine.ServingEngine`` runs it at
    construction and counts the result in ``stats["degraded_layers"]``.

    A corrupt layout whose node lost its dense ``w`` (packed with
    ``keep_dense=False``) CANNOT be degraded — the original
    ``LayoutError`` is re-raised, because a repack is the only safe
    answer and a silent wrong result never is.

    Returns ``(exec_params, report, degraded)``: the (skeleton-copied,
    leaf-shared) tree, the updated report (``None``/unknown types pass
    through unchanged), and ``degraded`` as ``[(layer_path,
    LayoutError), ...]``.
    """
    import logging

    from repro.core import validate as V
    from repro.core.packed import DegradedLayer

    log = logging.getLogger("repro.serve.compile")
    degraded = []

    def walk(node, path):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            sub = f"{path}/{k}" if path else k
            if k != "packed":
                out[k] = walk(v, sub)
                continue
            if v is None or isinstance(v, (dict, DegradedLayer)):
                out[k] = v
                continue
            try:
                out[k] = V.validate_layout(v, path=sub)
            except V.LayoutError as e:
                if "w" not in node:
                    raise     # no dense fallback weight: repack or die
                out[k] = DegradedLayer(path=path or "packed", code=e.code,
                                       detail=e.detail)
                degraded.append((path, e))
                log.warning(
                    "layer %s: packed layout failed validation — "
                    "degrading to masked-dense execution: %s", path, e)
        return out

    tree = walk(exec_params, "")
    if isinstance(report, CompileReport) and degraded:
        bad = {(f"{p}/w" if p else "w"): e for p, e in degraded}
        rows = tuple(
            dataclasses.replace(
                r, degraded=True,
                reason=f"[{bad[r.path].code}] degraded to masked-dense: "
                       f"{bad[r.path].detail}")
            if r.path in bad else r
            for r in report)
        report = dataclasses.replace(report, rows=rows)
    return tree, report, degraded
