"""Ahead-of-time compiles of every cell's programs for a described v5e.

The admission prefill at 4096 and 8192 tokens and the engine's serving
step (16 slots, a 4096-entry ring), at the configurations' 6 layers and
published widths, compiled for one chip of a described ``v5e:2x2``: what
the TPU compiler refuses, or what does not fit the chip's 16 GB, fails
here at no chip time.  Each test prints ``memory_analysis()``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.  Keep these tests in this
one file.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import spec

CELLS = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]]
HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """What the program sees on the chip: a TPU backend, so its kernels
    lower through Mosaic, and a fresh cache of jitted programs."""
    from repro.serve import engine as E
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _packed(K, N, block, density, lead, sharding):
    """Abstract PackedLayout of a (K, N) weight: 4 degree bins, each
    padded to the degree ``density`` of its column blocks keeps."""
    from repro.core import bcs as BCS
    from repro.core.packed import PackedLayout
    bk, bn = block
    Kb, Nb = K // bk, N // bn
    L = max(1, round(density * Kb))
    bins = BCS.bin_bounds(Nb, 4)
    return PackedLayout(
        values=tuple(_sds(lead + (b - a, L, bk, bn), jnp.bfloat16, sharding)
                     for a, b in bins),
        k_idx=tuple(_sds(lead + (b - a, L), jnp.int32, sharding)
                    for a, b in bins),
        nnz=_sds(lead + (Nb,), jnp.int32, sharding),
        perm=_sds(lead + (Nb,), jnp.int32, sharding),
        inv_perm=_sds(lead + (Nb,), jnp.int32, sharding),
        scales=None, block=tuple(block), shape=(K, N), n_shards=0)


def _pack_pruned(tree, model, block, density, sharding, path=""):
    """``tree`` with every node whose weight ``model.is_pruned`` names
    replaced by its packed layout, as ``compile_model(keep_dense=False)``
    serves it (the leading stack dims kept)."""
    out = {}
    for k, v in tree.items():
        at = f"{path}/{k}" if path else k
        if isinstance(v, dict) and "w" in v and model.is_pruned(f"{at}/w"):
            w = v["w"]
            out[k] = {"packed": _packed(*w.shape[-2:], block, density,
                                        w.shape[:-2], sharding)}
        elif isinstance(v, dict):
            out[k] = _pack_pruned(v, model, block, density, sharding, at)
        else:
            out[k] = v
    return out


def _served_params(cell, sharding):
    """Shapes of what the cell serves: the family's weights, with every
    projection it prunes packed where the configuration prunes."""
    model, cfg = cell.family(), cell.config
    p = _placed(jax.eval_shape(lambda: model.init(cfg, 0)), sharding)
    pr = cfg.get("pruning")
    if pr:
        p = _pack_pruned(p, model, pr["block"], 1 - pr["rate"], sharding)
    return p


def _report(what, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{what}: arguments {m.argument_size_in_bytes:.4g} B, outputs "
          f"{m.output_size_in_bytes:.4g} B, temporaries "
          f"{m.temp_size_in_bytes:.4g} B, aliased {m.alias_size_in_bytes:.4g}"
          f" B; total {total:.4g} B")
    return total


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_programs_compile(cell_name, one_chip, on_tpu):
    from repro.serve import engine as E
    from repro.serve import kvcache as KV
    cell = spec.load(cell_name)
    cfg, mix = cell.config, cell.traffic
    acfg = cell.family().arch_config(cfg)
    params = _served_params(cell, one_chip)
    for P, _ in mix["prompt_lengths"]:
        c = E._jit_prefill(acfg, None).lower(
            params, _sds((1, P), jnp.int32, one_chip), None).compile()
        assert _report(f"{cell_name} prefill {P}", c) < HBM
        if cfg.get("pruning"):
            assert "tpu_custom_call" in c.as_text()
    n, cap = mix["n_slots"], mix["seq_cap"]
    cache = _placed(jax.eval_shape(
        lambda: KV.init_slots(None, acfg, n, cap)), one_chip)
    col = _sds((n, 1), jnp.int32, one_chip)
    c = E._jit_serving_step(acfg, None).lower(
        params, col, cache, col, _sds((n,), jnp.int32, one_chip)).compile()
    assert _report(f"{cell_name} serving step", c) < HBM
    if cfg.get("pruning"):
        assert "tpu_custom_call" in c.as_text()
