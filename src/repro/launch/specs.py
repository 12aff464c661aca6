"""ShapeDtypeStruct input stand-ins for every (arch × shape) dry-run cell.

No device allocation: shardings are attached to the structs so
``jax.jit(...).lower(**specs)`` sees the production layout.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.arch import ArchConfig, ShapeConfig
from repro.models.model import cache_specs
from repro.models.params import ParamSpec, abstract_params, is_spec, model_specs
from repro.parallel.sharding import ParallelConfig, param_shardings, resolve_spec


def _sds(shape, dtype, mesh: Optional[Mesh], logical, pcfg: ParallelConfig):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    spec = resolve_spec(shape, logical, pcfg.act_rules, mesh)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Optional[Mesh],
                pcfg: ParallelConfig) -> Dict[str, Any]:
    B = shape.global_batch
    S = 1 if shape.kind == "decode" else shape.seq_len
    out: Dict[str, Any] = {}
    if cfg.frontend == "embeddings":
        out["frame_embeddings"] = _sds((B, S, cfg.d_model), jnp.dtype(cfg.dtype), mesh,
                                       ("act_batch", "act_seq", "act_embed"), pcfg)
        if shape.kind == "train":
            out["labels"] = _sds((B, S), jnp.int32, mesh, ("act_batch", "act_seq"), pcfg)
        if cfg.cross_attention and shape.kind != "decode":
            out["cond"] = _sds((B, cfg.cross_seq, cfg.d_model), jnp.dtype(cfg.dtype),
                               mesh, ("act_batch", None, "act_embed"), pcfg)
    else:
        out["tokens"] = _sds((B, S), jnp.int32, mesh, ("act_batch", "act_seq"), pcfg)
    return out


def abstract_params_sharded(cfg: ArchConfig, mesh: Optional[Mesh], pcfg: ParallelConfig):
    if mesh is None:
        return abstract_params(cfg)
    sh = param_shardings(model_specs(cfg), mesh, pcfg)
    return abstract_params(cfg, sh)


def abstract_cache_sharded(cfg: ArchConfig, batch: int, cap: int,
                           mesh: Optional[Mesh], pcfg: ParallelConfig):
    specs = cache_specs(cfg, batch, cap, pcfg.kernel)

    def mk(spec: ParamSpec):
        dt = jnp.dtype(spec.dtype or cfg.dtype)
        if mesh is None:
            return jax.ShapeDtypeStruct(spec.shape, dt)
        ps = resolve_spec(spec.shape, spec.logical, pcfg.act_rules, mesh)
        return jax.ShapeDtypeStruct(spec.shape, dt, sharding=NamedSharding(mesh, ps))

    return jax.tree.map(mk, specs, is_leaf=is_spec)


def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Optional[Mesh],
                pcfg: ParallelConfig, optimizer=None) -> Dict[str, Any]:
    """Everything the step function for this cell takes, as sharded structs."""
    params = abstract_params_sharded(cfg, mesh, pcfg)
    batch = batch_specs(cfg, shape, mesh, pcfg)
    if shape.kind == "train":
        assert optimizer is not None
        opt_state = optimizer.abstract_state(params)
        return {"params": params, "opt_state": opt_state, "batch": batch,
                "step": jax.ShapeDtypeStruct((), jnp.int32)}
    if shape.kind == "prefill":
        return {"params": params, "batch": batch}
    cache = abstract_cache_sharded(cfg, shape.global_batch, shape.seq_len, mesh, pcfg)
    return {"params": params, "cache": cache, "batch": batch,
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}
