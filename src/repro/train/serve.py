"""Serving entry points: shard_map'd prefill and decode_step builders.

Used by the dry-run (abstract lowering), examples/serve_lm.py (continuous-
batching serving with greedy sampling), and tests/test_serving.py.

``comm="auto"`` resolves a *per-phase* CommConfig from the TuneDB: prefill
and decode are distinct tuned consumers (``sweep.CONSUMERS['all_reduce']``)
with opposite cost structures — decode's tiny latency-bound per-token
combines vs prefill's throughput-bound bulk reduces — so the two phases
select different configs from the same measurements
(``select_config(consumer=..., objective="e2e")``).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import CommConfig
from repro.launch import input_specs as isp
from repro.models import decode as dec
from repro.models import sharding, transformer
from repro.models.common import MeshContext, ModelConfig, Runtime

# Which sweep consumer loop stands in for each serving phase when
# ``comm="auto"`` resolves a config (the per-phase half of the tuned path).
PHASE_CONSUMERS = {"prefill": "prefill", "decode": "decode_step"}


def cache_len(cfg: ModelConfig, shape: isp.ShapeSpec) -> int:
    if cfg.family == "vlm":
        return shape.seq_len + cfg.num_patches
    return shape.seq_len


def serve_msg_bytes(cfg: ModelConfig, shape: isp.ShapeSpec) -> int:
    """Dominant TP-collective message size of a serving phase (bytes).

    Both phases' per-layer combine carries (tokens, d_model) f32 partials:
    decode moves one token per sequence, prefill the whole prompt — the
    message-size axis along which the TuneDB answers diverge.
    """
    tokens = shape.global_batch
    if shape.kind == "prefill":
        tokens *= shape.seq_len
    return 4 * cfg.d_model * tokens


def resolve_serve_comm(cfg: ModelConfig, mesh, comm,
                       shape: isp.ShapeSpec,
                       tune_db_path=None,
                       objective: str = "e2e") -> CommConfig:
    """Per-phase ``comm="auto"`` resolution for the serving path.

    A concrete ``CommConfig`` passes through untouched.  ``"auto"`` asks
    the autotuner for this phase's consumer loop (``PHASE_CONSUMERS``) at
    this phase's message size, ranking by the measured consumer-loop time
    (``objective="e2e"`` — a decode step is exactly the consumer whose
    fixed per-op cost the bare microbench cannot see).
    """
    if isinstance(comm, CommConfig):
        return comm
    from repro.core.collectives import resolve_config
    consumer = PHASE_CONSUMERS.get(shape.kind, "decode_step")
    return resolve_config(comm, "all_reduce", serve_msg_bytes(cfg, shape),
                          mesh=mesh, db_path=tune_db_path,
                          objective=objective, consumer=consumer)


def serve_runtime(cfg: ModelConfig, mesh, comm,
                  shape: isp.ShapeSpec, attn_tiling: str = "auto",
                  tune_db_path=None, objective: str = "e2e") -> Runtime:
    comm = resolve_serve_comm(cfg, mesh, comm, shape,
                              tune_db_path=tune_db_path, objective=objective)
    mesh_ctx = MeshContext.from_mesh(mesh)
    return Runtime(cfg=cfg, mesh=mesh_ctx, comm=comm,
                   attn_tiling=attn_tiling,
                   seq_axes=isp.decode_seq_axes(shape, mesh))


def build_serve_fn(cfg: ModelConfig, mesh, comm,
                   shape: isp.ShapeSpec, attn_tiling: str = "auto",
                   tune_db_path=None, objective: str = "e2e",
                   cache_capacity: int | None = None):
    """Returns (rt, jitted_fn, abstract_args) for the dry-run / serving.

    prefill kind: fn(params, batch) -> ServeState
    decode kind:  fn(params, token, state) -> ServeState

    ``comm`` may be a concrete ``CommConfig`` or ``"auto"`` (per-phase
    TuneDB selection; the resolved config is ``rt.comm``).

    ``cache_capacity`` (prefill only) decouples the KV-cache capacity from
    the prompt length: build the prefill spec at the prompt's own sequence
    length while the caches it returns cover ``cache_capacity`` positions
    (prompt + planned generation).  Defaults to ``cache_len(cfg, shape)``
    — a cache exactly as long as the prompt.
    """
    rt = serve_runtime(cfg, mesh, comm, shape, attn_tiling,
                       tune_db_path=tune_db_path, objective=objective)
    abstract_params = jax.eval_shape(
        lambda k: transformer.init_model(k, cfg, mesh.shape["model"]),
        jax.random.PRNGKey(0))
    pspec = sharding.param_specs(abstract_params, cfg, rt.mesh, fsdp=False)

    # One spec source for both phases: decode_inputs' ServeState spec tree
    # (cache layout, vocab-sharded logits, scalar length) is structural —
    # it does not depend on the fed sequence length — so prefill's
    # out_specs and decode's in/out_specs can never drift.
    (token, state_abs), (token_spec, state_spec) = isp.decode_inputs(
        cfg, shape, mesh)

    if shape.kind == "prefill":
        min_len = cache_len(cfg, shape)
        max_len = cache_capacity if cache_capacity is not None else min_len
        if max_len < min_len:
            raise ValueError(
                f"cache_capacity={max_len} is smaller than the prefill "
                f"shape needs ({min_len}: prompt"
                + (" + patch prefix" if cfg.family == "vlm" else "") + ")")
        batch, bspec = isp.prefill_inputs(cfg, shape, mesh)

        def fn(params, batch):
            return dec.prefill(params, batch, rt, max_len)

        sm = jax.shard_map(fn, mesh=mesh, in_specs=(pspec, bspec),
                           out_specs=state_spec, check_vma=False)
        return rt, jax.jit(sm), (abstract_params, batch)

    # decode
    if cache_capacity is not None:
        raise ValueError("cache_capacity applies to the prefill builder; "
                         "a decode ShapeSpec's seq_len IS the capacity")

    def fn(params, token, state):
        return dec.decode_step(params, token, state, rt)

    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(pspec, token_spec, state_spec),
                       out_specs=state_spec, check_vma=False)
    return rt, jax.jit(sm), (abstract_params, token, state_abs)
