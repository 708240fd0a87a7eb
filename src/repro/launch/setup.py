"""Launcher glue: build params/runtime/train-step for a (config, mesh) pair.

Two paths:
- ``setup_concrete`` — materializes parameters (smoke tests, examples,
  real training).
- ``setup_abstract``  — ShapeDtypeStructs only (the multi-pod dry-run; no
  device allocation ever happens).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import CommConfig
from repro.models import sharding, transformer
from repro.models.common import MeshContext, ModelConfig, Runtime
from repro.optim import adamw
from repro.train import train_step as ts


@dataclasses.dataclass
class Session:
    cfg: ModelConfig
    mesh: Mesh
    rt: Runtime
    param_spec: Any
    opt_spec: Any
    mask: Any
    oc: adamw.OptConfig
    params: Any = None
    opt_state: Any = None
    ms_mask: Any = None


def build_session(cfg: ModelConfig, mesh: Mesh, comm: CommConfig | str,
                  oc: Optional[adamw.OptConfig] = None, fsdp: bool = False,
                  seed: int = 0, concrete: bool = True,
                  attn_tiling: str = "auto",
                  seq_parallel: bool = False,
                  tune_db_path=None,
                  objective: str = "latency") -> Session:
    """Build a session: mesh, runtime, specs and (``concrete``) params.

    The optimizer state is left to the trainers (:func:`init_opt_state`):
    serving a model never reads its Adam moments, which in f32 take four
    times the bytes of bf16 weights.

    ``comm="auto"`` asks the autotuner for the fastest measured config for
    the LM path's dominant collective — the per-layer row-parallel TP
    combine, an (tokens, d_model) f32 partial sum — falling back to
    ``OPTIMIZED_CONFIG`` on a cold TuneDB.  The lookup size is a nominal
    1K-token microbatch; TuneDB answers by log-space-nearest message size,
    so the estimate only needs the right order of magnitude.
    ``objective="e2e"`` ranks by the measured row_parallel consumer-loop
    time instead of the bare combine latency — the per-layer matmul is
    exactly the hideable compute of the paper's §5 argument.
    """
    mesh_ctx = MeshContext.from_mesh(mesh)
    tp = mesh_ctx.model_size
    oc = oc or adamw.OptConfig()
    if not isinstance(comm, CommConfig):
        from repro.core.collectives import resolve_config
        msg_bytes = 4 * cfg.d_model * 1024
        comm = resolve_config(comm, "all_reduce", msg_bytes, mesh=mesh,
                              db_path=tune_db_path, objective=objective,
                              consumer="row_parallel")

    init_fn = functools.partial(transformer.init_model, cfg=cfg, tp=tp)
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init_fn, key)
    pspec = sharding.param_specs(shapes, cfg, mesh_ctx, fsdp=fsdp)
    plan = sharding.build_fsdp_plan(shapes, cfg, mesh_ctx) if fsdp else None
    rt = Runtime(cfg=cfg, mesh=mesh_ctx, comm=comm, fsdp_plan=plan,
                 attn_tiling=attn_tiling, seq_parallel=seq_parallel)
    mask = sharding.grad_model_sum_mask(shapes, cfg, tp,
                                        seq_parallel=seq_parallel)
    ospec = adamw.state_specs(pspec, oc, rt, plan)

    sess = Session(cfg=cfg, mesh=mesh, rt=rt, param_spec=pspec,
                   opt_spec=ospec, mask=mask, oc=oc)
    sess.ms_mask = sharding.model_sharded_mask(pspec)
    if concrete:
        out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
        sess.params = jax.jit(init_fn, out_shardings=out_shardings)(key)
    return sess


def init_opt_state(sess: Session):
    """Initialize optimizer state with the right shardings (via shard_map so
    the ZeRO slice sizing sees local shards)."""
    mesh = sess.mesh
    rt = sess.rt

    def _init(params):
        return adamw.init_state(params, sess.oc, rt, rt.fsdp_plan)

    fn = jax.jit(jax.shard_map(
        _init, mesh=mesh, in_specs=(sess.param_spec,),
        out_specs=sess.opt_spec, check_vma=False))
    return fn(sess.params)


def batch_spec(cfg: ModelConfig, mesh: Mesh, batch: dict) -> dict:
    """Shard every batch leaf's dim0 over the data axes (pod included)."""
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return jax.tree.map(lambda _: P(axes), batch)


def make_sharded_train_step(sess: Session, accum_steps: int = 1,
                            donate: bool = True):
    rt = sess.rt
    fn = ts.make_train_step(rt, sess.oc, sess.mask, accum_steps,
                            ms_mask=sess.ms_mask)
    metric_spec = {k: P() for k in
                   ("loss", "ce", "aux", "lr", "grad_norm")}

    def wrapped(params, opt_state, batch):
        return fn(params, opt_state, batch)

    bspec = jax.tree.map(
        lambda _: P(tuple(a for a in sess.mesh.axis_names if a != "model")),
        {"tokens": 0, "labels": 0})

    def build(batch_tree_spec):
        sm = jax.shard_map(
            wrapped, mesh=sess.mesh,
            in_specs=(sess.param_spec, sess.opt_spec, batch_tree_spec),
            out_specs=(sess.param_spec, sess.opt_spec, metric_spec),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(0, 1) if donate else ())

    return build


def make_sharded_eval_step(sess: Session):
    rt = sess.rt
    fn = ts.make_eval_step(rt)
    metric_spec = {"loss": P(), "ce": P(), "aux": P()}

    def build(batch_tree_spec):
        sm = jax.shard_map(
            fn, mesh=sess.mesh,
            in_specs=(sess.param_spec, batch_tree_spec),
            out_specs=metric_spec,
            check_vma=False)
        return jax.jit(sm)

    return build
