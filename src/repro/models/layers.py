"""Core layers (manual-SPMD: these run inside shard_map).

Tensor-parallel convention: activations enter replicated across the ``model``
axis; column-parallel matmuls produce sharded features; row-parallel matmuls
produce partial sums that are combined with an ACCL-X all-reduce.  The combine
can run **buffered** (single psum after the full matmul) or **streaming**
(chunk-pipelined ``overlapped_matmul_allreduce``) per the CommConfig — the
paper's §3.1 modes applied to TP.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import collectives, streaming
from repro.core.config import CommMode, Scheduling
from repro.models.common import Runtime


# ----------------------------------------------------------------------
# Initialization helpers (host-side, full arrays; sharded by the launcher)
# ----------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = (1.0 / d_in) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------
# Normalization
# ----------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * lax.rsqrt(var + eps)
    return (h * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary position embedding
# ----------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Tensor-parallel matmuls
# ----------------------------------------------------------------------

def tp_grad_sum(x: jnp.ndarray, rt: Runtime, enable: bool = True) -> jnp.ndarray:
    """Megatron's *f* operator: identity forward, all-reduce backward.

    Placed where a replicated activation enters a model-sharded branch —
    each TP rank back-propagates only its shard's partial cotangent, so the
    backward pass must sum them.  Routed through ACCL-X like every other
    collective.
    """
    if not enable or rt.mesh.tp == 1:
        return x

    @jax.custom_vjp
    def f(y):
        return y

    def fwd(y):
        return y, None

    def bwd(_, ct):
        return (collectives.all_reduce(ct, rt.tp_comm(), rt.comm),)

    f.defvjp(fwd, bwd)
    return f(x)


def scale_grad(x: jnp.ndarray, s: float) -> jnp.ndarray:
    """Identity forward; scales the cotangent by ``s`` in backward.

    Used for losses computed replicated-identically on every TP rank (MoE
    aux): the rank-partial grad convention sums contributions over the model
    axis at sync time, so an identical-on-all-ranks path must pre-scale its
    cotangent by 1/tp to stay exact.
    """
    @jax.custom_vjp
    def f(y):
        return y

    def fwd(y):
        return y, None

    def bwd(_, ct):
        return (ct * s,)

    f.defvjp(fwd, bwd)
    return f(x)


def sp_shard_seq(x: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Slice this rank's seq shard (SP stack entry).

    Custom transpose: the cotangents of the shards are disjoint in time, so
    the backward pass reassembles the full-seq cotangent with an all-gather
    (without this, upstream layers — embeddings — would see only this
    rank's token positions)."""
    if rt.mesh.tp == 1:
        return x

    L = x.shape[1] // rt.mesh.tp

    @jax.custom_vjp
    def f(v):
        shard = lax.axis_index(rt.mesh.axis_model)
        return lax.dynamic_slice_in_dim(v, shard * L, L, axis=1)

    def fwd(v):
        shard = lax.axis_index(rt.mesh.axis_model)
        return lax.dynamic_slice_in_dim(v, shard * L, L, axis=1), None

    def bwd(_, ct):
        return (collectives.all_gather(ct, rt.tp_comm(), rt.comm, axis=1),)

    f.defvjp(fwd, bwd)
    return f(x)


def sp_all_gather(x_s: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Megatron-SP g operator: gather the seq-sharded activation to full.

    Forward all-gather over the seq dim; its AD transpose (psum_scatter)
    sums the rank-partial cotangents — so no separate f operator is needed
    on SP branches.  Use ONLY where the gathered value is consumed by
    rank-local sharded branches; for replicated consumers use
    sp_unshard_seq (identity-slice transpose).
    """
    if rt.mesh.tp == 1:
        return x_s
    return collectives.all_gather(x_s, rt.tp_comm(), rt.comm, axis=1)


def sp_unshard_seq(x_s: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Stack-exit gather: output consumed REPLICATED (final norm / CE), whose
    cotangent is already identical on every rank — the transpose takes this
    rank's slice without summing (a sum would count it tp times)."""
    if rt.mesh.tp == 1:
        return x_s

    L = x_s.shape[1]

    @jax.custom_vjp
    def f(v):
        return collectives.all_gather(v, rt.tp_comm(), rt.comm, axis=1)

    def fwd(v):
        return collectives.all_gather(v, rt.tp_comm(), rt.comm, axis=1), None

    def bwd(_, ct):
        shard = lax.axis_index(rt.mesh.axis_model)
        return (lax.dynamic_slice_in_dim(ct, shard * L, L, axis=1),)

    f.defvjp(fwd, bwd)
    return f(x_s)


def sp_reduce_scatter(partial: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Row-parallel combine in SP form: psum_scatter over the seq dim
    (replaces the all-reduce; same wire volume, sharded result)."""
    if rt.mesh.tp == 1:
        return partial

    @jax.custom_vjp
    def f(v):
        return _sp_rs_fwd(v)

    def _sp_rs_fwd(v):
        # wire in the activation dtype (bf16): half the bytes of an f32
        # combine; the f32 matmul accumulation already happened upstream.
        vt = jnp.moveaxis(v.astype(rt.cfg.dtype), 1, 0)
        out = collectives.reduce_scatter(vt, rt.tp_comm(), rt.comm)
        return jnp.moveaxis(out, 0, 1)

    def fwd(v):
        return _sp_rs_fwd(v), None

    def bwd(_, ct):
        # transpose of (sum over ranks + scatter) with replicated-partials
        # semantics: all-gather the cotangent back to full seq
        g = collectives.all_gather(ct, rt.tp_comm(), rt.comm, axis=1)
        return (g,)

    f.defvjp(fwd, bwd)
    return f(partial)


def col_parallel(x: jnp.ndarray, w_shard: jnp.ndarray) -> jnp.ndarray:
    """Replicated x @ column-sharded w -> feature-sharded output (no comm)."""
    return jnp.dot(x, w_shard, preferred_element_type=jnp.float32).astype(x.dtype)


def row_parallel(x_shard: jnp.ndarray, w_shard: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Feature-sharded x @ row-sharded w -> replicated output (one combine).

    Streaming mode — and any config with ``Scheduling.OVERLAPPED`` — routes
    the combine through ``streaming.overlapped_matmul_allreduce``: the
    per-layer TP reduce is chunked and double-buffered against the matmul,
    reusing the runtime's TP communicator so hop-aware tuning sees the real
    topology.  Buffered+fused issues one psum after the full matmul (paper
    §3.1/§5 applied to TP).  The chunked path agrees with the fused one to
    f32 rounding: a dot over a block of rows may sum in another order.
    """
    if rt.mesh.tp == 1:
        return jnp.dot(x_shard, w_shard, preferred_element_type=jnp.float32
                       ).astype(x_shard.dtype)
    if (rt.comm.mode == CommMode.STREAMING
            or rt.comm.scheduling == Scheduling.OVERLAPPED):
        lead = x_shard.shape[:-1]
        h2 = x_shard.reshape(-1, x_shard.shape[-1])
        out = streaming.overlapped_matmul_allreduce(
            h2, w_shard, rt.tp_comm(), rt.comm)
        return out.reshape(*lead, w_shard.shape[-1]).astype(x_shard.dtype)
    partial = jnp.dot(x_shard, w_shard, preferred_element_type=jnp.float32)
    out = collectives.all_reduce(partial, rt.tp_comm(), rt.comm)
    return out.astype(x_shard.dtype)


# ----------------------------------------------------------------------
# MLP (SwiGLU / GELU), column->row parallel
# ----------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, mlp_type: str, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype)}
    if mlp_type == "swiglu":
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(params, x: jnp.ndarray, rt: Runtime, mlp_type: str,
        sharded: bool | None = None, sp: bool = False) -> jnp.ndarray:
    """``sp=True``: x arrives seq-sharded; all-gather in, psum-scatter out
    (Megatron-SP). Otherwise x is replicated and the f operator applies."""
    if sharded is None:
        sharded = bool(rt.cfg.d_ff) and rt.cfg.d_ff % rt.mesh.tp == 0
    if sp and sharded and rt.mesh.tp > 1:
        x = sp_all_gather(x, rt)
    else:
        x = tp_grad_sum(x, rt, sharded)
    up = col_parallel(x, params["w_up"])
    if mlp_type == "swiglu":
        gate = col_parallel(x, params["w_gate"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    if sp and sharded and rt.mesh.tp > 1:
        partial = jnp.dot(h, params["w_down"],
                          preferred_element_type=jnp.float32)
        return sp_reduce_scatter(partial, rt).astype(x.dtype)
    return row_parallel(h, params["w_down"], rt)


# ----------------------------------------------------------------------
# Vocab-sharded embedding / logits / cross-entropy
# ----------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype):
    emb = (jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02).astype(dtype)
    return {"table": emb}


def embed(params, token_ids: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """Vocab-sharded lookup: local gather + all-reduce of masked rows."""
    table = params["table"]            # (vocab/tp, d) local shard
    tp = rt.mesh.tp
    if tp == 1 or table.shape[0] >= rt.cfg.vocab_size:
        # vocab replicated (not divisible by tp): plain lookup
        return jnp.take(table, token_ids, axis=0)
    shard = lax.axis_index(rt.mesh.axis_model)
    vshard = table.shape[0]
    local = token_ids - shard * vshard
    valid = (local >= 0) & (local < vshard)
    rows = jnp.take(table, jnp.clip(local, 0, vshard - 1), axis=0)
    rows = jnp.where(valid[..., None], rows, jnp.zeros_like(rows))
    return collectives.all_reduce(rows, rt.tp_comm(), rt.comm).astype(table.dtype)


def logits_shard(params, x: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """x (…, d) -> vocab-sharded logits (…, vocab/tp); no combine (CE and
    sampling handle the sharded vocab with two small reductions)."""
    table = params["table"]
    # f operator only when the vocab is genuinely sharded (table is a shard).
    x = tp_grad_sum(x, rt, rt.mesh.tp > 1
                    and table.shape[0] < rt.cfg.vocab_size)
    return jnp.dot(x, table.T.astype(x.dtype), preferred_element_type=jnp.float32)


def cross_entropy_vocab_sharded(logits: jnp.ndarray, labels: jnp.ndarray,
                                rt: Runtime, mask: Optional[jnp.ndarray] = None
                                ) -> jnp.ndarray:
    """Stable CE over vocab-sharded logits: pmax + psum over the model axis."""
    tp = rt.mesh.tp
    if logits.shape[-1] >= rt.cfg.vocab_size:
        tp = 1   # vocab replicated on every model rank: no CE collectives
    z = logits.astype(jnp.float32)
    # Math-neutral stability shift; stop_gradient BEFORE pmax (no JVP rule).
    zmax = lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
    if tp > 1:
        zmax = collectives.all_reduce(zmax, rt.tp_comm(), rt.comm, op="max")
    ez = jnp.exp(z - zmax)
    denom = jnp.sum(ez, axis=-1, keepdims=True)
    if tp > 1:
        denom = collectives.all_reduce(denom, rt.tp_comm(), rt.comm)
    vshard = logits.shape[-1]
    # NOTE (replicated-VJP invariant): consumers of a psum output must be
    # replicated computations.  We therefore psum the *raw* picked logit and
    # form the loss identically on every rank — attaching -log(denom) only on
    # the label-owning rank would starve the other ranks' softmax-denominator
    # gradient.
    if tp > 1:
        shard = lax.axis_index(rt.mesh.axis_model)
        local = labels - shard * vshard
        valid = (local >= 0) & (local < vshard)
        picked_z = jnp.take_along_axis(
            z, jnp.clip(local, 0, vshard - 1)[..., None], axis=-1)[..., 0]
        picked_z = jnp.where(valid, picked_z, 0.0)
        picked_z = collectives.all_reduce(picked_z, rt.tp_comm(), rt.comm)
    else:
        picked_z = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    nll = -(picked_z - zmax[..., 0] - jnp.log(denom[..., 0]))
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def greedy_sample_vocab_sharded(logits: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """argmax over vocab-sharded logits (decode path)."""
    tp = rt.mesh.tp
    vshard = logits.shape[-1]
    local_max = jnp.max(logits, axis=-1)
    local_arg = jnp.argmax(logits, axis=-1)
    if tp == 1 or vshard >= rt.cfg.vocab_size:
        return local_arg
    shard = lax.axis_index(rt.mesh.axis_model)
    global_arg = local_arg + shard * vshard
    gmax = collectives.all_reduce(local_max, rt.tp_comm(), rt.comm, op="max")
    cand = jnp.where(local_max >= gmax, global_arg, jnp.iinfo(jnp.int32).max)
    return collectives.all_reduce(cand, rt.tp_comm(), rt.comm, op="min")
