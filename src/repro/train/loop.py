"""Production training loop: data prefetch + async checkpoints + watchdog +
preemption drain, over the shard_map'd train step."""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import AsyncCheckpointer
from repro.data.pipeline import DataConfig, PrefetchLoader, SyntheticLM
from repro.launch import setup as setup_mod
from repro.obs import trace as obs_trace
from repro.runtime.fault_tolerance import PreemptionGuard, StepWatchdog


@dataclasses.dataclass
class LoopConfig:
    n_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    accum_steps: int = 1


def train(sess: setup_mod.Session, data_cfg: DataConfig, loop: LoopConfig,
          log: Callable[[str], None] = print,
          guard: Optional[PreemptionGuard] = None,
          faults=None):
    """Run the training loop.

    ``guard`` lets a caller share one :class:`PreemptionGuard` across
    loops (or pre-arm a software drain via ``guard.request()``); by default
    the loop installs its own.  ``faults`` (a
    :class:`repro.runtime.faults.FaultInjector`) is polled at every step
    boundary: stragglers inject host delay, ``Preempt`` events request the
    drain, and ``RankLost`` raises
    :class:`~repro.runtime.faults.RankLostError` out of the loop — after an
    emergency checkpoint at the last completed step, so the elastic restart
    (``elastic_restore``) resumes from exactly where the rank died.

    A preemption drain persists the optimizer state alongside the params
    (``emergency_save(..., opt_state=...)``): a same-mesh resume via
    :func:`repro.runtime.fault_tolerance.resume_session` then continues
    with identical Adam moments, making the post-resume loss stream
    bitwise-identical to an uninterrupted run.
    """
    mesh = sess.mesh
    daxes = tuple(a for a in mesh.axis_names if a != "model")
    bspec = {"tokens": P(daxes), "labels": P(daxes)}
    step_fn = setup_mod.make_sharded_train_step(
        sess, accum_steps=loop.accum_steps, donate=True)(bspec)

    # Record which comm path this run takes (fused psum vs chunk-overlapped
    # TP reduce / MoE a2a) — the session may have resolved comm_cfg="auto".
    cc = sess.rt.comm
    log(f"[comm] mode={cc.mode.value} scheduling={cc.scheduling.value} "
        f"transport={cc.transport.value} algorithm={cc.algorithm}")

    if sess.opt_state is None:
        sess.opt_state = setup_mod.init_opt_state(sess)
    source = SyntheticLM(data_cfg)
    start_step = int(np.asarray(jax.device_get(sess.opt_state["step"])))
    loader = PrefetchLoader(source, start_step=start_step)
    ckpt = AsyncCheckpointer(loop.ckpt_dir) if loop.ckpt_dir else None
    watchdog = StepWatchdog()
    params, opt_state = sess.params, sess.opt_state
    history = []

    def put(batch):
        sharding = {k: NamedSharding(mesh, bspec[k]) for k in bspec}
        return {k: jax.device_put(jnp.asarray(batch[k]), sharding[k])
                for k in bspec}

    own_guard = guard is None
    if own_guard:
        guard = PreemptionGuard()
        guard.__enter__()
    try:
        for i in range(start_step, start_step + loop.n_steps):
            if faults is not None:
                try:
                    faults.poll(i, guard=guard)
                except Exception:
                    # Rank death: checkpoint the last completed step so the
                    # elastic restart loses at most the in-flight step, then
                    # let the error unwind to the recovery driver.
                    if loop.ckpt_dir:
                        from repro.checkpoint.checkpointer import \
                            emergency_save
                        emergency_save(loop.ckpt_dir, i, params,
                                       opt_state=opt_state)
                    sess.params, sess.opt_state = params, opt_state
                    raise
            if guard.preempted:
                log(f"[preempt] draining at step {i}")
                if loop.ckpt_dir:
                    from repro.checkpoint.checkpointer import emergency_save
                    emergency_save(loop.ckpt_dir, i, params,
                                   opt_state=opt_state)
                break
            batch = next(loader)
            watchdog.start_step(i)
            with obs_trace.span("train.step", cat="train", step=i):
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     put(batch))
                jax.block_until_ready(metrics["loss"])
            ev = watchdog.end_step()
            if ev is not None:
                log(f"[straggler] step {ev.step}: {ev.duration*1e3:.1f}ms "
                    f"(threshold {ev.threshold*1e3:.1f}ms)")
            history.append(float(metrics["loss"]))
            if i % loop.log_every == 0:
                log(f"step {i}: loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e}")
            if ckpt and (i + 1) % loop.ckpt_every == 0:
                ckpt.save(i + 1, params)
            if guard.preempted:
                log(f"[preempt] draining at step {i}")
                if loop.ckpt_dir:
                    from repro.checkpoint.checkpointer import emergency_save
                    emergency_save(loop.ckpt_dir, i + 1, params,
                                   opt_state=opt_state)
                break
    finally:
        if own_guard:
            guard.__exit__(None, None, None)
    if ckpt:
        ckpt.wait()
    loader.close()
    sess.params, sess.opt_state = params, opt_state
    return history
