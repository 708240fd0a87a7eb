"""Fault-tolerance runtime: straggler watchdog, preemption handler, elastic
re-meshing.

At 1000+ nodes, *something* is always failing.  The framework's contract:

1. **Checkpoint/restart** — async sharded checkpoints every N steps
   (repro.checkpoint) + restore-with-resharding onto whatever mesh survives.
2. **Preemption** — SIGTERM triggers a synchronous emergency checkpoint at
   the next step boundary (the loop polls a flag; the handler never touches
   jax state from the signal context).
3. **Straggler mitigation** — a step-time watchdog keeps a robust running
   estimate (median + MAD); steps slower than ``median + k·MAD`` are logged
   with their host metadata.  On a real deployment this feeds the scheduler
   that re-shards around the slow host; here it drives tests and metrics.
4. **Elastic re-mesh** — given a checkpoint and a NEW device topology,
   ``elastic_restore`` rebuilds the session on the surviving mesh and
   reshards every array (ZeRO slices are re-flattened automatically since
   the optimizer state layout is a pure function of (params, mesh)).
"""
from __future__ import annotations

import dataclasses
import signal
import statistics
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# ----------------------------------------------------------------------
# Straggler watchdog
# ----------------------------------------------------------------------

@dataclasses.dataclass
class StragglerEvent:
    step: int
    duration: float
    threshold: float


class StepWatchdog:
    """Robust step-time outlier detection (median + k·MAD).

    Retention is bounded for long-running jobs: ``events`` keeps the most
    recent ``max_events`` stragglers (older ones are counted in
    ``events_dropped`` and the ``watchdog.events_dropped`` metrics counter,
    never silently lost), and ``durations`` keeps enough history for the
    rolling ``window`` plus a stable ``median_step`` — O(1) memory over an
    unbounded run instead of one float per step forever.

    Every completed step emits a ``watchdog.step`` instant event when
    tracing is on; detected stragglers additionally emit
    ``watchdog.straggler`` and bump the ``watchdog.stragglers`` counter.
    """

    def __init__(self, k: float = 5.0, warmup: int = 5, window: int = 50,
                 max_events: int = 256):
        self.k = k
        self.warmup = warmup
        self.window = window
        self.max_events = max_events
        self.durations: deque[float] = deque(maxlen=max(4 * window, 200))
        self.events: deque[StragglerEvent] = deque(maxlen=max_events)
        self.events_dropped = 0
        self._t0: Optional[float] = None
        self._step = 0

    def start_step(self, step: int):
        self._step = step
        self._t0 = time.perf_counter()

    def end_step(self) -> Optional[StragglerEvent]:
        if self._t0 is None:
            return None
        dt = time.perf_counter() - self._t0
        # Consume the start mark: a second end_step at the same boundary is
        # a no-op instead of appending the duration twice (which would skew
        # the median and could emit a phantom straggler).
        self._t0 = None
        hist = list(self.durations)[-self.window:]
        event = None
        if len(hist) >= self.warmup:
            med = statistics.median(hist)
            mad = statistics.median([abs(x - med) for x in hist]) or 1e-9
            thr = med + self.k * mad
            if dt > thr:
                event = StragglerEvent(self._step, dt, thr)
                if len(self.events) == self.events.maxlen:
                    self.events_dropped += 1
                    obs_metrics.registry().counter(
                        "watchdog.events_dropped").inc()
                self.events.append(event)
                obs_metrics.registry().counter("watchdog.stragglers").inc()
                obs_trace.instant("watchdog.straggler", cat="watchdog",
                                  step=self._step, ms=dt * 1e3,
                                  threshold_ms=thr * 1e3)
        self.durations.append(dt)
        obs_trace.instant("watchdog.step", cat="watchdog", step=self._step,
                          ms=dt * 1e3)
        return event

    @property
    def median_step(self) -> float:
        return statistics.median(self.durations) if self.durations else 0.0


# ----------------------------------------------------------------------
# Preemption handling
# ----------------------------------------------------------------------

class PreemptionGuard:
    """SIGTERM/SIGINT -> set a flag; the training loop checkpoints and exits
    at the next step boundary.

    Contract details that matter in production:

    - **SIGINT is guarded by default** — a Ctrl-C drains exactly like a
      scheduler's SIGTERM instead of stack-tracing mid-step.
    - **Pre-existing custom handlers are chained**, not dropped: if the
      launcher installed its own SIGTERM hook, the guard sets its flag and
      then calls the old handler.  Default dispositions (``SIG_DFL``,
      ``SIG_IGN``, Python's KeyboardInterrupt handler) are *replaced* — the
      whole point is to turn them into a drain.
    - **Nested / re-entrant use restores correctly**: each ``__enter__``
      pushes the handlers it displaced and ``__exit__`` pops exactly that
      frame, so an inner guard (e.g. an eval loop inside the train loop)
      hands the signals back to the outer one, not to the defaults.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._requested = threading.Event()
        self._stack: list[dict] = []
        self._signals = tuple(signals)

    @staticmethod
    def _chainable(old) -> bool:
        """Is ``old`` a custom handler worth chaining?  Dispositions and
        Python's default KeyboardInterrupt raiser are not — replacing them
        IS the guard's job."""
        return callable(old) and old is not signal.default_int_handler

    def __enter__(self):
        frame = {}
        for sig in self._signals:
            old = signal.getsignal(sig)
            frame[sig] = old
            chain = old if self._chainable(old) else None

            def handler(signum, sframe, _chain=chain):
                self._requested.set()
                if _chain is not None:
                    _chain(signum, sframe)

            signal.signal(sig, handler)
        self._stack.append(frame)
        return self

    def __exit__(self, *exc):
        frame = self._stack.pop()
        for sig, old in frame.items():
            signal.signal(sig, old)
        return False

    @property
    def preempted(self) -> bool:
        return self._requested.is_set()

    def request(self):   # for tests / software-triggered drain
        self._requested.set()


# ----------------------------------------------------------------------
# Elastic re-meshing
# ----------------------------------------------------------------------

def survivor_topology(topology, new_mesh):
    """The :class:`~repro.core.topology.TorusSpec` the survivors re-form on:
    ``topology.shrink`` at the new mesh's device count (identity when the
    count is unchanged or there was no torus)."""
    if topology is None:
        return None
    n_new = int(np.prod(list(new_mesh.shape.values())))
    return topology if n_new == topology.n_ranks else topology.shrink(n_new)


def _ring_hops(spec) -> int:
    """Worst-case hop distance of the rank ring on ``spec`` (the LM TP
    combine's wire pattern) — what the re-selection prices the new fabric
    at."""
    if spec is None:
        return 1
    n = spec.n_ranks
    return max((spec.hops(i, (i + 1) % n) for i in range(n)), default=1)


def elastic_restore(ckpt_dir, cfg, new_mesh, comm, oc, step: Optional[int] = None,
                    fsdp: bool = False, reselect: bool = False,
                    tune_db_path=None, topology=None,
                    objective: str = "latency"):
    """Rebuild a training session on a NEW mesh from a checkpoint.

    The checkpoint stores full (unsharded) arrays; the session on the
    surviving topology re-shards them via device_put. The ZeRO optimizer
    slices are NOT restored (their layout depends on the dead mesh) — they
    are reconstructed deterministically, which costs one step of Adam
    history on re-scale; params and step counter survive exactly.

    ``reselect=True`` makes recovery tuner-aware: the dead mesh's
    ``topology`` (a TorusSpec, optional) is shrunk onto the survivors
    (:func:`survivor_topology`) and the session's CommConfig is re-selected
    by extrapolating the calibrated Eq. 1 model over the TuneDB
    (:func:`repro.tune.elastic.model_reselect`) at the new ring's hop
    distance — the previously optimal config was tuned for a fabric that no
    longer exists, and re-measuring it mid-recovery would cost a sweep.  No
    sweep runs on this path (``sweep.runs`` stays flat); a cold DB falls
    back to nearest-measured selection.
    """
    from jax.sharding import NamedSharding
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch import setup

    ck = Checkpointer(ckpt_dir)
    step = ck.latest_step() if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    if reselect:
        from repro.core.config import CommConfig
        from repro.tune import topology_key
        from repro.tune.db import TuneDB
        from repro.tune.elastic import model_reselect
        new_topo = survivor_topology(topology, new_mesh)
        db = TuneDB.load(tune_db_path)
        n_new = int(np.prod(list(new_mesh.shape.values())))
        fallback_kw = {}
        if isinstance(comm, CommConfig):
            fallback_kw["fallback"] = comm   # keep the old config on a cold DB
        comm = model_reselect(
            "all_reduce", 4 * cfg.d_model * 1024, db=db,
            hops=_ring_hops(new_topo), objective=objective,
            topo=topology_key(n_devices=n_new), **fallback_kw)
    sess = setup.build_session(cfg, new_mesh, comm, oc=oc, fsdp=fsdp,
                               concrete=True)
    shardings = jax.tree.map(lambda s: NamedSharding(new_mesh, s),
                             sess.param_spec)
    params = ck.restore(step, sess.params, target_sharding=shardings)
    sess.params = params
    sess.opt_state = setup.init_opt_state(sess)
    # carry the step counter forward
    import jax.numpy as jnp
    sess.opt_state["step"] = jax.device_put(
        jnp.asarray(step, jnp.int32),
        NamedSharding(new_mesh, jax.sharding.PartitionSpec()))
    return sess, step


def resume_session(ckpt_dir, sess, step: Optional[int] = None):
    """Same-mesh resume after a preemption drain.

    Restores params at the newest committed step, and — when the drain also
    persisted the optimizer state (``emergency_save(..., opt_state=...)``
    writes it under ``<ckpt_dir>/opt``) — restores the exact Adam moments
    too, so the resumed loss stream is bitwise-identical to the
    uninterrupted run.  Without a drained opt state the optimizer is
    re-initialized (one step of Adam history lost), matching
    :func:`elastic_restore`.
    """
    from pathlib import Path
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch import setup

    ck = Checkpointer(ckpt_dir)
    step = ck.latest_step() if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    shardings = jax.tree.map(lambda s: NamedSharding(sess.mesh, s),
                             sess.param_spec)
    sess.params = ck.restore(step, sess.params, target_sharding=shardings)
    opt_ck = Checkpointer(Path(ckpt_dir) / "opt")
    sess.opt_state = setup.init_opt_state(sess)
    if opt_ck.latest_step() == step:
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(sess.mesh, s), sess.opt_spec)
        sess.opt_state = opt_ck.restore(step, sess.opt_state,
                                        target_sharding=opt_shardings)
    sess.opt_state["step"] = jax.device_put(
        jnp.asarray(step, jnp.int32),
        NamedSharding(sess.mesh, jax.sharding.PartitionSpec()))
    return sess, step
