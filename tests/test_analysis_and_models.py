"""HLO analyzer, latency-model properties, scheduler equivalence, streaming
engine pieces — the measurement infrastructure must itself be correct."""
import numpy as np
import pytest

from helpers import run_multidevice


def test_hlo_analysis_scan_trip_counts():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze_hlo

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    a = analyze_hlo(jax.jit(f).lower(x, w).compile().as_text())
    assert a["flops"] == 10 * 2 * 128 ** 3

    def g(x, w):                      # nested scans: 3 × 5 iterations
        def outer(c, _):
            def inner(cc, _):
                return cc @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    a2 = analyze_hlo(jax.jit(g).lower(x, w).compile().as_text())
    assert a2["flops"] == 15 * 2 * 128 ** 3


def test_hlo_analysis_counts_collectives_in_scans():
    out = run_multidevice("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo

mesh = make_mesh((8,), ("x",))

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
         check_vma=False)
def f(xs):
    def body(c, _):
        return (jax.lax.psum(c, "x") * jnp.float32(0.1)).astype(c.dtype), None
    out, _ = jax.lax.scan(body, xs, None, length=7)
    return out

x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
a = analyze_hlo(jax.jit(f).lower(x).compile().as_text())
# 7 all-reduces of a (1, 1024) f32 shard
assert a["collective_counts"]["all-reduce"] == 7, a["collective_counts"]
assert a["collective_bytes"]["all-reduce"] == 7 * 1024 * 4
print("HLO COLLECTIVES OK")
""")
    assert "HLO COLLECTIVES OK" in out


@pytest.mark.parametrize("lowered", (False, True),
                         ids=("optimized", "lowered"))
def test_hlo_analysis_parses_tuple_and_async_collectives(lowered):
    """Combined collectives print a tuple type with ``/*index=5*/`` markers;
    TPU programs split them into ``-start``/``-done`` pairs.  The lowered,
    pre-optimization text prints ``ENTRY main {`` and names without ``%``."""
    from repro.launch.hlo_analysis import permute_overlap_stats
    tup = ", ".join(("/*index=5*/" if i == 5 else "") + "f32[8,32]{1,0}"
                    for i in range(6))
    ops = ", ".join(f"%d{i}" for i in range(6))
    text = "\n".join([
        "ENTRY %main (p: f32[8,32]) -> f32[8,32] {",
        *(f"  %d{i} = f32[8,32]{{1,0}} add(%p, %p)" for i in range(6)),
        f"  %ar = ({tup}) all-reduce({ops}), channel_id=1, to_apply=%sum",
        "  %s0 = f32[8,32]{1,0} all-reduce-start(%d0), channel_id=2",
        "  %s1 = f32[8,32]{1,0} all-reduce-start(%d1), channel_id=3",
        "  %m = f32[8,32]{1,0} multiply(%p, %p)",
        "  %e0 = f32[8,32]{1,0} all-reduce-done(%s0)",
        "  ROOT %e1 = f32[8,32]{1,0} all-reduce-done(%s1)",
        "}"])
    if lowered:
        text = text.replace("%", "").replace(
            "main (p: f32[8,32]) -> f32[8,32]", "main")
    st = permute_overlap_stats(text, ops=("all-reduce",))
    assert st["n_collectives"] == 3, st
    assert st["sync_permutes"] == 1 and st["async_pairs"] == 2, st
    assert st["pair_gaps"] == [1, 1] and st["independent_pairs"] == 3, st


def test_latency_model_eq1_properties():
    """Eq. 1 invariants from the paper, under the hypothesis strategy."""
    from helpers import require_hypothesis
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from repro.core import latmodel
    from repro.core.config import (CommConfig, CommMode, Scheduling, V5E)

    @settings(max_examples=50, deadline=None)
    @given(st.integers(64, 1 << 22))
    def check(msg):
        buf_host = CommConfig(mode=CommMode.BUFFERED, scheduling=Scheduling.HOST)
        buf_pl = CommConfig(mode=CommMode.BUFFERED, scheduling=Scheduling.FUSED)
        str_pl = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.FUSED)
        l_bh = latmodel.pingping_latency(msg, buf_host, V5E)
        l_bp = latmodel.pingping_latency(msg, buf_pl, V5E)
        l_sp = latmodel.pingping_latency(msg, str_pl, V5E)
        # strict ordering: streaming-PL < buffered-PL < buffered-host
        assert l_sp < l_bp < l_bh
        # host-scheduling penalty == 2*(l_k_host - l_k_fused)
        assert abs((l_bh - l_bp) - 2 * (V5E.host_dispatch - V5E.fused_dispatch)) < 1e-12
        # effective bw below link peak, monotone in message size
        assert latmodel.effective_bandwidth(msg, str_pl, V5E) < V5E.ici_bw

    check()


def test_scheduler_runners_equivalent():
    """Host-scheduled and fused runners must produce identical numerics; the
    host runner pays one dispatch per phase (the paper's l_k accounting)."""
    import jax.numpy as jnp
    from repro.core import scheduler

    phases = [
        scheduler.Phase("a", lambda c: c * 2.0),
        scheduler.Phase("comm", lambda c: c + 1.0, is_comm=True),
        scheduler.Phase("b", lambda c: c ** 2),
    ]
    x = jnp.arange(8.0)
    host = scheduler.HostScheduledRunner(phases)
    fused = scheduler.FusedRunner(phases)
    out_h = host.run_step(x)
    out_f = fused.run_step(x)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_f))
    assert host.dispatch_count == 3
    assert fused.dispatch_count == 1
    assert host.modeled_dispatch_overhead() > fused.modeled_dispatch_overhead()


def test_streaming_pipelined_consume():
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import CommConfig, Communicator, streaming

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
cfg = CommConfig(chunk_bytes=512)
x = np.random.RandomState(0).randn(8, 256).astype(np.float32)

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")))
def f(xs):
    total, received = streaming.pipelined_consume(
        xs[0], comm.ring_perm(), "x", cfg,
        consume=lambda acc, i, chunk: acc + jnp.sum(chunk),
        init=jnp.zeros(()))
    return total[None], received[None]

total, received = f(x)
ref = np.roll(x, 1, axis=0)
assert np.allclose(np.asarray(received), ref)
assert np.allclose(np.asarray(total), ref.sum(1), rtol=1e-5)
print("PIPELINED CONSUME OK")
""")
    assert "PIPELINED CONSUME OK" in out


def test_wire_bytes_model():
    from repro.core import latmodel
    from repro.core.config import CommConfig, Compression
    msg = 1 << 20
    none = latmodel.wire_bytes(msg, CommConfig())
    bf16 = latmodel.wire_bytes(msg, CommConfig(compression=Compression.BF16))
    int8 = latmodel.wire_bytes(
        msg, CommConfig(algorithm="ring", compression=Compression.INT8))
    assert none == msg
    assert bf16 == msg / 2
    assert msg / 4 < int8 < msg / 3   # payload/4 + scales overhead
