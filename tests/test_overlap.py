"""Overlapped double-buffered halo exchange: the correctness harness.

Every comm path the SWE step can take — scheduling (host / fused /
overlapped) x transport (ordered / unordered) x partition count (1 / 2 / 4)
— must produce BITWISE-identical simulation state: the schedules differ only
in dependency structure, never in arithmetic.  Plus an HLO-level check that
the overlapped step really decouples interior compute from the permutes, and
hypothesis properties for the streaming engine's chunking round-trips.
"""
import numpy as np
import pytest

from helpers import require_hypothesis, run_multidevice


# ----------------------------------------------------------------------
# Parity matrix: scheduling x transport x n_parts, 20 steps, bitwise
# ----------------------------------------------------------------------

def test_parity_matrix_bitwise():
    out = run_multidevice("""
import itertools, jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, Scheduling, Transport
from repro.swe import driver
from repro.swe.partition import _rcb

N_STEPS = 20
ELEMENTS = 400

def flatten(sim, s):
    part = _rcb(sim.mesh.centroids, sim.pm.n_parts)
    counts = np.zeros(sim.pm.n_parts, int)
    vals = np.zeros((sim.mesh.n_elements, 3))
    for e in range(sim.mesh.n_elements):
        p = part[e]
        vals[e] = s[p, counts[p]]
        counts[p] += 1
    return vals

mesh1 = make_mesh((1,), ("data",))
ref_sim = driver.build_simulation(ELEMENTS, mesh1, CommConfig())
ref = flatten(ref_sim, np.asarray(
    driver.make_sim_runner(ref_sim, N_STEPS)(ref_sim.state, 0.0)))

checked = 0
for n_parts, sched, transport in itertools.product(
        (1, 2, 4),
        (Scheduling.HOST, Scheduling.FUSED, Scheduling.OVERLAPPED),
        (Transport.ORDERED, Transport.UNORDERED)):
    cfg = CommConfig(scheduling=sched, transport=transport,
                     window=2 if transport == Transport.ORDERED else 4)
    dmesh = make_mesh((n_parts,), ("data",))
    sim = driver.build_simulation(ELEMENTS, dmesh, cfg)
    if sched == Scheduling.HOST:
        s, _ = driver.make_host_scheduled_runner(sim).run(
            sim.state, 0.0, N_STEPS)
    else:
        s = driver.make_sim_runner(sim, N_STEPS)(sim.state, 0.0)
    v = flatten(sim, np.asarray(s))
    assert np.array_equal(ref, v), (
        f"parity broke: parts={n_parts} sched={sched.value} "
        f"transport={transport.value} maxdiff={np.abs(ref - v).max()}")
    checked += 1
assert checked == 18
print("PARITY MATRIX OK", checked)
""", n_devices=4)
    assert "PARITY MATRIX OK 18" in out


# ----------------------------------------------------------------------
# Interior/boundary partition invariants (what makes the scatter exact)
# ----------------------------------------------------------------------

def test_boundary_partition_invariants():
    from repro.swe.dg_solver import initial_state
    from repro.swe.mesh_gen import generate_bight_mesh
    from repro.swe.partition import partition_mesh

    mesh = generate_bight_mesh(800, seed=1)
    for n_parts in (1, 2, 4, 8):
        pm = partition_mesh(mesh, n_parts, initial_state(mesh))
        for p in range(pm.n_parts):
            nb = int(pm.n_boundary[p])
            k = int(pm.valid[p].sum())
            # boundary + interior(core) covers every real element exactly
            assert nb + int(pm.n_core[p]) == k
            real = pm.boundary_idx[p, :nb].tolist()
            assert len(set(real)) == nb                # no duplicates
            # boundary elements are exactly those with a remote edge
            remote = np.where((pm.edge_type[p] == 3).any(axis=1))[0]
            assert sorted(real) == remote.tolist()
            # padding repeats a real boundary row (0 when none exist), so
            # duplicate scatter writes carry identical values
            pad = pm.boundary_idx[p, nb:]
            assert (pad == (real[0] if nb else 0)).all()


# ----------------------------------------------------------------------
# HLO: the overlapped step decouples interior compute from the permutes
# ----------------------------------------------------------------------

def test_overlapped_step_hlo_decouples_compute():
    """The overlapped program must contain substantially more compute that is
    independent of the collective-permutes than the fused one (the property
    that lets a latency-hiding scheduler run it during the transfer).  On
    backends that split permutes into ``collective-permute-start``/``-done``
    pairs, additionally require compute scheduled inside a pair; this host's
    CPU backend emits synchronous permutes, so the dependency-class check is
    the load-bearing one."""
    out = run_multidevice("""
import jax
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, OVERLAPPED_CONFIG
from repro.swe import driver
from repro.launch.hlo_analysis import permute_overlap_stats

mesh = make_mesh((4,), ("data",))
stats = {}
for label, cfg in (("fused", CommConfig()), ("overlapped", OVERLAPPED_CONFIG)):
    sim = driver.build_simulation(500, mesh, cfg)
    run = driver.make_sim_runner(sim, n_inner=1)
    txt = jax.jit(lambda s: run(s, 0.0)).lower(sim.state).compile().as_text()
    stats[label] = permute_overlap_stats(txt)

for label, st in stats.items():
    assert st["sync_permutes"] + st["async_pairs"] >= 1, (label, st)
if stats["overlapped"]["async_pairs"]:
    assert max(stats["overlapped"]["pair_gaps"]) > 0, stats["overlapped"]
assert (stats["overlapped"]["overlappable_compute"]
        > stats["fused"]["overlappable_compute"]), stats
print("HLO OVERLAP OK", stats["fused"]["overlappable_compute"],
      stats["overlapped"]["overlappable_compute"])
""", n_devices=4)
    assert "HLO OVERLAP OK" in out


# ----------------------------------------------------------------------
# Double-buffered exchange == serialized exchange, both transports
# ----------------------------------------------------------------------

def test_double_buffered_exchange_matches_serial():
    out = run_multidevice("""
import jax, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives, streaming
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, Transport

mesh = make_mesh((4,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
rounds = [comm.ring_perm(1), comm.reverse_ring_perm(1), comm.ring_perm(2)]
x = np.random.RandomState(0).randn(4, 3, 64).astype(np.float32)

for transport in (Transport.UNORDERED, Transport.ORDERED):
    cfg = CommConfig(transport=transport, window=2, chunk_bytes=512)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def serial(xs):
        outs = collectives.multi_neighbor_exchange(
            [xs[0, r] for r in range(3)], rounds, comm, cfg)
        return jax.numpy.stack(outs)[None]

    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def double_buffered(xs):
        _, outs = streaming.double_buffered_exchange(
            [xs[0, r] for r in range(3)], rounds, "x", cfg)
        return jax.numpy.stack(outs)[None]

    a, b = np.asarray(serial(x)), np.asarray(double_buffered(x))
    assert np.array_equal(a, b), transport
print("DOUBLE BUFFER OK")
""", n_devices=4)
    assert "DOUBLE BUFFER OK" in out


# ----------------------------------------------------------------------
# Tuner integration: the sweep space enumerates OVERLAPPED and "auto"
# can select it for the halo exchange
# ----------------------------------------------------------------------

def test_space_enumerates_overlapped_for_overlap_capable_only():
    from repro.core.config import CommMode, Scheduling
    from repro.tune.space import enumerate_configs
    halo = enumerate_configs("multi_neighbor")
    assert any(c.scheduling == Scheduling.OVERLAPPED for c in halo)
    # all_to_all gained chunked-overlap delivery (streaming only)
    a2a = enumerate_configs("all_to_all")
    ov = [c for c in a2a if c.scheduling == Scheduling.OVERLAPPED]
    assert ov and all(c.mode == CommMode.STREAMING for c in ov)
    # ...including both segment sizes (the axis the pruning model separates)
    assert len({c.chunk_bytes for c in ov}) > 1
    # every other collective executes overlapped == fused: collapsed away
    for coll in ("sendrecv", "all_reduce", "all_gather", "reduce_scatter",
                 "hierarchical_all_reduce"):
        assert not any(c.scheduling == Scheduling.OVERLAPPED
                       for c in enumerate_configs(coll)), coll
    # the hierarchical (cross-pod) all-reduce is a first-class sweep target
    assert enumerate_configs("hierarchical_all_reduce")


def test_auto_selects_overlapped_when_fastest(tmp_path):
    out = run_multidevice(f"""
import jax
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, Scheduling
from repro.swe import driver
from repro.tune.db import TuneDB, TuneEntry, topology_key
from repro.tune.space import config_to_dict

topo = topology_key(n_devices=4)
db = TuneDB()
db.add(TuneEntry(topo=topo, collective="multi_neighbor", msg_bytes=1024,
                 config=config_to_dict(CommConfig()), us_per_call=100.0))
db.add(TuneEntry(topo=topo, collective="multi_neighbor", msg_bytes=1024,
                 config=config_to_dict(
                     CommConfig(scheduling=Scheduling.OVERLAPPED)),
                 us_per_call=10.0))
path = db.save(r"{tmp_path / 'tunedb.json'}")

mesh = make_mesh((4,), ("data",))
sim = driver.build_simulation(400, mesh, "auto", tune_db_path=path)
assert sim.comm_cfg.scheduling == Scheduling.OVERLAPPED, sim.comm_cfg
s = driver.make_sim_runner(sim, 3)(sim.state, 0.0)
jax.block_until_ready(s)
print("AUTO OVERLAPPED OK")
""", n_devices=4)
    assert "AUTO OVERLAPPED OK" in out


# ----------------------------------------------------------------------
# Chunk-level halo consume: the overlapped SWE step folds each
# recv_slot-aligned wire chunk as it lands — still bitwise-exact
# ----------------------------------------------------------------------

def test_chunk_level_halo_consume_parity_bitwise():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
import jax.numpy as jnp
from repro.core import streaming
from repro.core.config import CommConfig, Scheduling, Transport
from repro.swe import driver
from repro.swe.partition import _rcb

N_STEPS = 5
ELEMENTS = 16000   # large enough halo that 512B chunks split every round

def flatten(sim, s):
    part = _rcb(sim.mesh.centroids, sim.pm.n_parts)
    counts = np.zeros(sim.pm.n_parts, int)
    vals = np.zeros((sim.mesh.n_elements, 3))
    for e in range(sim.mesh.n_elements):
        p = part[e]
        vals[e] = s[p, counts[p]]
        counts[p] += 1
    return vals

mesh1 = make_mesh((1,), ("data",))
ref_sim = driver.build_simulation(ELEMENTS, mesh1, CommConfig())
ref = flatten(ref_sim, np.asarray(
    driver.make_sim_runner(ref_sim, N_STEPS)(ref_sim.state, 0.0)))

for transport in (Transport.ORDERED, Transport.UNORDERED):
    cfg = CommConfig(scheduling=Scheduling.OVERLAPPED, transport=transport,
                     window=2, chunk_bytes=512)
    dmesh = make_mesh((4,), ("data",))
    sim = driver.build_simulation(ELEMENTS, dmesh, cfg)
    probe = jnp.zeros((sim.pm.s_max, 3), jnp.float32)
    n, L = streaming.aligned_chunks(probe, cfg, align=3)
    assert n > 1, (n, L, sim.pm.s_max)     # multi-chunk rounds exercised
    assert L % 3 == 0                      # recv_slot-aligned chunks
    s = driver.make_sim_runner(sim, N_STEPS)(sim.state, 0.0)
    v = flatten(sim, np.asarray(s))
    assert np.array_equal(ref, v), (transport, np.abs(ref - v).max())
print("CHUNK HALO PARITY OK")
""", n_devices=4)
    assert "CHUNK HALO PARITY OK" in out


# ----------------------------------------------------------------------
# LM overlap parity: TP reduce and MoE all_to_all bitwise vs fused
# across partition counts x transports
# ----------------------------------------------------------------------

def test_lm_tp_reduce_parity_bitwise():
    out = run_multidevice("""
import numpy as np, jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, CommMode, Scheduling, Transport
from repro.models import layers
from repro.models.common import MeshContext, ModelConfig, Runtime

cfg_model = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                        n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128)

def run_tp(tp, comm_cfg, x, w):
    mesh = make_mesh((tp,), ("model",))
    rt = Runtime(cfg=cfg_model,
                 mesh=MeshContext(data_axes=(), model_size=tp, data_sizes=()),
                 comm=comm_cfg)
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(None, "model"), P("model", None)), out_specs=P(),
             check_vma=False)
    def f(xs, ws):
        return layers.row_parallel(xs, ws, rt)
    return np.asarray(f(x, w))

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(96, 64), jnp.float32)
w = jnp.asarray(rng.randn(64, 32), jnp.float32)

# The streaming paths split the matmul by token rows, and XLA's CPU dot sums
# a row chunk in another order than the whole matrix (last-bit differences
# even on one device), so they match the fused path to f32 rounding of a
# 64-term dot, not bit for bit.  All streaming variants share one chunking
# and are bitwise equal to each other.
checked = 0
for tp in (2, 4):
    ref = run_tp(tp, CommConfig(mode=CommMode.BUFFERED,
                                scheduling=Scheduling.FUSED), x, w)
    chunked = None
    for transport in (Transport.ORDERED, Transport.UNORDERED):
        for sched in (Scheduling.FUSED, Scheduling.OVERLAPPED):
            c = CommConfig(mode=CommMode.STREAMING, scheduling=sched,
                           transport=transport, window=2, chunk_bytes=512)
            out = run_tp(tp, c, x, w)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5,
                                       err_msg=str((tp, sched, transport)))
            if chunked is None:
                chunked = out
            assert np.array_equal(chunked, out), (tp, sched, transport)
            checked += 1
assert checked == 8
print("TP REDUCE PARITY OK", checked)
""", n_devices=4)
    assert "TP REDUCE PARITY OK 8" in out


def test_moe_a2a_parity_bitwise():
    """Raw chunked all_to_all AND the full a2a MoE block are bitwise equal
    to the fused path across partition counts and both transports."""
    out = run_multidevice("""
import numpy as np, jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, CommMode, Scheduling, Transport
from repro.models import moe
from repro.models.common import MeshContext, ModelConfig, Runtime

rng = np.random.RandomState(1)
checked = 0
for dp in (2, 4):
    mesh = make_mesh((dp,), ("data",))
    comm = Communicator.from_mesh(mesh, "data")
    x = jnp.asarray(rng.randn(dp * dp, 8, 24), jnp.float32)

    def run_a2a(c):
        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"), check_vma=False)
        def f(v):
            return collectives.all_to_all(v, comm, c, split_axis=0,
                                          concat_axis=0)
        return np.asarray(f(x))

    ref = run_a2a(CommConfig(mode=CommMode.BUFFERED,
                             scheduling=Scheduling.FUSED))
    for transport in (Transport.ORDERED, Transport.UNORDERED):
        c = CommConfig(mode=CommMode.STREAMING,
                       scheduling=Scheduling.OVERLAPPED,
                       transport=transport, window=2, chunk_bytes=512)
        assert np.array_equal(ref, run_a2a(c)), (dp, transport)
        checked += 1

# Full MoE block with a2a dispatch+combine (EP over the data axis)
cfg_model = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                        n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128,
                        n_experts=4, n_experts_per_tok=2)
params = moe.init_moe(jax.random.PRNGKey(0), cfg_model, jnp.float32, tp=1)
params = jax.tree.map(lambda a: a, params)
xs = jnp.asarray(rng.randn(4 * 16, 32), jnp.float32)

for dp in (2, 4):
    mesh = make_mesh((dp,), ("data",))
    def run_block(c):
        rt = Runtime(cfg=cfg_model,
                     mesh=MeshContext(data_axes=("data",), model_size=1,
                                      data_sizes=(dp,)),
                     comm=c)
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P("data"), P()), out_specs=(P("data"), P()),
                 check_vma=False)
        def f(v, p):
            y, aux = moe.moe_block_a2a(p, v, rt)
            return y, aux
        return f(xs, params)
    ref_y, ref_aux = run_block(CommConfig(mode=CommMode.BUFFERED,
                                          scheduling=Scheduling.FUSED))
    for transport in (Transport.ORDERED, Transport.UNORDERED):
        c = CommConfig(mode=CommMode.STREAMING,
                       scheduling=Scheduling.OVERLAPPED,
                       transport=transport, window=2, chunk_bytes=512)
        y, aux = run_block(c)
        assert np.array_equal(np.asarray(ref_y), np.asarray(y)), (dp, transport)
        assert np.array_equal(np.asarray(ref_aux), np.asarray(aux))
        checked += 1
assert checked == 8
print("MOE A2A PARITY OK", checked)
""", n_devices=4)
    assert "MOE A2A PARITY OK 8" in out


# ----------------------------------------------------------------------
# HLO: the overlapped LM paths decouple their collectives (chunked combines
# are mutually independent; the fused paths have a single dependent chain)
# ----------------------------------------------------------------------

def test_lm_overlap_hlo_decouples_collectives():
    out = run_multidevice("""
import numpy as np, jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, CommMode, Scheduling
from repro.launch.hlo_analysis import permute_overlap_stats
from repro.models import layers
from repro.models.common import MeshContext, ModelConfig, Runtime

cfg_model = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                        n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128)
mesh = make_mesh((4,), ("model",))
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(128, 64), jnp.float32)
w = jnp.asarray(rng.randn(64, 32), jnp.float32)

# What the layer emits is checked on the lowered program, before any XLA
# pass.  XLA's all-reduce combiner then merges the independent chunk
# all-reduces into one tuple all-reduce (on the CPU and, compiled for a
# described v5e, on the TPU), so the compiled overlapped program holds a single
# reduce.  That is pinned too: a change to either shows up here.
def lower_tp(comm_cfg):
    rt = Runtime(cfg=cfg_model,
                 mesh=MeshContext(data_axes=(), model_size=4, data_sizes=()),
                 comm=comm_cfg)
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(None, "model"), P("model", None)), out_specs=P(),
             check_vma=False)
    def f(xs, ws):
        return layers.row_parallel(xs, ws, rt)
    return jax.jit(f).lower(x, w)

def ar_stats(text):
    return permute_overlap_stats(text, ops=("all-reduce",))

fused_lo = lower_tp(CommConfig(mode=CommMode.BUFFERED))
ov_lo = lower_tp(CommConfig(mode=CommMode.STREAMING,
                            scheduling=Scheduling.OVERLAPPED, chunk_bytes=512))
fused = ar_stats(fused_lo.as_text(dialect="hlo"))
ov = ar_stats(ov_lo.as_text(dialect="hlo"))
assert fused["n_collectives"] == 1 and fused["independent_pairs"] == 0, fused
n = ov["n_collectives"]
assert n > 1 and ov["independent_pairs"] == n * (n - 1) // 2, ov
for lo in (fused_lo, ov_lo):
    compiled = ar_stats(lo.compile().as_text())
    assert compiled["n_collectives"] == 1, compiled

# MoE all_to_all: one fused op vs n mutually independent chunk exchanges
dmesh = make_mesh((4,), ("data",))
comm = Communicator.from_mesh(dmesh, "data")
xx = jnp.asarray(rng.randn(16, 8, 24), jnp.float32)

def lower_a2a(c):
    @partial(jax.shard_map, mesh=dmesh, in_specs=P("data"),
             out_specs=P("data"), check_vma=False)
    def f(v):
        return collectives.all_to_all(v, comm, c)
    return jax.jit(f).lower(xx).compile().as_text()

fused_a = permute_overlap_stats(lower_a2a(CommConfig(mode=CommMode.BUFFERED)),
                                ops=("all-to-all",))
ov_a = permute_overlap_stats(
    lower_a2a(CommConfig(mode=CommMode.STREAMING,
                         scheduling=Scheduling.OVERLAPPED, chunk_bytes=512)),
    ops=("all-to-all",))
assert fused_a["independent_pairs"] == 0, fused_a
assert ov_a["n_collectives"] > 1 and ov_a["independent_pairs"] > 0, ov_a
print("LM HLO DECOUPLING OK", ov["independent_pairs"], ov_a["independent_pairs"])
""", n_devices=4)
    assert "LM HLO DECOUPLING OK" in out


# ----------------------------------------------------------------------
# Chunk-level consume edge cases (sizes not divisible by the chunking,
# n_chunks=1 degradation, INT8 wire format at chunk boundaries)
# ----------------------------------------------------------------------

def test_pipelined_consume_alignment_property():
    """Chunk boundaries are align-multiples, consume sees exactly the
    reassembled message, and any size (divisible or not) round-trips
    bitwise."""
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import streaming
    from repro.core.config import CommConfig, Transport

    mesh = make_mesh((1,), ("x",))

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 200), st.integers(1, 7),
           st.sampled_from((512, 1024)),
           st.sampled_from((Transport.ORDERED, Transport.UNORDERED)),
           st.integers(1, 3))
    def check(rows, align, chunk_bytes, transport, window):
        cfg = CommConfig(chunk_bytes=chunk_bytes, transport=transport,
                         window=window)
        rng = np.random.RandomState(rows * 13 + align)
        x = jnp.asarray(rng.randn(rows, align), jnp.float32)
        n, L = streaming.aligned_chunks(x, cfg, align=align)
        assert L % align == 0                 # never splits a logical row
        assert n * L >= x.size and (n - 1) * L < x.size

        order = []

        @partial(jax.shard_map, mesh=mesh, in_specs=P(),
                 out_specs=(P(), P()), check_vma=False)
        def f(v):
            def consume(chunks, i, chunk):
                order.append(i)
                return chunks + [chunk]
            folded, msg = streaming.pipelined_consume(
                v, [(0, 0)], "x", cfg, consume, [], align=align)
            return jnp.stack(folded), msg

        folded, msg = f(x)
        assert np.array_equal(np.asarray(msg), np.asarray(x))
        assert folded.shape == (n, L)
        assert order == list(range(n))
        # the folded chunks ARE the message: concatenation reassembles it
        flat = np.asarray(folded).reshape(-1)[: x.size]
        assert np.array_equal(flat, np.asarray(x).reshape(-1))

    check()


def test_pipelined_consume_single_chunk_degradation():
    """A message smaller than chunk_bytes degrades to exactly one consume
    call (the n_chunks=1 buffered-equivalent pattern)."""
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import streaming
    from repro.core.config import CommConfig

    mesh = make_mesh((1,), ("x",))
    cfg = CommConfig(chunk_bytes=1 << 20)
    x = jnp.arange(300, dtype=jnp.float32).reshape(100, 3)
    calls = []

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def f(v):
        _, msg = streaming.pipelined_consume(
            v, [(0, 0)], "x", cfg,
            lambda c, i, ch: calls.append(i) or c, None, align=3)
        return msg

    msg = f(x)
    assert calls == [0]
    assert np.array_equal(np.asarray(msg), np.asarray(x))


def test_int8_chunk_boundary_roundtrip_property():
    """INT8 wire compression quantizes each wire chunk independently; the
    reassembled message must equal the per-chunk quantize->dequantize
    reference bitwise for any (size, chunk size) — chunk boundaries must
    never leak across quantization blocks."""
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import plugins, streaming
    from repro.core.config import CommConfig, Compression

    mesh = make_mesh((1,), ("x",))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(10, 400), st.sampled_from((512, 1024)),
           st.sampled_from((16, 64)))
    def check(elems, chunk_bytes, block):
        cfg = CommConfig(chunk_bytes=chunk_bytes, algorithm="ring",
                         compression=Compression.INT8, quant_block=block)
        rng = np.random.RandomState(elems + block)
        x = jnp.asarray(rng.randn(elems) * 10, jnp.float32)

        @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                 check_vma=False)
        def f(v):
            _, msg = streaming.pipelined_consume(
                v, [(0, 0)], "x", cfg, lambda c, i, ch: c, None)
            return msg

        out = np.asarray(f(x))
        # reference: identical chunk geometry, per-chunk quant round-trip
        n, L = streaming.aligned_chunks(x, cfg)
        flat = np.zeros(n * L, np.float32)
        flat[:elems] = np.asarray(x)
        ref_parts = []
        for i in range(n):
            chunk = jnp.asarray(flat[i * L:(i + 1) * L])
            q, s = plugins.quantize_int8(chunk, block)
            ref_parts.append(np.asarray(
                plugins.dequantize_int8(q, s, (L,), jnp.float32)))
        ref = np.concatenate(ref_parts)[:elems]
        assert np.array_equal(out, ref)

    check()


# ----------------------------------------------------------------------
# Hypothesis properties: streaming engine chunking round-trips
# ----------------------------------------------------------------------

def test_split_chunks_roundtrip_property():
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    import jax.numpy as jnp
    from repro.core import streaming

    dtypes = (jnp.float32, jnp.float16, jnp.int32, jnp.bfloat16)

    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.integers(1, 7), min_size=1, max_size=4),
           st.integers(0, len(dtypes) - 1),
           st.integers(1, 12))
    def check(shape, dtype_i, n):
        dtype = dtypes[dtype_i]
        size = int(np.prod(shape))
        rng = np.random.RandomState(size * 31 + n)
        x = jnp.asarray(rng.randn(*shape) * 100).astype(dtype)
        chunks, unsplit = streaming.split_chunks(x, n)
        assert chunks.shape[0] == n
        assert chunks.size >= x.size          # zero-padded, never truncated
        back = unsplit(chunks)
        assert back.shape == x.shape and back.dtype == x.dtype
        assert np.array_equal(np.asarray(back), np.asarray(x))

    check()


def test_num_chunks_bounds_property():
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from repro.core.config import CommConfig
    from repro.core.streaming import num_chunks

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 10 << 20), st.integers(512, 1 << 20),
           st.integers(1, 64))
    def check(nbytes, chunk_bytes, max_chunks):
        cfg = CommConfig(chunk_bytes=chunk_bytes, max_chunks=max_chunks)
        n = num_chunks(nbytes, cfg)
        assert 1 <= n <= max_chunks
        if n < max_chunks:                   # uncapped: chunks cover the data
            assert n * chunk_bytes >= nbytes

    check()


def test_chunked_permute_roundtrip_property():
    """Identity-perm chunked_permute is a bitwise round-trip for any shape,
    dtype, chunk size, transport, and window (the wire format must never
    lose or reorder data)."""
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import streaming
    from repro.core.config import CommConfig, Transport

    mesh = make_mesh((1,), ("x",))
    dtypes = (jnp.float32, jnp.float16)

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.integers(1, 9), min_size=1, max_size=3),
           st.integers(0, len(dtypes) - 1),
           st.sampled_from((512, 1024, 4096)),
           st.sampled_from((Transport.ORDERED, Transport.UNORDERED)),
           st.integers(1, 4))
    def check(shape, dtype_i, chunk_bytes, transport, window):
        cfg = CommConfig(chunk_bytes=chunk_bytes, transport=transport,
                         window=window)
        rng = np.random.RandomState(int(np.prod(shape)) + window)
        x = jnp.asarray(rng.randn(*shape)).astype(dtypes[dtype_i])

        @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                 check_vma=False)
        def f(v):
            return streaming.chunked_permute(v, [(0, 0)], "x", cfg)

        out = f(x)
        assert out.shape == x.shape and out.dtype == x.dtype
        assert np.array_equal(np.asarray(out), np.asarray(x))

    check()
