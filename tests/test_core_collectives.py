"""ACCL-X collective correctness: every algorithm/mode/transport/compression
combination must agree with the plain-numpy reference on an 8-device mesh."""
import numpy as np
import pytest

from helpers import run_multidevice


def test_all_reduce_all_algorithms():
    out = run_multidevice("""
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import (CommConfig, Compression, Communicator, collectives)

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(0).randn(8, 40).astype(np.float32)
ref = x.sum(0)
for name, cfg, tol in [
    ("native", CommConfig(), 1e-5),
    ("ring", CommConfig(algorithm="ring"), 1e-5),
    ("ring_int8", CommConfig(algorithm="ring", compression=Compression.INT8), 2e-1),
    ("ring_bf16", CommConfig(algorithm="ring", compression=Compression.BF16), 1e-1),
]:
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def f(xs):
        return collectives.all_reduce(xs[0], comm, cfg)[None]
    out = np.asarray(f(x))
    assert np.allclose(out, np.broadcast_to(ref, out.shape),
                       atol=tol * (np.abs(ref).max() + 1)), name
print("OK")
""")
    assert "OK" in out


def test_sendrecv_modes_and_transports():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import CommConfig, CommMode, Transport, Communicator, collectives

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(1).randn(8, 130).astype(np.float32)
for mode in (CommMode.STREAMING, CommMode.BUFFERED):
    for tr in (Transport.ORDERED, Transport.UNORDERED):
        for chunk in (512, 2048):
            cfg = CommConfig(mode=mode, transport=tr, chunk_bytes=chunk, window=2)
            @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
            def g(xs):
                return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]
            out = np.asarray(g(x))
            assert np.allclose(out, np.roll(x, 1, axis=0)), (mode, tr, chunk)
print("OK")
""")
    assert "OK" in out


def test_reduce_scatter_and_gather_roundtrip():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import CommConfig, Communicator, collectives

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(2).randn(8, 16, 5).astype(np.float32)
for algo in ("native", "ring"):
    cfg = CommConfig(algorithm=algo)
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def rs(xs):
        seg = collectives.reduce_scatter(xs[0], comm, cfg)
        return collectives.all_gather(seg, comm, cfg, axis=0)[None]
    out = np.asarray(rs(x))
    ref = x.sum(0)
    assert np.allclose(out[0], ref, atol=1e-4), algo
print("OK")
""")
    assert "OK" in out


def test_hierarchical_all_reduce_multipod():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import CommConfig, Communicator, collectives

mesh = make_mesh((2, 4), ("pod", "data"))
ci = Communicator.from_mesh(mesh, "data")
co = Communicator.from_mesh(mesh, "pod")
x = np.random.RandomState(3).randn(2, 4, 33).astype(np.float32)
@partial(jax.shard_map, mesh=mesh, in_specs=P("pod", "data"),
         out_specs=P("pod", "data"))
def f(xs):
    return collectives.hierarchical_all_reduce(
        xs[0, 0], ci, co, CommConfig())[None, None]
out = np.asarray(f(x))
assert np.allclose(out, np.broadcast_to(x.sum((0, 1)), out.shape), atol=1e-4)
print("OK")
""")
    assert "OK" in out


def test_edge_color_rounds_properties():
    from repro.core.collectives import edge_color_rounds
    import itertools
    rng = np.random.RandomState(0)
    for trial in range(20):
        n = rng.randint(3, 10)
        edges = set()
        for _ in range(rng.randint(1, 3 * n)):
            s, d = rng.randint(0, n, 2)
            if s != d:
                edges.add((int(s), int(d)))
        rounds = edge_color_rounds(sorted(edges))
        # every edge appears exactly once
        flat = [e for r in rounds for e in r]
        assert sorted(flat) == sorted(edges)
        # each round is ppermute-valid
        for r in rounds:
            srcs = [s for s, _ in r]
            dsts = [d for _, d in r]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)
