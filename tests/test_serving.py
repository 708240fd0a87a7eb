"""Serving path: prefill == forward, decode == incremental forward."""
import pytest

from helpers import run_multidevice

ARCHS = ["qwen3-8b", "gemma3-1b", "mixtral-8x22b", "deepseek-v3-671b",
         "mamba2-130m", "zamba2-7b", "seamless-m4t-large-v2",
         "phi-3-vision-4.2b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_matches_forward(arch):
    out = run_multidevice("""
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig
from repro.launch import setup, input_specs as isp
from repro.models import transformer
from repro.train import serve as serve_mod

ARCH = {arch!r}
cfg = dataclasses.replace(get_smoke_config(ARCH), dtype=jnp.float32)
comm = CommConfig()
mesh = make_mesh((2, 4), ("data", "model"))
sess = setup.build_session(cfg, mesh, comm, concrete=True)
rng = np.random.RandomState(0)
B, S = 4, 32
shape = isp.ShapeSpec("smoke", S, B, "prefill")
rt, pre_fn, _ = serve_mod.build_serve_fn(cfg, mesh, comm, shape)
batch = {{"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))}}
if cfg.family == "vlm":
    batch["patches"] = jnp.asarray(
        rng.randn(B, cfg.num_patches, cfg.frontend_dim), jnp.float32)
if cfg.family == "audio":
    batch["frames"] = jnp.asarray(rng.randn(B, S, cfg.frontend_dim), jnp.float32)
state = pre_fn(sess.params, batch)
vocab_sharded = cfg.vocab_size % 4 == 0
fwd = jax.jit(jax.shard_map(
    lambda p, b: transformer.forward(p, b, rt, train=False).logits,
    mesh=mesh,
    in_specs=(sess.param_spec, jax.tree.map(lambda _: P(("data",)), batch)),
    out_specs=P(("data",), None, "model" if vocab_sharded else None),
    check_vma=False))
full = np.asarray(fwd(sess.params, batch))
pre = np.asarray(state.last_logits)
err = np.abs(full[:, -1] - pre).max() / (np.abs(full[:, -1]).max() + 1e-9)
assert err < 2e-3, err
print("PREFILL OK", err)
""".format(arch=arch))
    assert "PREFILL OK" in out


def test_decode_matches_extended_prefill():
    """Greedy-decoding N tokens == prefilling the extended sequence."""
    out = run_multidevice("""
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig
from repro.launch import setup, input_specs as isp
from repro.train import serve as serve_mod

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype=jnp.float32)
comm = CommConfig()
mesh = make_mesh((2, 4), ("data", "model"))
sess = setup.build_session(cfg, mesh, comm, concrete=True)
rng = np.random.RandomState(0)
B, S, GEN = 4, 24, 4
MAX = S + GEN
shape_p = isp.ShapeSpec("s", S, B, "prefill")
shape_d = isp.ShapeSpec("s", MAX, B, "decode")
# Prefill spec at the PROMPT length; its caches cover MAX via cache_capacity.
_, pre_fn, pre_abs = serve_mod.build_serve_fn(
    cfg, mesh, comm, shape_p, cache_capacity=serve_mod.cache_len(cfg, shape_d))
_, dec_fn, _ = serve_mod.build_serve_fn(cfg, mesh, comm, shape_d)
assert pre_abs[1]["tokens"].shape == (B, S), pre_abs[1]["tokens"].shape

tokens = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
state = pre_fn(sess.params, {"tokens": jnp.asarray(tokens)})
seq = tokens.copy()
for i in range(GEN):
    nxt = np.asarray(jnp.argmax(state.last_logits, axis=-1)).astype(np.int32)
    seq = np.concatenate([seq, nxt[:, None]], axis=1)
    state = dec_fn(sess.params, jnp.asarray(nxt), state)

# reference: prefill the full generated sequence; logits at each step must
# produce the same greedy choices
shape_ref = isp.ShapeSpec("s", MAX, B, "prefill")
_, ref_fn, _ = serve_mod.build_serve_fn(cfg, mesh, comm, shape_ref)
ref_state = ref_fn(sess.params, {"tokens": jnp.asarray(seq[:, :MAX])})
last_dec = np.asarray(jnp.argmax(state.last_logits, -1))
last_ref = np.asarray(jnp.argmax(ref_state.last_logits, -1))
assert np.array_equal(last_dec, last_ref), (last_dec, last_ref)
print("DECODE OK")
""")
    assert "DECODE OK" in out


def test_prefill_spec_at_prompt_length():
    """Satellite regression: the prefill builder's spec is built at the
    prompt's own sequence length (the traced program matches what is fed)
    while ``cache_capacity`` independently sizes the KV caches for the
    planned generation — and the builders reject the nonsense combinations
    (capacity smaller than the prompt, capacity on the decode builder)."""
    out = run_multidevice("""
import dataclasses
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig
from repro.launch import setup, input_specs as isp
from repro.train import serve as serve_mod

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype=jnp.float32)
comm = CommConfig()
mesh = make_mesh((2, 4), ("data", "model"))
sess = setup.build_session(cfg, mesh, comm, concrete=True)
rng = np.random.RandomState(0)
B, S, MAX = 4, 12, 24
shape_p = isp.ShapeSpec("s", S, B, "prefill")
shape_d = isp.ShapeSpec("s", MAX, B, "decode")
rt, pre_fn, (params_abs, batch_abs) = serve_mod.build_serve_fn(
    cfg, mesh, comm, shape_p, cache_capacity=MAX)
assert batch_abs["tokens"].shape == (B, S), batch_abs["tokens"].shape

state = pre_fn(sess.params, {"tokens": jnp.asarray(
    rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))})
# Caches were sized by cache_capacity, not the prompt: a full MAX - S
# generation fits without re-prefilling.
_, dec_fn, _ = serve_mod.build_serve_fn(cfg, mesh, comm, shape_d)
for _ in range(MAX - S):
    nxt = jnp.argmax(state.last_logits, axis=-1).astype(jnp.int32)
    state = dec_fn(sess.params, nxt, state)
assert state.last_logits.shape[0] == B

# Defaulted capacity == prompt length (a cache exactly as long as fed).
_, _, (_, small_abs) = serve_mod.build_serve_fn(cfg, mesh, comm, shape_p)
assert small_abs["tokens"].shape == (B, S)

try:
    serve_mod.build_serve_fn(cfg, mesh, comm, shape_p, cache_capacity=S - 1)
    raise AssertionError("capacity < prompt must raise")
except ValueError:
    pass
try:
    serve_mod.build_serve_fn(cfg, mesh, comm, shape_d, cache_capacity=MAX)
    raise AssertionError("cache_capacity on the decode builder must raise")
except ValueError:
    pass
print("PROMPT SPEC OK")
""")
    assert "PROMPT SPEC OK" in out


def test_auto_comm_selects_per_phase():
    """comm="auto": prefill and decode resolve DIFFERENT CommConfigs from
    one engineered TuneDB (consumer-tagged entries), and decode under the
    auto-resolved config is bitwise-identical to passing that config
    statically."""
    out = run_multidevice("""
import dataclasses, tempfile, os
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig, CommMode, Scheduling, Transport
from repro.launch import setup, input_specs as isp
from repro.train import serve as serve_mod
from repro.tune.db import TuneDB, TuneEntry, topology_key
from repro.tune.space import config_to_dict

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype=jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
topo = topology_key(mesh)

# Engineered DB: the decode_step loop says the small-chunk overlapped
# config wins, the prefill loop says the jumbo fused config does.
A = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.OVERLAPPED,
               transport=Transport.UNORDERED, window=4, chunk_bytes=4096)
Bc = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.FUSED,
                transport=Transport.UNORDERED, window=8, chunk_bytes=1 << 20)
db = TuneDB()
for consumer, msg, win, lose in (("decode_step", 4096, A, Bc),
                                 ("prefill", 1 << 20, Bc, A)):
    db.add(TuneEntry(topo=topo, collective="all_reduce", msg_bytes=msg,
                     config=config_to_dict(win), us_per_call=10.0,
                     e2e_us=20.0, consumer=consumer))
    db.add(TuneEntry(topo=topo, collective="all_reduce", msg_bytes=msg,
                     config=config_to_dict(lose), us_per_call=9.0,
                     e2e_us=60.0, consumer=consumer))
with tempfile.TemporaryDirectory() as td:
    db_path = os.path.join(td, "tunedb.json")
    db.save(db_path)

    B, S, MAX = 4, 12, 16
    shape_p = isp.ShapeSpec("s", S, B, "prefill")
    shape_d = isp.ShapeSpec("s", MAX, B, "decode")
    rt_p, pre_fn, _ = serve_mod.build_serve_fn(
        cfg, mesh, "auto", shape_p, tune_db_path=db_path,
        cache_capacity=MAX)
    rt_d, dec_fn, _ = serve_mod.build_serve_fn(
        cfg, mesh, "auto", shape_d, tune_db_path=db_path)
    assert rt_p.comm == Bc, rt_p.comm
    assert rt_d.comm == A, rt_d.comm
    assert rt_p.comm != rt_d.comm

    # Decode under auto == decode under the explicit winning config, bitwise.
    sess = setup.build_session(cfg, mesh, CommConfig(), concrete=True)
    _, dec_static, _ = serve_mod.build_serve_fn(cfg, mesh, A, shape_d)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (B, S)).astype(np.int32)
    s_auto = pre_fn(sess.params, {"tokens": jnp.asarray(tokens)})
    s_stat = s_auto
    for _ in range(MAX - S):
        nxt = jnp.argmax(s_auto.last_logits, axis=-1).astype(jnp.int32)
        s_auto = dec_fn(sess.params, nxt, s_auto)
        s_stat = dec_static(sess.params, nxt, s_stat)
        np.testing.assert_array_equal(np.asarray(s_auto.last_logits),
                                      np.asarray(s_stat.last_logits))
print("AUTO PHASE OK")
""")
    assert "AUTO PHASE OK" in out
