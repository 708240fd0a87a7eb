"""Disk-backed plan store: roundtrips, canonical keys, corrupt/stale entry
recovery, schema versioning, env/CLI activation, concurrent writers, and the
plans._memo disk tier (a cleared in-memory cache warm-starts from disk)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from helpers import REPO


def _planstore():
    from repro.core import planstore
    return planstore


@pytest.fixture
def disk_store(tmp_path, monkeypatch):
    """plans cache + planstore activated on a fresh tmp dir, fully undone."""
    planstore = _planstore()
    from repro.core import plans
    monkeypatch.delenv(planstore.ENV_VAR, raising=False)
    planstore.configure(str(tmp_path))
    plans.clear_cache()
    plans.reset_stats()
    yield planstore.active()
    planstore.configure(None)
    plans.clear_cache()
    plans.reset_stats()


# ----------------------------------------------------------------------
# Key canonicalization
# ----------------------------------------------------------------------

def test_cfg_key_is_stable_json_primitives():
    """_cfg_key must never leak enum objects (the old dataclasses.astuple
    encoding did) and must carry the schema stamp that versions the disk
    format."""
    from repro.core import plans
    from repro.core.config import CommConfig, Transport
    planstore = _planstore()

    key = plans._cfg_key(CommConfig())
    assert key[0] == plans.CFG_KEY_SCHEMA
    for name, value in key[1:]:
        assert isinstance(name, str)
        assert value is None or isinstance(value, (bool, int, float, str))
    # deterministic + JSON-roundtrippable
    assert plans._cfg_key(CommConfig()) == key
    canon = planstore.canonical_key(key)
    assert planstore.canonical_key(key) == canon
    json.loads(canon)
    # a config change produces a different key
    other = plans._cfg_key(CommConfig(transport=Transport.ORDERED))
    assert other != key
    assert plans._cfg_key(None) == ()


def test_canonical_key_rejects_non_primitives():
    planstore = _planstore()

    class Weird:
        pass

    with pytest.raises(TypeError):
        planstore.canonical_key(("a", Weird()))
    # nested tuples of primitives are fine and order-sensitive
    a = planstore.canonical_key((1, ("x", 2.5), None, True))
    b = planstore.canonical_key((1, ("x", 2.5), True, None))
    assert a != b


def test_non_serializable_keys_stay_memory_only(tmp_path):
    """put never raises: a non-canonical key (or unencodable value) returns
    False and writes nothing."""
    planstore = _planstore()
    store = planstore.PlanStore(tmp_path)

    class Weird:
        pass

    assert store.put("ring", ("a", Weird()), (1, 2)) is False
    assert store.get("ring", ("a", Weird())) is planstore.MISSING
    assert store.put("plan", ("k",), object()) is False   # unencodable value
    assert store.entry_count() == 0


# ----------------------------------------------------------------------
# Roundtrips
# ----------------------------------------------------------------------

def test_plain_kind_roundtrips(tmp_path):
    """rounds / ring / perm values come back as the same nested int tuples
    the in-memory cache stores."""
    planstore = _planstore()
    planstore.reset_disk_stats()
    store = planstore.PlanStore(tmp_path)
    values = {
        "rounds": (((0, 1), (2, 3)), ((1, 2),)),
        "ring": tuple((i, (i + 1) % 8) for i in range(8)),
        "perm": ((0, 1), (1, 0)),
    }
    for kind, value in values.items():
        key = ("t", kind, 8)
        assert store.get(kind, key) is planstore.MISSING
        assert store.put(kind, key, value)
        got = store.get(kind, key)
        assert got == value and isinstance(got, tuple)
    st = planstore.disk_stats()
    assert st == {"disk_hits": 3, "disk_misses": 3,
                  "disk_writes": 3, "disk_corrupt": 0}


def test_chunk_and_comm_plan_roundtrip_through_memo(disk_store):
    """The real path: plans.* builders persist on miss; a cleared in-memory
    cache (a "fresh process") rebuilds the identical value from disk and the
    disk hit counts as a plan hit."""
    from repro.core import plans
    from repro.core.communicator import Communicator
    from repro.core.config import CommConfig, Transport
    planstore = _planstore()

    cfg = CommConfig(chunk_bytes=2048, transport=Transport.ORDERED, window=2)
    comm = Communicator(("x",), (8,))
    c1 = plans.chunk_plan((1024,), np.float32, cfg)
    p1 = plans.get_plan("sendrecv", comm, cfg, (1024,), np.float32)
    st = plans.cache_stats()
    assert st["disk_writes"] >= 2 and st["disk_hits"] == 0

    plans.clear_cache()                  # memory gone, disk survives
    hits_before = st["plan_hits"]
    c2 = plans.chunk_plan((1024,), np.float32, cfg)
    p2 = plans.get_plan("sendrecv", comm, cfg, (1024,), np.float32)
    st = plans.cache_stats()
    assert c2 == c1 and c2 is not c1     # rebuilt from disk, value-identical
    assert p2 == p1 and p2 is not p1
    assert st["disk_hits"] >= 2
    assert st["plan_hits"] > hits_before   # disk hits count as plan hits
    assert st["disk_corrupt"] == 0


def test_executable_roundtrip(tmp_path):
    """AOT-compiled programs serialize whole and replay bit-identically."""
    import jax
    import jax.numpy as jnp
    planstore = _planstore()
    store = planstore.PlanStore(tmp_path)

    x = jnp.arange(8.0)
    compiled = jax.jit(lambda v: v * 2.0 + 1.0).lower(x).compile()
    assert store.get_executable(("aot", 8)) is planstore.MISSING
    assert store.put_executable(("aot", 8), compiled)
    loaded = store.get_executable(("aot", 8))
    assert loaded is not planstore.MISSING
    assert (np.asarray(loaded(x)).tobytes()
            == np.asarray(compiled(x)).tobytes())


# ----------------------------------------------------------------------
# Corrupt / stale / mismatched entries: always a rebuildable miss
# ----------------------------------------------------------------------

def _single_entry(tmp_path):
    return next((tmp_path / "plans").glob("*.json"))


def test_truncated_entry_recovers_by_rebuild(tmp_path):
    planstore = _planstore()
    planstore.reset_disk_stats()
    store = planstore.PlanStore(tmp_path)
    key, value = ("k", 1), ((0, 1), (1, 2))
    assert store.put("rounds", key, value)
    path = _single_entry(tmp_path)
    path.write_text(path.read_text()[:11])        # torn write simulation
    assert store.get("rounds", key) is planstore.MISSING
    st = planstore.disk_stats()
    assert st["disk_corrupt"] == 1 and st["disk_misses"] == 1
    assert not path.exists()                      # bad file removed
    # the caller's contract: rebuild and overwrite, then it hits again
    assert store.put("rounds", key, value)
    assert store.get("rounds", key) == value


def test_schema_version_mismatch_is_miss(tmp_path):
    planstore = _planstore()
    planstore.reset_disk_stats()
    store = planstore.PlanStore(tmp_path)
    assert store.put("ring", ("r",), ((0, 1),))
    path = _single_entry(tmp_path)
    entry = json.loads(path.read_text())
    entry["schema"] = planstore.SCHEMA_VERSION + 1
    path.write_text(json.dumps(entry))
    assert store.get("ring", ("r",)) is planstore.MISSING
    assert planstore.disk_stats()["disk_corrupt"] == 1


def test_key_mismatch_never_answers_wrong_lookup(tmp_path):
    """The full key stored in the entry guards against hash collisions and
    recycled files: a tampered key field is a miss, not a wrong answer."""
    planstore = _planstore()
    store = planstore.PlanStore(tmp_path)
    assert store.put("perm", ("p", 8), ((0, 1),))
    path = _single_entry(tmp_path)
    entry = json.loads(path.read_text())
    entry["key"] = ["p", 9]
    path.write_text(json.dumps(entry))
    assert store.get("perm", ("p", 8)) is planstore.MISSING


def test_corrupt_program_entry_is_miss(tmp_path):
    planstore = _planstore()
    planstore.reset_disk_stats()
    store = planstore.PlanStore(tmp_path)
    path = store._exec_path(planstore.canonical_key(("prog", 1)))
    path.parent.mkdir(parents=True)
    path.write_bytes(b"not a pickle")
    assert store.get_executable(("prog", 1)) is planstore.MISSING
    st = planstore.disk_stats()
    assert st["disk_corrupt"] == 1 and st["disk_misses"] == 1
    assert not path.exists()


# ----------------------------------------------------------------------
# Activation: env var, --plan-dir override, disabled
# ----------------------------------------------------------------------

def test_env_and_configure_control(tmp_path, monkeypatch):
    planstore = _planstore()
    monkeypatch.delenv(planstore.ENV_VAR, raising=False)
    planstore.configure(None)
    assert planstore.active() is None

    monkeypatch.setenv(planstore.ENV_VAR, str(tmp_path / "via-env"))
    st = planstore.active()
    assert st is not None and st.root == tmp_path / "via-env"

    # explicit empty string disables even with the env var set
    assert planstore.configure("") is None
    assert planstore.active() is None

    # clearing the override hands control back to the env, then to nothing
    planstore.configure(None)
    assert planstore.active() is not None
    monkeypatch.delenv(planstore.ENV_VAR)
    assert planstore.active() is None


def test_inert_without_directory(monkeypatch):
    """No dir configured -> plans cache is memory-only and touches no disk
    counters."""
    planstore = _planstore()
    from repro.core import plans
    monkeypatch.delenv(planstore.ENV_VAR, raising=False)
    planstore.configure(None)
    plans.clear_cache()
    plans.reset_stats()
    from repro.core.config import CommConfig
    plans.chunk_plan((64,), np.float32, CommConfig())
    st = plans.cache_stats()
    assert st["disk_hits"] == 0 and st["disk_misses"] == 0
    assert st["disk_writes"] == 0


# ----------------------------------------------------------------------
# Concurrent writers
# ----------------------------------------------------------------------

def test_two_process_concurrent_writes_leave_valid_store(tmp_path):
    """Two processes hammering the same keys must both exit cleanly and
    leave every entry readable (atomic replace: last writer wins, readers
    never see a torn file)."""
    code = """
import sys
from repro.core import planstore
store = planstore.PlanStore(sys.argv[1])
ring = tuple((j, (j + 1) % 8) for j in range(8))
for rep in range(3):
    for i in range(20):
        assert store.put("ring", ("race", i), ring)
        got = store.get("ring", ("race", i))
        assert got is planstore.MISSING or got == ring
print("WRITER OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    procs = [subprocess.Popen([sys.executable, "-c", code, str(tmp_path)],
                              env=env, cwd=str(REPO),
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True)
             for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, f"writer failed\n{out}\n{err}"
        assert "WRITER OK" in out

    planstore = _planstore()
    store = planstore.PlanStore(tmp_path)
    ring = tuple((j, (j + 1) % 8) for j in range(8))
    for i in range(20):
        assert store.get("ring", ("race", i)) == ring
    # no temp-file litter left behind
    assert not list((tmp_path / "plans").glob("*.tmp"))
