"""Drive ACCL-X's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

One chip:
  swe    the paper's shallow-water solver (``build_simulation`` +
         ``make_sim_runner``) on a synthetic bight mesh, in 20-step
         segments; the same steps on the host CPU backend are the reference.
  serve  gemma3-1b at its published widths in bf16, weights drawn from
         ``--seed`` (``build_session`` + ``build_serve_fn``: one prefill and
         one decode program); 4 requests of 512 prompt tokens, 32 greedy
         tokens each; the logits of the last decode step are checked
         against a prefill over the prompt plus the generated tokens.

Four chips:
  swe4       the same mesh on 4 partitions, fused and overlapped exchange,
             against the 1-partition run on device 0.
  allreduce  ``collectives.all_reduce``, native and ring, at 4 KiB and
             64 MiB per device, against a numpy sum.
  serve4     gemma3-1b on a (1, 4) data x model mesh against TP=1 on
             device 0: the TP=4 decode is fed TP=1's greedy tokens, and
             the logits of the prefill and of every decode step are
             compared.

Every phase raises on a failed check.  The script exits non-zero, and
prints no result line, unless JAX's default device is a TPU.  The last line
of stdout is exactly ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": ...}}``; the readings are on the lines before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Requested elements; the bight generator yields 872,167 and 86,578.  The
# v5e compiler's gather lowering takes compile time linear in the gathered
# rows (the step gathers 3 per element), so the four-chip phase, which
# compiles three programs at four times the chip cost, keeps the small mesh.
SWE_ELEMENTS = 1_000_000
SWE4_ELEMENTS = 100_000
SWE_REF_ELEMENTS = 4_000    # the mesh also run on the host CPU backend
SWE_SEGMENTS = 4            # 20-step segments; the first one compiles
SWE_INNER = 20
# TPU vs CPU backend after the same steps.  Both run f32; the TPU's
# division, sqrt and fusion differ from the CPU's in the last bits.
SWE_STATE_RTOL = 1e-4       # max |diff| over max |state|
SWE_DRIFT_ATOL = 1e-6       # |mass drift difference|

SERVE_ARCH = "gemma3-1b"
SERVE_BATCH = 4
SERVE_PROMPT = 512          # > the 512-token window: local and global layers
SERVE_GEN = 32
# bf16 activations through 26 layers, reduced in another order by the
# decode (one token against the cache) than by the prefill (all tokens at
# once), or at TP=4 than at TP=1: both read 1.6 % on a v5e.  A wrong cache
# slot, position or reduce moves the logits by their own size.
LOGIT_RTOL = 2e-2           # max |diff| over max |reference logit|

ALLREDUCE_BYTES = (4 << 10, 64 << 20)   # per device


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def bytes_in_use(devices) -> dict:
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in devices}


# ----------------------------------------------------------------------
# SWE
# ----------------------------------------------------------------------

def swe_run(n_elements: int, mesh, comm, segments: int = SWE_SEGMENTS,
            n_inner: int = SWE_INNER) -> dict:
    """Build and run the simulation; time each segment to completion."""
    import jax
    import numpy as np
    from repro.swe import driver

    t0 = time.perf_counter()
    sim = driver.build_simulation(n_elements, mesh, comm)
    build_s = time.perf_counter() - t0
    run = driver.make_sim_runner(sim, n_inner=n_inner)
    area_valid = sim.pm.area * sim.pm.valid

    def mass(state):
        return float(np.sum(np.asarray(state, np.float64)[..., 0]
                            * area_valid))

    m0 = mass(sim.state)
    state, t, seg_s = sim.state, 0.0, []
    for _ in range(segments):
        t1 = time.perf_counter()
        state = jax.block_until_ready(run(state, t))
        seg_s.append(time.perf_counter() - t1)
        t += n_inner * sim.swe.dt
    final = np.asarray(state)
    steady = sorted(seg_s[1:])[len(seg_s[1:]) // 2]
    return dict(sim=sim, state=final, build_s=build_s,
                compile_s=seg_s[0] - steady,
                us_per_step=steady / n_inner * 1e6,
                drift=(mass(final) - m0) / m0,
                finite=bool(np.isfinite(final).all()),
                steps=segments * n_inner)


def phase_swe(n_elements: int = SWE_ELEMENTS,
              ref_elements: int = SWE_REF_ELEMENTS,
              segments: int = SWE_SEGMENTS, device=None,
              cpu_device=None) -> dict:
    """One partition on the accelerator at the full mesh; a small mesh on
    the accelerator against the same steps on the host CPU backend."""
    import jax
    import numpy as np
    from repro.core.config import CommConfig
    from repro.launch.mesh import make_mesh

    device = device or jax.devices()[0]
    cpu_device = cpu_device or jax.devices("cpu")[0]
    on_dev = make_mesh((1,), ("data",), devices=[device])
    full = swe_run(n_elements, on_dev, CommConfig(), segments)
    out = dict(elements=full["sim"].mesh.n_elements, steps=full["steps"],
               build_s=full["build_s"], compile_s=full["compile_s"],
               us_per_step=full["us_per_step"], drift=full["drift"],
               peak_bytes=peak_bytes(device))
    log(f"[swe] elements={out['elements']} steps={out['steps']} "
        f"mesh+partition {out['build_s']:.2f}s compile {out['compile_s']:.2f}s "
        f"steady {out['us_per_step']:.1f} us/step "
        f"({out['elements'] / out['us_per_step']:.1f} elements/us) "
        f"mass drift {out['drift']:.6e} "
        f"peak_bytes_in_use={out['peak_bytes']}")
    if not full["finite"]:
        raise AssertionError("swe: non-finite state on the device")
    del full

    small = swe_run(ref_elements, on_dev, CommConfig(), segments)
    ref = swe_run(ref_elements, make_mesh((1,), ("data",),
                                          devices=[cpu_device]),
                  CommConfig(), segments)
    scale = float(np.abs(ref["state"]).max())
    err = float(np.abs(small["state"] - ref["state"]).max())
    out.update(ref_elements=ref["sim"].mesh.n_elements,
               ref_drift=small["drift"], cpu_drift=ref["drift"],
               state_max_abs_diff=err, state_scale=scale)
    log(f"[swe] reference mesh {out['ref_elements']} elements, "
        f"{ref['steps']} steps: mass drift {small['drift']:.6e} on "
        f"{device.platform}, {ref['drift']:.6e} on cpu (limit "
        f"{SWE_DRIFT_ATOL:g}); state max|diff| {err:.3e} of {scale:.3e} "
        f"(limit {SWE_STATE_RTOL:g} x)")
    if not small["finite"]:
        raise AssertionError("swe: non-finite state on the device")
    if err > SWE_STATE_RTOL * scale:
        raise AssertionError(f"swe: state differs from the CPU backend by "
                             f"{err:.3e} > {SWE_STATE_RTOL} x {scale:.3e}")
    if abs(small["drift"] - ref["drift"]) > SWE_DRIFT_ATOL:
        raise AssertionError(f"swe: mass drift {small['drift']:.6e} vs CPU "
                             f"{ref['drift']:.6e} (> {SWE_DRIFT_ATOL})")
    return out


def phase_swe4(n_elements: int = SWE4_ELEMENTS,
               segments: int = SWE_SEGMENTS - 1) -> dict:
    """4 partitions, fused and overlapped, against 1 partition on
    device 0 — compared in global element order."""
    import jax
    import numpy as np
    from repro.core.config import OVERLAPPED_CONFIG, CommConfig
    from repro.launch.mesh import make_mesh
    from repro.swe.driver import flatten_state

    devs = jax.devices()[:4]
    one = swe_run(n_elements, make_mesh((1,), ("data",), devices=devs[:1]),
                  CommConfig(), segments)
    ref = flatten_state(one["sim"], one["state"])
    out = {"elements": one["sim"].mesh.n_elements,
           "us_per_step_1": one["us_per_step"]}
    for name, cfg in (("fused", CommConfig()),
                      ("overlapped", OVERLAPPED_CONFIG)):
        r = swe_run(n_elements, make_mesh((4,), ("data",), devices=devs),
                    cfg, segments)
        got = flatten_state(r["sim"], r["state"])
        err = float(np.abs(got - ref).max())
        bitwise = bool(np.array_equal(got, ref))
        out[name] = dict(us_per_step=r["us_per_step"],
                         compile_s=r["compile_s"], max_abs_diff=err,
                         bitwise=bitwise)
        log(f"[swe4] {name}: 4 partitions vs 1, {r['steps']} steps, "
            f"max|diff| {err:.3e} bitwise={bitwise} "
            f"steady {r['us_per_step']:.1f} us/step "
            f"(1 partition {one['us_per_step']:.1f}) "
            f"compile {r['compile_s']:.2f}s")
        scale = float(np.abs(ref).max())
        if not np.isfinite(got).all() or err > SWE_STATE_RTOL * scale:
            raise AssertionError(f"swe4 {name}: 4-partition state differs "
                                 f"by {err:.3e} (scale {scale:.3e})")
    return out


# ----------------------------------------------------------------------
# all_reduce
# ----------------------------------------------------------------------

def phase_allreduce(sizes=ALLREDUCE_BYTES, n: int = 4) -> dict:
    """Integer-valued f32 payloads: every summation order is exact, so
    both algorithms must equal the numpy sum bit for bit."""
    from functools import partial

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import CommConfig, Communicator, collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n,), ("x",), devices=jax.devices()[:n])
    comm = Communicator.from_mesh(mesh, "x")
    rng = np.random.RandomState(0)
    out = {}
    for nbytes in sizes:
        x = rng.randint(-8, 8, (n, nbytes // 4)).astype(np.float32)
        ref = x.sum(0)
        xd = jax.device_put(x, NamedSharding(mesh, P("x")))
        for algo in ("native", "ring"):
            cfg = CommConfig(algorithm=algo)

            @jax.jit
            @partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                     out_specs=P("x"))
            def f(xs):
                return collectives.all_reduce(xs[0], comm, cfg)[None]

            jax.block_until_ready(f(xd))
            t0 = time.perf_counter()
            got = jax.block_until_ready(f(xd))
            us = (time.perf_counter() - t0) * 1e6
            got = np.asarray(got)
            exact = bool((got == ref[None]).all())
            out[f"{algo}_{nbytes}"] = dict(us=us, exact=exact)
            log(f"[allreduce] {algo:6s} {nbytes:>9d} B/device on {n}: "
                f"exact={exact} one call {us:.1f} us")
            if not exact:
                raise AssertionError(f"all_reduce {algo} at {nbytes} B "
                                     f"differs from the numpy sum")
    return out


# ----------------------------------------------------------------------
# Serving
# ----------------------------------------------------------------------

def serve_config(arch: str = SERVE_ARCH):
    from repro.configs.registry import get_config
    cfg = get_config(arch)
    import jax.numpy as jnp
    return dataclasses.replace(cfg, dtype=jnp.bfloat16)


def _compiled_serve_fns(cfg, mesh, params, batch: int, prompt: int,
                        gen: int, comm):
    """AOT-compiled prefill (caches sized for prompt + gen) and decode."""
    import jax.numpy as jnp
    from repro.launch import input_specs as isp
    from repro.train import serve

    shape_p = isp.ShapeSpec("chip_smoke_prefill", prompt, batch, "prefill")
    shape_d = isp.ShapeSpec("chip_smoke_decode", prompt + gen, batch,
                            "decode")
    rt, prefill, _ = serve.build_serve_fn(
        cfg, mesh, comm, shape_p,
        cache_capacity=serve.cache_len(cfg, shape_d))
    _, decode, _ = serve.build_serve_fn(cfg, mesh, comm, shape_d)
    toks = jnp.zeros((batch, prompt), jnp.int32)
    t0 = time.perf_counter()
    prefill_c = prefill.lower(params, {"tokens": toks}).compile()
    t1 = time.perf_counter()
    # a real ServeState to lower decode against (shapes and shardings)
    state = prefill_c(params, {"tokens": toks})
    tok = jnp.zeros((batch,), jnp.int32)
    t2 = time.perf_counter()
    decode_c = decode.lower(params, tok, state).compile()
    t3 = time.perf_counter()
    return rt, prefill_c, decode_c, t1 - t0, t3 - t2


def greedy(prefill_c, decode_c, params, prompts, gen: int,
           forced=None) -> dict:
    """Prefill, then ``gen`` decode steps; returns the greedy tokens, the
    logits of the prefill and of every decode step, and the timings (every
    program compiled before the clock starts).  ``forced`` ((B, gen)
    tokens) feeds those tokens to the decode steps instead of the greedy
    ones, so that two runs can be compared step by step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def pick(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    warm = jax.block_until_ready(pick(prefill_c(params, {"tokens": prompts})
                                      .last_logits))
    # placed as the greedy tokens are, so both feeds run the same program
    feed = (None if forced is None else
            [jax.device_put(forced[:, j].astype(np.int32), warm.sharding)
             for j in range(gen)])
    t0 = time.perf_counter()
    state = prefill_c(params, {"tokens": prompts})
    tok = pick(state.last_logits)
    jax.block_until_ready(tok)
    ttft = time.perf_counter() - t0
    toks, logits = [tok], [state.last_logits]
    t1 = time.perf_counter()
    for j in range(gen):
        state = decode_c(params, tok if feed is None else feed[j], state)
        tok = pick(state.last_logits)
        toks.append(tok)
        logits.append(state.last_logits)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t1
    return dict(tokens=np.stack([np.asarray(t) for t in toks], 1),
                logits=[np.asarray(l, np.float32) for l in logits],
                ttft_s=ttft, ms_per_token=decode_s / gen * 1e3)


def phase_serve(cfg=None, batch: int = SERVE_BATCH,
                prompt: int = SERVE_PROMPT, gen: int = SERVE_GEN,
                seed: int = 0, device=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.config import CommConfig
    from repro.launch import input_specs as isp, setup
    from repro.launch.mesh import make_mesh
    from repro.train import serve

    cfg = cfg or serve_config()
    device = device or jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[device])
    comm = CommConfig()
    sess = setup.build_session(cfg, mesh, comm, seed=seed)
    if sess.opt_state is not None:
        raise AssertionError("serving allocated optimizer state")
    rt, prefill_c, decode_c, cp, cd = _compiled_serve_fns(
        cfg, mesh, sess.params, batch, prompt, gen, comm)
    prompts = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int32))
    run = greedy(prefill_c, decode_c, sess.params, prompts, gen)

    # Reference: one prefill over the prompt plus the gen tokens the decode
    # steps consumed; its last logits are those of decode step gen.
    seq = np.concatenate([np.asarray(prompts), run["tokens"][:, :gen]], 1)
    _, ref_fn, _ = serve.build_serve_fn(
        cfg, mesh, comm, isp.ShapeSpec("chip_smoke_ref", prompt + gen,
                                       batch, "prefill"))
    ref = np.asarray(ref_fn(sess.params, {"tokens": jnp.asarray(seq)}
                            ).last_logits, np.float32)
    err = float(np.abs(run["logits"][-1] - ref).max())
    scale = float(np.abs(ref).max())
    finite = all(bool(np.isfinite(l).all()) for l in run["logits"])
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(sess.params))
    out = dict(params=n_params, layers=cfg.n_layers,
               compile_prefill_s=cp, compile_decode_s=cd,
               ttft_ms=run["ttft_s"] * 1e3,
               ms_per_token=run["ms_per_token"],
               decode_vs_prefill_max_abs=err, logit_scale=scale,
               attention="jnp (Runtime.use_pallas=False), "
                         f"attn_tiling={rt.attn_tiling}",
               peak_bytes=peak_bytes(device))
    log(f"[serve] {cfg.name} {n_params / 1e9:.3f}B params, {cfg.n_layers} "
        f"layers, d_model={cfg.d_model}, vocab={cfg.vocab_size}, "
        f"{jnp.dtype(cfg.dtype).name}; {batch} requests x {prompt} prompt "
        f"tokens, {gen} greedy tokens")
    log(f"[serve] attention path: {out['attention']} (the Pallas flash "
        f"kernel is not selected by any entry point)")
    log(f"[serve] compile prefill {cp:.2f}s decode {cd:.2f}s; "
        f"TTFT {out['ttft_ms']:.2f} ms; {out['ms_per_token']:.3f} ms/token "
        f"(batch {batch}); peak_bytes_in_use={out['peak_bytes']}")
    log(f"[serve] decode step {gen} vs prefill over prompt+{gen}: "
        f"max|diff| {err:.4g} of max|logit| {scale:.4g} "
        f"(limit {LOGIT_RTOL} x)")
    if not finite:
        raise AssertionError("serve: non-finite logits")
    if err > LOGIT_RTOL * scale:
        raise AssertionError(f"serve: decode logits differ from the prefill "
                             f"reference by {err:.4g} > {LOGIT_RTOL} x "
                             f"{scale:.4g}")
    return out


def phase_serve4(cfg=None, batch: int = SERVE_BATCH,
                 prompt: int = SERVE_PROMPT, gen: int = SERVE_GEN,
                 seed: int = 0) -> dict:
    """TP=4 on a (1, 4) mesh against TP=1 on device 0.  TP=1 decodes
    greedily; TP=4 is fed the same tokens, so the two runs see the same
    sequences and the logits of every step must agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.config import CommConfig
    from repro.launch import setup
    from repro.launch.mesh import make_mesh

    cfg = cfg or serve_config()
    devs = jax.devices()[:4]
    prompts = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int32))
    runs = {}
    for tp, mesh in ((1, make_mesh((1, 1), ("data", "model"),
                                   devices=devs[:1])),
                     (4, make_mesh((1, 4), ("data", "model"),
                                   devices=devs))):
        comm = CommConfig()
        sess = setup.build_session(cfg, mesh, comm, seed=seed)
        _, prefill_c, decode_c, cp, cd = _compiled_serve_fns(
            cfg, mesh, sess.params, batch, prompt, gen, comm)
        forced = None if tp == 1 else runs[1]["tokens"][:, :gen]
        runs[tp] = greedy(prefill_c, decode_c, sess.params, prompts, gen,
                          forced=forced)
        log(f"[serve4] TP={tp}: compile prefill {cp:.2f}s decode {cd:.2f}s; "
            f"TTFT {runs[tp]['ttft_s'] * 1e3:.2f} ms; "
            f"{runs[tp]['ms_per_token']:.3f} ms/token")
        if tp == 4:     # the TP=4 weights and caches are live here
            in_use = bytes_in_use(devs)
            log(f"[serve4] TP=4 bytes_in_use per device: {in_use}")
        del sess, prefill_c, decode_c
    # step 0 is the prefill, step k the k-th decode step
    rel = [float(np.abs(got - ref).max() / np.abs(ref).max())
           for ref, got in zip(runs[1]["logits"], runs[4]["logits"])]
    worst = int(np.argmax(rel))
    same = int((runs[1]["tokens"] == runs[4]["tokens"]).sum())
    log(f"[serve4] logits TP=4 vs TP=1, fed the same tokens, prefill and "
        f"{gen} decode steps: max|diff| / max|logit| {rel[0]:.4g} at the "
        f"prefill, {max(rel[1:]):.4g} at the worst decode step; worst "
        f"{rel[worst]:.4g} at step {worst} (limit {LOGIT_RTOL}); greedy "
        f"picks equal {same}/{runs[1]['tokens'].size}")
    log("[serve4] per step: " + " ".join(f"{r:.4g}" for r in rel))
    if not all(np.isfinite(l).all() for l in runs[4]["logits"]):
        raise AssertionError("serve4: non-finite logits at TP=4")
    if rel[worst] > LOGIT_RTOL:
        raise AssertionError(f"serve4: TP=4 logits differ from TP=1 by "
                             f"{rel[worst]:.4g} x max|logit| at step "
                             f"{worst} (> {LOGIT_RTOL})")
    held = [b for b in in_use.values() if b is not None]
    if held and min(held) < max(held) / 4:
        raise AssertionError(f"serve4: TP=4 state is not spread over the "
                             f"4 devices: {in_use}")
    return dict(logit_rel_diff=rel, tokens_equal=same, bytes_in_use=in_use)


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # The SWE reference runs on the host CPU backend in this process.
    plat = os.environ.get("JAX_PLATFORMS")
    if plat and "cpu" not in plat.split(","):
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"

    import jax
    from repro.launch import compile_cache

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke.py: JAX's default device is {dev0.platform!r}, "
              f"not a TPU; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache_dir = compile_cache.configure()
    log(f"device: {dev0.platform} {dev0.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    phases = {}
    t0 = time.perf_counter()
    if args.chips == 1:
        phases["swe"] = phase_swe()
        phases["serve"] = phase_serve(seed=args.seed)
    else:
        phases["swe4"] = phase_swe4()
        phases["allreduce"] = phase_allreduce()
        phases["serve4"] = phase_serve4(seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    log("phases: " + json.dumps(phases, default=float))
    print(json.dumps({"ok": True,
                      "device": {"platform": dev0.platform,
                                 "kind": dev0.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
