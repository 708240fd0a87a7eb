"""Continuous-batching serving demo: per-phase tuned comm, waves of
requests arriving mid-flight, greedy decode on the sequence-sharded KV cache.

Requests arrive on a seeded schedule while earlier waves are still
decoding.  Waiting requests are admitted in fixed-shape waves (so no serving
step ever recompiles); each wave is prefilled at the *prompt length* —
the KV caches it builds cover prompt + generation via ``cache_capacity`` —
and active waves then decode round-robin, one token per step, retiring as
their (per-request, variable) generation targets complete.

``--comm auto`` resolves a different CommConfig per phase from the TuneDB:
prefill and decode are distinct tuned consumers (latency-bound per-token
combines vs throughput-bound bulk reduces) and select different winners
from the same measurements.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/serve_lm.py --arch qwen3-8b --comm auto
"""
import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.core import plans
from repro.core.config import CommConfig
from repro.launch import compile_cache, input_specs as isp, setup
from repro.launch.mesh import make_mesh
from repro.train import serve as serve_mod


def _cfg_str(c: CommConfig) -> str:
    return (f"{c.mode.value}/{c.scheduling.value}/{c.transport.value}"
            f"/chunk{c.chunk_bytes}/{c.algorithm}")


@dataclasses.dataclass
class Request:
    rid: int
    arrival: int              # decode-step tick the request arrives at
    prompt: np.ndarray        # (prompt_len,) int32
    gen_target: int           # tokens to generate (variable per request)


@dataclasses.dataclass
class Wave:
    wid: int
    requests: list            # Request per slot (tail slots may repeat)
    valid: list               # bool per slot (False = tail padding)
    state: object = None
    steps: int = 0
    tokens: list = dataclasses.field(default_factory=list)  # (B,) per step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--batch", type=int, default=4,
                    help="wave size (fixed serving shape)")
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=32,
                    help="max tokens per request (each request draws a "
                    "target in [gen/2, gen])")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arrival-every", type=int, default=3,
                    help="a new request arrives every N decode steps")
    ap.add_argument("--max-active", type=int, default=2,
                    help="concurrent waves in flight")
    ap.add_argument("--comm", default="static",
                    help="'static' (paper default CommConfig) or 'auto' "
                    "(per-phase TuneDB selection)")
    ap.add_argument("--tune-db", default=None,
                    help="TuneDB path for --comm auto")
    ap.add_argument("--objective", default="e2e",
                    choices=("latency", "e2e"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--expect-phase-distinct", action="store_true",
                    help="exit non-zero unless prefill and decode resolved "
                    "DIFFERENT CommConfigs (CI guard for per-phase auto)")
    ap.add_argument("--expect-plan-hits", action="store_true",
                    help="exit non-zero unless the CommPlan cache recorded "
                    "hits > 0 while serving (plan-cached comm path guard)")
    args = ap.parse_args()

    cfg = dataclasses.replace(get_smoke_config(args.arch), dtype=jnp.float32)
    n = jax.device_count()
    model_axis = 4 if n >= 4 else 1
    mesh = make_mesh((n // model_axis, model_axis), ("data", "model"))
    comm = "auto" if args.comm == "auto" else CommConfig()
    sess = setup.build_session(cfg, mesh, CommConfig(), concrete=True)

    max_len = args.prompt_len + args.gen
    # Prefill spec at PROMPT length; cache capacity covers generation too.
    shape_p = isp.ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    shape_d = isp.ShapeSpec("serve", max_len, args.batch, "decode")
    rt_p, prefill_fn, pre_abs = serve_mod.build_serve_fn(
        cfg, mesh, comm, shape_p, tune_db_path=args.tune_db,
        objective=args.objective,
        cache_capacity=serve_mod.cache_len(cfg, shape_d))
    rt_d, decode_fn, _ = serve_mod.build_serve_fn(
        cfg, mesh, comm, shape_d, tune_db_path=args.tune_db,
        objective=args.objective)
    print(f"[prefill] comm: {_cfg_str(rt_p.comm)}")
    print(f"[decode]  comm: {_cfg_str(rt_d.comm)}")
    distinct = rt_p.comm != rt_d.comm
    if distinct:
        print("phase-distinct configs selected")

    # The traced prefill program is built for exactly the prompt shape —
    # assert the fed batch matches the spec (the silent-mismatch bug this
    # demo used to carry: a max_len spec fed prompt_len tokens).
    abs_tokens = pre_abs[1]["tokens"]
    assert abs_tokens.shape == (args.batch, args.prompt_len), (
        abs_tokens.shape, (args.batch, args.prompt_len))

    rng = np.random.RandomState(args.seed)
    reqs = [Request(rid=r, arrival=r * args.arrival_every,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       args.prompt_len).astype(np.int32),
                    gen_target=int(rng.randint(max(1, args.gen // 2),
                                               args.gen + 1)))
            for r in range(args.requests)]
    pending = list(reqs)          # not yet arrived
    waiting: list = []            # arrived, not yet admitted to a wave
    active: list = []             # waves in flight
    finished: dict = {}           # rid -> list of generated token ids
    ttft: dict = {}               # rid -> seconds from arrival to 1st logits
    arrival_wall: dict = {}

    def pick(logits):
        return np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)

    tick = 0                      # global decode-step clock
    wid = 0
    rr = 0                        # round-robin cursor over active waves
    decode_steps = 0
    decode_wall = 0.0
    t_run = time.perf_counter()
    while pending or waiting or active:
        while pending and pending[0].arrival <= tick:
            r = pending.pop(0)
            arrival_wall[r.rid] = time.perf_counter()
            waiting.append(r)
        can_admit = len(active) < args.max_active and waiting and (
            len(waiting) >= args.batch or not pending)
        if can_admit:
            members = waiting[:args.batch]
            del waiting[:len(members)]
            valid = [True] * len(members)
            while len(members) < args.batch:     # tail wave: pad + mask
                members.append(members[-1])
                valid.append(False)
            wave = Wave(wid=wid, requests=members, valid=valid)
            wid += 1
            toks = jnp.asarray(np.stack([r.prompt for r in members]))
            t0 = time.perf_counter()
            wave.state = jax.block_until_ready(
                prefill_fn(sess.params, {"tokens": toks}))
            dt = time.perf_counter() - t0
            for r, v in zip(members, valid):
                if v:
                    ttft[r.rid] = time.perf_counter() - arrival_wall[r.rid]
            print(f"[prefill] wave {wave.wid}: "
                  f"{sum(valid)} reqs x {args.prompt_len} tok, "
                  f"{dt * 1e3:.1f} ms ({len(active) + 1} wave(s) in flight)")
            active.append(wave)
            continue
        if not active:
            tick += 1             # idle: nothing admitted, wait for arrivals
            continue
        wave = active[rr % len(active)]
        tok = pick(wave.state.last_logits)
        t0 = time.perf_counter()
        wave.state = decode_fn(sess.params, jnp.asarray(tok), wave.state)
        jax.block_until_ready(wave.state.last_logits)
        decode_wall += time.perf_counter() - t0
        wave.tokens.append(tok)
        wave.steps += 1
        decode_steps += 1
        tick += 1
        need = max(r.gen_target for r, v in zip(wave.requests, wave.valid)
                   if v)
        if wave.steps >= need:
            gen = np.stack(wave.tokens, 1)       # (B, steps)
            done = 0
            for i, (r, v) in enumerate(zip(wave.requests, wave.valid)):
                if v and r.rid not in finished:
                    finished[r.rid] = gen[i, :r.gen_target].tolist()
                    done += 1
            active.remove(wave)
            print(f"[decode]  wave {wave.wid}: retired after {wave.steps} "
                  f"steps ({done} reqs complete, "
                  f"{len(active)} wave(s) remain)")
        rr += 1

    wall = time.perf_counter() - t_run
    gen_tokens = sum(len(v) for v in finished.values())
    ms_tok = decode_wall / max(1, decode_steps) * 1e3
    print(f"served {len(finished)}/{args.requests} requests, "
          f"{gen_tokens} tokens in {wall:.2f} s")
    print(f"[decode]  {decode_steps} steps, {ms_tok:.1f} ms/token/wave, "
          f"{gen_tokens / max(decode_wall, 1e-9) / n:.1f} tokens/s/rank "
          f"({n} ranks)")
    if ttft:
        p50 = float(np.median(list(ttft.values())))
        print(f"[prefill] TTFT p50 {p50 * 1e3:.1f} ms over {len(ttft)} reqs")
    stats = plans.cache_stats()
    hits = stats.get("plan_hits", 0) + stats.get("program_hits", 0)
    print(f"plans cache: {stats.get('plan_hits', 0)} plan hits / "
          f"{stats.get('plan_misses', 0)} misses, "
          f"{stats.get('program_hits', 0)} program hits")
    for rid in sorted(finished)[:2]:
        print(f"  req{rid}: {finished[rid][:12]}")

    if args.expect_phase_distinct and not distinct:
        print("EXPECT-PHASE-DISTINCT FAILED: prefill and decode resolved "
              "the same CommConfig", file=sys.stderr)
        return 2
    if args.expect_plan_hits and hits <= 0:
        print("EXPECT-PLAN-HITS FAILED: the serving run recorded zero "
              "CommPlan cache hits", file=sys.stderr)
        return 3
    assert sorted(finished) == [r.rid for r in reqs], "dropped requests"
    return 0


if __name__ == "__main__":
    compile_cache.configure()
    raise SystemExit(main())
