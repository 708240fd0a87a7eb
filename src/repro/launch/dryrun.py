"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. constructs abstract (ShapeDtypeStruct) params/optimizer/batch or caches,
  3. ``jit(step).lower(...)`` then ``.compile()`` — proving the sharding
     configuration is coherent end to end (no allocation ever happens),
  4. records ``memory_analysis()``, ``cost_analysis()`` and the per-type
     collective byte counts parsed from the optimized HLO,
into ``artifacts/dryrun/{arch}__{shape}__{mesh}.json`` for EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--shapes train_4k,...]

It runs on 512 forced host CPU devices and exits at once on an accelerator.
"""
import argparse
import dataclasses
import json
import os
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs.registry import get_config, list_archs
from repro.core.config import CommConfig, CommMode, Scheduling, Transport, Compression
from repro.launch import compile_cache, input_specs as isp
from repro.launch.mesh import exit_unless_host_cpu, make_production_mesh

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", type_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (optimized) HLO.

    Operand types appear inline in HLO text: ``all-reduce(f32[4096]{0} %x)``.
    Falls back to the result type when operands carry no inline types.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*([a-z0-9_\[\],{}()\s]*?)\s*"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start)?\(", stripped)
        if not m:
            continue
        op = m.group(2)
        if m.group(3) == "-start" or "-done(" in stripped:
            pass
        if re.search(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)-done\(", stripped):
            continue
        # operand types inside the call parens
        call = stripped[stripped.index(m.group(2)):]
        operand_types = re.findall(r"([a-z0-9]+\[[0-9,]*\])(?:\{[^}]*\})?\s*%",
                                   call)
        nbytes = sum(_shape_bytes(t) for t in operand_types)
        if nbytes == 0:
            result_types = re.findall(r"([a-z0-9]+\[[0-9,]*\])", m.group(1))
            nbytes = sum(_shape_bytes(t) for t in result_types)
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def build_comm(args) -> CommConfig:
    return CommConfig(
        mode=CommMode(args.mode),
        scheduling=Scheduling.FUSED,
        transport=Transport(args.transport),
        compression=Compression(args.compression),
        algorithm=args.algorithm,
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, comm: CommConfig,
             fsdp: str = "auto", attn_tiling: str = "auto",
             moment_dtype: str = "float32", seq_parallel: bool = False,
             shard_attn: str = "", grad_comm: "CommConfig|None" = None,
             padded_heads: int = 0, remat_policy: str = "") -> dict:
    import jax.numpy as jnp
    from repro.launch import setup
    from repro.models import decode as dec
    from repro.optim import adamw

    t0 = time.time()
    cfg = get_config(arch)
    if shard_attn:
        cfg = dataclasses.replace(cfg, shard_attn=shard_attn)
    if padded_heads:
        cfg = dataclasses.replace(cfg, padded_heads=padded_heads)
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    shape = isp.SHAPES[shape_name]
    ok, reason = isp.applicable(arch, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "comm": dataclasses.asdict(comm),
           "status": "skip", "reason": reason}
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 1
    for s in mesh.shape.values():
        n_chips *= s

    # FSDP for ≥30B-param training cells (weights would not fit TP-only).
    use_fsdp = (shape.kind == "train" and
                (fsdp == "on" or (fsdp == "auto"
                                  and cfg.param_count() > 2e10)))
    oc = adamw.OptConfig(zero1=True,
                         moment_dtype=getattr(jnp, moment_dtype),
                         grad_comm=grad_comm)

    if shape.kind == "train":
        sess = setup.build_session(cfg, mesh, comm, oc=oc, fsdp=use_fsdp,
                                   concrete=False, attn_tiling=attn_tiling,
                                   seq_parallel=seq_parallel)
        batch, bspec = isp.train_inputs(cfg, shape, mesh)
        abstract_params = jax.eval_shape(
            lambda k: __import__("repro.models.transformer",
                                 fromlist=["init_model"]).init_model(
                k, cfg, mesh.shape["model"]), jax.random.PRNGKey(0))
        opt_abs = jax.eval_shape(
            lambda p: adamw.init_state(p, oc, sess.rt, sess.rt.fsdp_plan),
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                _local_shape(s.shape, sess.param_spec, mesh, path=None),
                s.dtype), abstract_params))
        # Build opt abstract with GLOBAL shapes instead:
        opt_abs = _globalize_opt(opt_abs, sess, mesh)
        step_builder = setup.make_sharded_train_step(sess, donate=False)
        fn = step_builder(bspec)
        lowered = fn.lower(abstract_params, opt_abs, batch)
    else:
        from repro.train import serve as serve_mod
        sess_rt, fn, args_abs = serve_mod.build_serve_fn(
            cfg, mesh, comm, shape, attn_tiling=attn_tiling)
        lowered = fn.lower(*args_abs)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from repro.launch.hlo_analysis import analyze_hlo
    scaled = analyze_hlo(hlo)

    rec.update({
        "status": "ok",
        "n_chips": n_chips,
        "fsdp": use_fsdp,
        "opts": {"seq_parallel": seq_parallel, "attn_tiling": attn_tiling,
                 "shard_attn": shard_attn, "padded_heads": padded_heads,
                 "moment_dtype": moment_dtype,
                 "grad_compression": (grad_comm.compression.value
                                      if grad_comm else "none")},
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "cost": {
            # raw XLA numbers (loop bodies counted ONCE — see hlo_analysis)
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        # trip-count-aware totals (the roofline source of truth)
        "scaled": {
            "flops": scaled["flops"],
            "hbm_bytes": scaled["hbm_bytes"],
            "dot_bytes": scaled["dot_bytes"],
            "collective_bytes": scaled["collective_bytes"],
            "collective_counts": scaled["collective_counts"],
            "collective_total": scaled["collective_total"],
        },
        "collectives": coll,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "tokens": shape.global_batch * (1 if shape.kind == "decode"
                                        else shape.seq_len),
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    })
    return rec


def _local_shape(shape, spec_tree, mesh, path):
    return shape  # placeholder (abstract opt init uses global shapes)


def _globalize_opt(opt_abs, sess, mesh):
    """Adjust ZeRO slice leaves to their global (tp, dp, k) shapes."""
    import jax.numpy as jnp
    if "m_slice" not in opt_abs:
        return opt_abs
    tp = mesh.shape["model"]
    data_axis = [a for a in mesh.axis_names if a != "model"][-1]
    dp = mesh.shape[data_axis]
    k = opt_abs["m_slice"].shape[-1]
    # init_state sized k from GLOBAL param shapes (eval_shape saw global
    # arrays); the true local flat size uses local shards. Recompute exactly:
    from repro.optim import adamw as _a
    reg, fs = _a.partition_params(
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                     _local_params_abstract(sess, mesh)), sess.rt.fsdp_plan)
    n = sum(int(l.size if hasattr(l, "size") else 0)
            for l in jax.tree.leaves(reg))
    pad = (-n) % dp
    k_local = (n + pad) // dp
    def fix(leaf, path_is_slice):
        return jax.ShapeDtypeStruct((tp, dp, k_local), leaf.dtype)
    out = dict(opt_abs)
    out["m_slice"] = jax.ShapeDtypeStruct((tp, dp, k_local),
                                          opt_abs["m_slice"].dtype)
    out["v_slice"] = jax.ShapeDtypeStruct((tp, dp, k_local),
                                          opt_abs["v_slice"].dtype)
    return out


def _local_params_abstract(sess, mesh):
    """Per-device param shapes under the session's param spec."""
    import numpy as np
    from repro.models import transformer
    global_abs = jax.eval_shape(
        lambda k: transformer.init_model(k, sess.cfg, mesh.shape["model"]),
        jax.random.PRNGKey(0))

    def localize(s, spec):
        shape = list(s.shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shape[i] //= mesh.shape[a]
        return jax.ShapeDtypeStruct(tuple(shape), s.dtype)

    return jax.tree.map(localize, global_abs, sess.param_spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--shapes", default=None, help="comma list")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="streaming")
    ap.add_argument("--transport", default="unordered")
    ap.add_argument("--compression", default="none")
    ap.add_argument("--algorithm", default="native")
    ap.add_argument("--attn-tiling", default="auto")
    ap.add_argument("--fsdp", default="auto")
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--shard-attn", default="")
    ap.add_argument("--grad-compression", default="",
                    help="int8|bf16: ring-compressed ZeRO grad RS/AG")
    ap.add_argument("--padded-heads", type=int, default=0)
    ap.add_argument("--remat-policy", default="")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    # Read when the CPU backend starts, which the check below does.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("DRYRUN_BASE_XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    exit_unless_host_cpu("python -m repro.launch.dryrun")
    compile_cache.configure()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = (args.shapes.split(",") if args.shapes
              else ([args.shape] if args.shape else list(isp.SHAPES)))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    comm = build_comm(args)

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                tag = f"__{args.tag}" if args.tag else ""
                out = ARTIFACTS / f"{arch}__{shape}__{mesh_name}{tag}.json"
                gcomm = None
                if args.grad_compression:
                    gcomm = CommConfig(algorithm="ring",
                                       compression=Compression(
                                           args.grad_compression))
                try:
                    rec = run_cell(arch, shape, mp, comm, fsdp=args.fsdp,
                                   attn_tiling=args.attn_tiling,
                                   moment_dtype=args.moment_dtype,
                                   seq_parallel=args.seq_parallel,
                                   shard_attn=args.shard_attn,
                                   grad_comm=gcomm,
                                   padded_heads=args.padded_heads,
                                   remat_policy=args.remat_policy)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": str(e)[-2000:],
                           "traceback": traceback.format_exc()[-4000:]}
                out.write_text(json.dumps(rec, indent=1, default=str))
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    mem_gb = (rec["memory"]["argument_bytes"]
                              + rec["memory"]["temp_bytes"]) / 2**30
                    extra = (f" flops={rec['scaled']['flops']:.3e}"
                             f" mem/dev={mem_gb:.2f}GiB"
                             f" coll={rec['scaled']['collective_total']:.3e}B"
                             f" compile={rec['compile_s']}s")
                print(f"[dryrun] {arch} {shape} {mesh_name}: {status}{extra}",
                      flush=True)


if __name__ == "__main__":
    main()
