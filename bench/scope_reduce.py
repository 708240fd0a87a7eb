"""Device time by the program's named scopes, and the program's host spans,
from a kept profiler trace.

The program names the phases of the SWE step with ``repro.obs.trace.scope``
(``jax.named_scope``), so each device operation's HLO ``op_name`` holds its
phase.  The TPU's ``XLA Ops`` line names each operation's instruction but
not its name stack; the compiled module's text gives it (:func:`op_names`).
The program's host spans (``swe.segment`` and its children) land in the
host plane beside the harness's ``bench.*`` annotations.

An operation's phase is the innermost of :data:`PHASES` in its name stack,
``unscoped`` where there is none (loop control, layout copies XLA added).
Busy time is counted once: per chip the phases take the union of busy
intervals in the order of :data:`PHASES` (``swe.exchange`` first, so the
halo exchange's time counts whether or not other work hides it), each
keeping only what no earlier phase took, and ``unscoped`` takes the rest.
So the phases sum to ``trace_reduce``'s busy time.  Idle time inside a
host span rests on the profiler lining up host and device clocks, which on
a TPU v5e it does to some hundreds of microseconds only.

    python3 bench/scope_reduce.py --workload <cell> --seed <n> \\
        [--seconds 20] [--record <file.json.gz>]

runs the cell once, traced, with a compile cache of its own so that the
runner compiles and XLA dumps the compiled module; prints the benchmark's
result line, then this breakdown per step.  ``--record`` writes the trace,
cut as in ``bench/tests/data/``, with the program's ring buffer, for the
tests.  Not yet read by the benchmark's metrics: ``harness.run_cell``
removes the raw trace before any reader runs.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Optional

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402

PHASES = ("swe.exchange", "swe.args", "swe.gather", "swe.flux", "swe.update")
UNSCOPED = "unscoped"
SEGMENT = "swe.segment"
SPAN_PREFIX = "swe."
# the segment runner's program (``driver.make_sim_runner``)
MODULE = "jit_body"
OP_NAME = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?\bmetadata=\{[^}]*?'
                     r'op_name="([^"]*)"')


def phase_of(op_name: str) -> str:
    """The innermost phase in an HLO ``op_name``, or ``unscoped``."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return UNSCOPED


def op_names(hlo_text: str) -> dict:
    """Instruction name -> HLO ``op_name`` (its name stack), from the text
    of a compiled module (``Compiled.as_text()``, or XLA's
    ``after_optimizations`` dump)."""
    out = {}
    for line in hlo_text.splitlines():
        m = OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def intersect(a: list, b: list) -> list:
    """Intersection of two disjoint sorted interval lists."""
    return tr.subtract(a, tr.subtract(a, b))


def cut(hlo_text: str) -> str:
    """``%fusion.3 = (f32[8], f32[8]) fusion(%a), kind=kLoop`` ->
    ``%fusion.3 = fusion()``: an instruction's name and opcode only."""
    name, _, rhs = hlo_text.partition(" = ")
    if rhs.startswith("("):             # a tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    m = re.search(r"([\w\-]+)\(", rhs)
    return f"{name} = {m.group(1) if m else rhs.strip()}()"


# ----------------------------------------------------------------------
# Loading
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Scoped:
    """A trace with each device operation's name stack and the program's
    host spans."""
    events: tr.Events          # device operations, bench.* annotations
    scopes: dict               # chip -> [op_name], in ``events.device`` order
    spans: list                # [(name, start_ns, end_ns)] program spans

    def to_json(self) -> dict:
        return {"device": {str(c): [[cut(n), a, b] for n, a, b in evs]
                           for c, evs in self.events.device.items()},
                "host": [list(e) for e in self.events.host],
                "scopes": {str(c): v for c, v in self.scopes.items()},
                "spans": [list(e) for e in self.spans]}


def load_xplane(path: str, names: dict) -> Scoped:
    """Read the trace at ``path``; an operation's name stack is
    ``names[instruction]`` (:func:`op_names` of the compiled module), empty
    for an instruction not in it."""
    from jax.profiler import ProfileData
    events = tr.load_xplane(path)
    scopes, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    scopes[int(m.group(1))] = [
                        names.get(tr.op_name(e.name), "")
                        for e in line.events]
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Scoped(events=events, scopes=scopes, spans=sorted(spans,
                  key=lambda s: s[1]))


def load_dir(trace_root: str, names: dict) -> Scoped:
    paths = glob.glob(os.path.join(trace_root, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane under {trace_root}, "
                                f"found {len(paths)}")
    return load_xplane(paths[0], names)


def load_json(path: str) -> Scoped:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        d = json.load(f)
    return Scoped(events=tr.Events.from_json(d),
                  scopes={int(k): v for k, v in d["scopes"].items()},
                  spans=[tuple(e) for e in d["spans"]])


# ----------------------------------------------------------------------
# Reduction
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Breakdown:
    """Seconds per chip, averaged over the chips, within the window."""
    window_s: float
    busy_s: float
    phases: dict               # phase (or unscoped) -> seconds
    idle_launch_s: float       # idle device time inside ``swe.segment``
    spans: dict                # span name -> [seconds of each, in window]
    chips: int

    def per_step(self, steps: int) -> dict:
        """The program's view of one step, with the benchmark's names."""
        us = 1e6 / steps
        out = {f"solver.{p.split('.', 1)[1]}_us": self.phases[p] * us
               for p in PHASES if p != "swe.exchange"}
        out["solver.unscoped_us"] = self.phases[UNSCOPED] * us
        out["comm.halo_us"] = self.phases["swe.exchange"] * us
        out["idle_launch_share.swe"] = self.idle_launch_s / self.window_s * 100
        for name, durs in sorted(self.spans.items()):
            key = "dispatch_us.swe" if name == SEGMENT else f"{name}_us"
            out[key] = sum(durs) / len(durs) * 1e6
        out["busy_us"] = self.busy_s * us
        return out


def breakdown(sc: Scoped, n_chips: Optional[int] = None) -> Breakdown:
    """Per-phase busy time, and idle time while the host was inside a
    ``swe.segment`` span, within the ``bench.window`` annotation."""
    windows = [(a, b) for n, a, b in sc.events.host if n == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW} annotation, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    chips = sorted(sc.events.device)[:n_chips]
    if not chips:
        raise ValueError("the trace holds no TPU operations")
    spans = [(n, a, b) for n, a, b in sc.spans if a >= lo and b <= hi]
    seg = tr.union((a, b) for n, a, b in spans if n == SEGMENT)
    phases: dict = defaultdict(float)
    busy = idle_launch = 0.0
    for c in chips:
        ops = [(n, a, b, s) for (n, a, b), s
               in zip(sc.events.device[c], sc.scopes[c]) if b > lo and a < hi]
        ops = tr.leaves(ops)
        taken: list = []
        for ph in PHASES + (UNSCOPED,):
            iv = tr.union(tr.clip([(a, b) for _, a, b, s in ops
                                   if phase_of(s) == ph], lo, hi))
            phases[ph] += tr.length(tr.subtract(iv, taken))
            taken = tr.union(taken + iv)
        busy += tr.length(taken)
        idle = tr.subtract([(lo, hi)], taken)
        idle_launch += tr.length(intersect(idle, seg))
    k, ns = len(chips), 1e-9
    durs = defaultdict(list)
    for n, a, b in spans:
        durs[n].append((b - a) * ns)
    return Breakdown(window_s=(hi - lo) * ns, busy_s=busy / k * ns,
                     phases={p: phases[p] / k * ns
                             for p in PHASES + (UNSCOPED,)},
                     idle_launch_s=idle_launch / k * ns, spans=dict(durs),
                     chips=k)


def dumped_op_names(dump_dir: str) -> dict:
    """:func:`op_names` of the runner's program as XLA dumped it."""
    paths = sorted(glob.glob(os.path.join(
        dump_dir, f"*.{MODULE}.*after_optimizations.txt")))
    if not paths:
        raise FileNotFoundError(f"XLA dumped no {MODULE} under {dump_dir}")
    return op_names(Path(paths[-1]).read_text())


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--record", default=None,
                    help="write the cut trace here (.json.gz)")
    args = ap.parse_args(argv)

    # XLA dumps the runner's compiled module, whose text holds each
    # instruction's name stack; a compile cache of this run's own makes the
    # runner compile, and so dump, here.
    work = tempfile.mkdtemp(prefix="scope_reduce_")
    dump = os.path.join(work, "hlo")
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} --xla_dump_to={dump} "
        f"--xla_dump_hlo_module_re={MODULE} --xla_dump_hlo_as_text").strip()
    from bench import harness
    from bench import program_trace
    spec = harness.load_spec()
    cell = harness.find(spec, "workloads", args.workload)
    traffic = json.loads(harness.traffic_file(cell["traffic"]).read_text())
    trace_dir = os.path.join(work, "trace")
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, trace_dir=trace_dir,
                                  cache_dir=os.path.join(work, "cache"))
        print(json.dumps(result), flush=True)
        sc = load_dir(trace_dir, dumped_op_names(dump))
        steps = result["attempted"] * traffic["n_inner"]
        b = breakdown(sc, cell["chips"])
        print(json.dumps({"workload": args.workload, "steps": steps,
                          "window_s": b.window_s, "busy_s": b.busy_s,
                          "per_step": b.per_step(steps)}), flush=True)
        if args.record:
            out = sc.to_json()
            out["program"] = program_trace.spans_of("driver", "setup")
            with gzip.open(args.record, "wt") as f:
                json.dump(out, f)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
