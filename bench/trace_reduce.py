"""Reduce a profiler trace of a measured window to the benchmark's numbers.

The trace is the ``*.xplane.pb`` that ``jax.profiler`` writes.  Per chip the
reduction reads the ``XLA Ops`` line of the plane ``/device:TPU:<n>`` (each
operation of a compiled program, with its start and length on the device,
the HLO instruction as its name), and from the host plane the ``bench.*``
annotations that the harness writes around its phases.  Everything is
clipped to the ``bench.window`` annotation.

Operations that contain others on the same line (a ``while`` loop and the
body it runs) count only through what they contain.

- busy: the union of the intervals of all operations of a chip.
- exposed collective time: the part of the union of collective operations
  (``collective-permute``, ``all-reduce``, ``all-gather``, ``all-to-all``,
  ``reduce-scatter``, ``collective-broadcast``, with their ``-start`` and
  ``-done`` halves) that no other operation covers.
- compute time: the union of the other operations.
- idle gaps: the complement of busy in the window, each named by the host
  annotation that overlaps it most.

Numbers are averages over the chips, in seconds.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION = "bench."
WINDOW = "bench.window"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather", "all-to-all",
               "reduce-scatter", "collective-broadcast")


def op_name(hlo_text: str) -> str:
    """``%fusion.16 = f32[...] fusion(...)`` -> ``fusion.16``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@functools.lru_cache(maxsize=1 << 16)
def op_kind(hlo_text: str) -> str:
    """The instruction's name without its numbering: ``fusion``,
    ``collective-permute-start``, ``copy-done`` ..."""
    name = op_name(hlo_text)
    return re.sub(r"(\.\d+|\.clone|\.sunk)+$", "", name)


@functools.lru_cache(maxsize=1 << 16)
def is_collective(hlo_text: str) -> bool:
    kind = op_kind(hlo_text)
    if any(kind.startswith(c) for c in COLLECTIVES):
        return True
    # an instruction named otherwise whose opcode is a collective
    rhs = hlo_text.split(" = ", 1)[1] if " = " in hlo_text else ""
    return any(re.search(r"\b" + re.escape(c) + r"(-start|-done)?\(", rhs)
               for c in COLLECTIVES)


# ----------------------------------------------------------------------
# Intervals
# ----------------------------------------------------------------------

def union(intervals: Iterable[tuple]) -> list[tuple]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def length(intervals: Iterable[tuple]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: list[tuple], cut: list[tuple]) -> list[tuple]:
    """``base`` minus ``cut``; both disjoint and sorted."""
    out = []
    j = 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        cur = a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


# ----------------------------------------------------------------------
# Events
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Events:
    """What the reduction reads: device operations per chip and host
    annotations, as ``(name, start_ns, end_ns)``."""
    device: dict            # chip id -> [(hlo text, start, end)]
    host: list              # [(annotation, start, end)]

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(device={int(k): [tuple(e) for e in v]
                           for k, v in d["device"].items()},
                   host=[tuple(e) for e in d["host"]])


def leaves(evs: list) -> list:
    """Drop the operations that contain later ones (control flow around
    its body); what is left does not nest."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or not (evs[i + 1][1] < e[2]
                                         and evs[i + 1][2] <= e[2])]


def load_xplane(path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return Events(device=device, host=host)


def load_json(path: str) -> Events:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return Events.from_json(json.load(f))


# ----------------------------------------------------------------------
# Summary
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    window_s: float                 # length of the traced window
    busy_s: float                   # per chip, averaged
    compute_s: float                # non-collective operations, per chip
    collective_s: float             # collective operations, per chip
    exposed_collective_s: float     # collectives under no other op, per chip
    chips: int
    device_ops: list                # [(op kind, seconds per chip)]
    idle_gaps: list                 # [(host annotation, seconds per chip)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [list(x) for x in self.device_ops[:top]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:top]]}


def _label_gaps(gaps, host) -> dict:
    """Seconds of idle device time by the host annotation that overlaps
    each gap most (the innermost phase, not the window)."""
    phases = sorted((a, b, n) for n, a, b in host if n != WINDOW)
    out: dict = defaultdict(float)
    i = 0
    for ga, gb in gaps:
        while i < len(phases) and phases[i][1] <= ga:
            i += 1
        best, best_overlap = "outside bench phases", 0.0
        k = i
        while k < len(phases) and phases[k][0] < gb:
            a, b, n = phases[k]
            ov = min(b, gb) - max(a, ga)
            if ov > best_overlap:
                best, best_overlap = n, ov
            k += 1
        out[best] += (gb - ga)
    return out


def summarize(ev: Events, n_chips: Optional[int] = None) -> Summary:
    windows = [(a, b) for n, a, b in ev.host if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    chips = sorted(ev.device)
    if n_chips is not None:
        chips = chips[:n_chips]
    if not chips:
        raise ValueError("the trace holds no TPU operations")
    busy = compute = coll = exposed = 0.0
    ops: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    for c in chips:
        evs = leaves([(n, a, b) for n, a, b in ev.device[c]
                      if b > lo and a < hi])
        coll_iv = union(clip([(a, b) for n, a, b in evs if is_collective(n)],
                             lo, hi))
        comp_iv = union(clip([(a, b) for n, a, b in evs
                              if not is_collective(n)], lo, hi))
        all_iv = union(coll_iv + comp_iv)
        busy += length(all_iv)
        compute += length(comp_iv)
        coll += length(coll_iv)
        exposed += length(subtract(coll_iv, comp_iv))
        for n, a, b in evs:
            ops[op_kind(n)] += min(b, hi) - max(a, lo)
        idle = subtract([(lo, hi)], all_iv)
        for k, v in _label_gaps(idle, ev.host).items():
            gaps[k] += v
    k = len(chips)
    ns = 1e-9
    return Summary(
        window_s=(hi - lo) * ns, busy_s=busy / k * ns,
        compute_s=compute / k * ns, collective_s=coll / k * ns,
        exposed_collective_s=exposed / k * ns, chips=k,
        device_ops=sorted(((n, v / k * ns) for n, v in ops.items()),
                          key=lambda x: -x[1]),
        idle_gaps=sorted(((n, v / k * ns) for n, v in gaps.items()),
                         key=lambda x: -x[1]))


def reduce_dir(trace_root: str, n_chips: Optional[int] = None) -> Summary:
    """Summarize the one ``*.xplane.pb`` under ``trace_root``."""
    paths = glob.glob(os.path.join(trace_root, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane under {trace_root}, "
                                f"found {len(paths)}")
    return summarize(load_xplane(paths[0]), n_chips)
