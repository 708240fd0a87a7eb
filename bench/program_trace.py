"""The program's own spans (``repro.obs.trace``), for the readers of the
``program_span`` metrics.

The harness imports the per-layer readers only for a traced run, and before
the entry's set-up.  Those readers import this module, and importing it
turns the program's tracing on (unless it is on already): set-up and every
segment of the window then leave their spans in the program's ring buffer,
on the profiler's host clock, and in the profiler's host plane.  Untraced
runs never import it and run with the program's tracing off.
"""
from __future__ import annotations

from typing import Optional

from repro.obs import trace as obs_trace

if not obs_trace.enabled():
    obs_trace.configure("1")


def spans(name: str) -> list[dict]:
    """The program's complete spans named ``name``, oldest first."""
    return [e for e in obs_trace.events()
            if e["ph"] == "X" and e["name"] == name]


def spans_of(*cats: str) -> list[dict]:
    """The program's complete spans of the given categories, oldest
    first."""
    return [e for e in obs_trace.events()
            if e["ph"] == "X" and e["cat"] in cats]


def last_seconds(name: str) -> Optional[float]:
    """Length of the latest span named ``name``, in s; None if there is
    none (a program without that span)."""
    found = spans(name)
    return found[-1]["dur"] * 1e-6 if found else None
