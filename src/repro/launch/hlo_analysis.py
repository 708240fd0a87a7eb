"""Trip-count-aware HLO cost analysis.

``compiled.cost_analysis()`` counts a ``while`` (lax.scan) body ONCE —
useless for scanned-layer transformers.  This module parses the optimized
HLO text, builds the computation call graph (entry → while bodies × trip
count → fusions), and accumulates:

- **flops**: 2 · prod(result dims) · prod(contracting dims) for every
  ``dot`` (dots are ≳95 % of model FLOPs; elementwise ignored), scaled by the
  enclosing computation's execution multiplier;
- **hbm bytes**: operand + result bytes of every *top-level* op in non-fusion
  computations (fusion internals stay on-chip; the fusion call site's own
  operands/results are the HBM traffic), scaled likewise;
- **collective bytes**: per collective type, scaled likewise.

Trip counts come from the loop condition's ``compare(iv, constant(N))``
pattern that lax.scan emits.  CPU-backend fusion boundaries differ from TPU
ones — recorded as an approximation in EXPERIMENTS.md.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SKIP_OPS = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "copy-start", "copy-done", "after-all", "partition-id")


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def split_computations(text: str):
    """name -> list of op lines, ``/*...*/`` comments stripped (tuple types
    carry ``/*index=5*/`` markers); also returns entry name.  Reads both the
    optimized text (``%name (params) -> type {`` headers) and the lowered,
    pre-optimization text (``name {``, ``ENTRY name {``)."""
    comps = {}
    entry = None
    cur = None
    for line in text.splitlines():
        s = _COMMENT_RE.sub("", line).strip()
        m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\)\s*->\s*.+)?\{\s*$",
                     s)
        if m and not s.startswith("ROOT"):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if s == "}":
            cur = None
            continue
        if cur is not None and "=" in s:
            comps[cur].append(s)
    return comps, entry


_LHS_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")


def build_symtab(comps) -> dict:
    """%var -> (dtype, dims) from definition lines (non-tuple results only)."""
    sym = {}
    for lines in comps.values():
        for line in lines:
            m = _LHS_RE.match(line)
            if m:
                sym[m.group(1)] = (m.group(2), m.group(3))
    return sym


_OPERAND_RE = re.compile(r"(?:^|\s)%?([A-Za-z_][\w.\-]*)\s*$")


def _operand_names(line: str):
    rhs = line.split("=", 1)[1]
    if "(" not in rhs:
        return []
    call = rhs[rhs.index("("):]
    # cut at the closing paren of the call (operands only, not attributes)
    depth = 0
    end = len(call)
    for i, ch in enumerate(call):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    # operands are comma-separated at bracket depth 0; each ends in its name,
    # with a ``%`` in optimized text and without one in lowered text
    names, depth, start = [], 0, 1
    for i, ch in enumerate(call[:end + 1]):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if (ch == "," and depth == 1) or i == end:
            m = _OPERAND_RE.search(call[start:i])
            if m:
                names.append(m.group(1))
            start = i + 1
    return names


def _called(line: str):
    """(kind, [computation names]) referenced by this op line."""
    out = []
    m = re.search(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)", line)
    if m:
        return "while", [m.group(1), m.group(2)]
    m = re.search(r"calls=%?([\w.\-]+)", line)
    if m:
        return "fusion", [m.group(1)]
    m = re.search(r"to_apply=%?([\w.\-]+)", line)
    if m:
        return "call", [m.group(1)]
    m = re.search(r"true_computation=%?([\w.\-]+),\s*false_computation=%?([\w.\-]+)",
                  line)
    if m:
        return "cond", [m.group(1), m.group(2)]
    return None, []


def _trip_count(cond_lines) -> int:
    """lax.scan cond: compare(iv, constant(N)) LT — take that N."""
    consts = []
    for line in cond_lines:
        if "compare(" in line and "direction=LT" in line:
            for c in re.findall(r"constant\((\d+)\)", line):
                consts.append(int(c))
    if consts:
        return max(consts)
    # fall back: any s32 constant in cond
    for line in cond_lines:
        for c in re.findall(r"constant\((\d+)\)", line):
            consts.append(int(c))
    return max(consts) if consts else 1


def _line_types(line: str, sym: dict):
    """(result_type, operand_types) resolved through the symbol table."""
    m = _LHS_RE.match(line)
    result = (m.group(2), m.group(3)) if m else None
    otypes = []
    for name in _operand_names(line):
        if name in sym:
            otypes.append(sym[name])
    return result, otypes


def _dot_flops(line: str, sym: dict) -> float:
    result, otypes = _line_types(line, sym)
    if result is None:
        return 0.0
    res_elems = _shape_elems(result[1])
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    if not m or not otypes:
        return 2.0 * res_elems  # unknown; minimal
    cdims = [int(d) for d in m.group(1).split(",") if d]
    lhs_dims = [int(d) for d in otypes[0][1].split(",") if d]
    k = 1
    for c in cdims:
        if c < len(lhs_dims):
            k *= lhs_dims[c]
    return 2.0 * res_elems * k


def analyze_hlo(text: str) -> dict:
    comps, entry = split_computations(text)
    if entry is None:
        entry = next(iter(comps), None)
    sym = build_symtab(comps)
    # which computations are fusion bodies (called via calls=)
    fusion_comps = set()
    for lines in comps.values():
        for line in lines:
            kind, names = _called(line)
            if kind == "fusion":
                fusion_comps.update(names)

    # Build call edges (caller, callee, per-call multiplier), then propagate
    # in topological order — shared (deduped) fusion computations may be
    # reached from several bodies with different multipliers.
    edges = []
    for c, lines in comps.items():
        for line in lines:
            kind, names = _called(line)
            if not names:
                continue
            if kind == "while":
                trips = _trip_count(comps.get(names[0], []))
                for n in names:
                    edges.append((c, n, float(trips)))
            else:
                for n in names:
                    edges.append((c, n, 1.0))
    indeg = defaultdict(int)
    out_edges = defaultdict(list)
    for a, b, t in edges:
        indeg[b] += 1
        out_edges[a].append((b, t))
    mult = defaultdict(float)
    mult[entry] = 1.0
    queue = [c for c in comps if indeg[c] == 0]
    topo_seen = 0
    while queue:
        c = queue.pop()
        topo_seen += 1
        for b, t in out_edges[c]:
            mult[b] += mult[c] * t
            indeg[b] -= 1
            if indeg[b] == 0:
                queue.append(b)

    flops = 0.0
    hbm = 0.0
    dot_bytes = 0.0     # operands+results of dots only (TPU-fusion-friendly
                        # lower-bound HBM traffic; raw `hbm` is the upper
                        # bound — CPU fusion boundaries overcount)
    coll_bytes = {k: 0.0 for k in _COLLECTIVES}
    coll_counts = {k: 0.0 for k in _COLLECTIVES}
    for cname, lines in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = cname in fusion_comps
        for line in lines:
            opm = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?\s([a-z][\w\-]*)\(",
                           line)
            opname = opm.group(1) if opm else ""
            if opname in ("dot", "convolution"):
                flops += m * _dot_flops(line, sym)
                r, o = _line_types(line, sym)
                db = sum(_shape_bytes(dt, dims) for dt, dims in o)
                if r:
                    db += _shape_bytes(r[0], r[1])
                dot_bytes += m * db
            if in_fusion:
                continue
            if not opname or opname in _SKIP_OPS or opname in (
                    "while", "conditional", "call"):
                continue
            result, otypes = _line_types(line, sym)
            nbytes = sum(_shape_bytes(dt, dims) for dt, dims in otypes)
            if result:
                nbytes += _shape_bytes(result[0], result[1])
            hbm += m * nbytes
            for ck in _COLLECTIVES:
                if opname == ck or opname == ck + "-start":
                    ob = sum(_shape_bytes(dt, dims) for dt, dims in otypes)
                    if ob == 0 and result:
                        ob = _shape_bytes(result[0], result[1])
                    coll_bytes[ck] += m * ob
                    coll_counts[ck] += m
    return {
        "flops": flops,
        "hbm_bytes": hbm,
        "dot_bytes": dot_bytes,
        "collective_bytes": coll_bytes,
        "collective_counts": coll_counts,
        "collective_total": sum(coll_bytes.values()),
    }


# ----------------------------------------------------------------------
# Compute/communication overlap analysis
# ----------------------------------------------------------------------

_COMPUTE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "sqrt",
    "rsqrt", "abs", "negate", "exponential", "tanh", "power", "select",
    "dot", "convolution", "reduce", "fusion", "scatter", "gather", "sine"))

_NAME_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OPNAME_RE = re.compile(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?\s([a-z][\w\-]*)\(")


def permute_overlap_stats(text: str,
                          ops: tuple = ("collective-permute",)) -> dict:
    """How much compute can run concurrently with the tracked collectives.

    ``ops`` names the collective op families to track (HLO opcode prefixes:
    ``collective-permute`` by default; pass e.g. ``("all-reduce",)`` or
    ``("all-to-all",)`` for the LM paths' combines).  Three complementary
    signals, so the check works on any backend:

    - **async pairs** (TPU/GPU backends split collectives into
      ``<op>-start``/``<op>-done``): for every pair, the number of compute
      ops scheduled between start and done — nonzero gaps mean the
      latency-hiding scheduler actually placed work inside the transfer.
    - **dependency classes** (all backends, incl. CPU's synchronous
      collectives): every op in a collective-bearing computation is
      *upstream* (feeds a collective), *downstream* (consumes one), or
      *overlappable* (neither — free to execute while the wire is busy).
      The overlapped halo schedule exists precisely to maximize that third
      class; the fused step funnels nearly all element work downstream.
    - **independent pairs**: the number of unordered pairs of tracked
      collectives with no dependency path between them — the signal for
      chunk-level decoupling (the fused TP reduce is ONE all-reduce, hence
      zero pairs; the chunk-overlapped one is N mutually independent
      reduces, hence N·(N−1)/2 pairs the scheduler may run concurrently).
    """
    comps, _ = split_computations(text)
    stats = {"sync_permutes": 0, "async_pairs": 0, "pair_gaps": [],
             "overlappable_compute": 0, "upstream_compute": 0,
             "downstream_compute": 0, "n_collectives": 0,
             "independent_pairs": 0}
    for lines in comps.values():
        op_rows = []   # (name, opname, operands)
        for line in lines:
            nm = _NAME_RE.match(line)
            opm = _OPNAME_RE.match(line)
            if not nm or not opm:
                continue
            op_rows.append((nm.group(1), opm.group(1), _operand_names(line)))
        permutes = [i for i, (_, op, _o) in enumerate(op_rows)
                    if any(op == p or op == p + "-start" or op == p + "-done"
                           for p in ops)]
        if not permutes:
            continue
        stats["sync_permutes"] += sum(
            1 for i in permutes if op_rows[i][1] in ops)
        # async start/done pairs and the compute scheduled between them
        starts = {op_rows[i][0]: i for i in permutes
                  if op_rows[i][1].endswith("-start")}
        for i in permutes:
            if not op_rows[i][1].endswith("-done"):
                continue
            for operand in op_rows[i][2]:
                if operand in starts:
                    j = starts[operand]
                    gap = sum(1 for k in range(j + 1, i)
                              if op_rows[k][1] in _COMPUTE_OPS)
                    stats["async_pairs"] += 1
                    stats["pair_gaps"].append(gap)
                    break
        # dependency classes (SSA def order makes single passes sufficient)
        defs = {name: k for k, (name, _, _) in enumerate(op_rows)}
        downstream = {op_rows[i][0] for i in permutes}
        for name, _op, operands in op_rows:
            if any(o in downstream for o in operands):
                downstream.add(name)
        upstream = set()
        frontier = [o for i in permutes for o in op_rows[i][2]]
        while frontier:
            n = frontier.pop()
            if n in upstream or n not in defs:
                continue
            upstream.add(n)
            frontier.extend(op_rows[defs[n]][2])
        for name, op, _operands in op_rows:
            if op not in _COMPUTE_OPS:
                continue
            if name in downstream:
                stats["downstream_compute"] += 1
            elif name in upstream:
                stats["upstream_compute"] += 1
            else:
                stats["overlappable_compute"] += 1
        # independent collective pairs: one logical collective per sync op
        # or -start op (the matching -done is the same logical transfer).
        coll_idx = [i for i in permutes
                    if not op_rows[i][1].endswith("-done")]
        ids = {i: b for b, i in enumerate(coll_idx)}
        masks: dict[str, int] = {}   # name -> bitmask of ancestor collectives
        anc = {}                     # collective bit -> ancestor mask
        for k, (name, _op, operands) in enumerate(op_rows):
            m = 0
            for o in operands:
                m |= masks.get(o, 0)
            if k in ids:
                anc[ids[k]] = m
                m |= 1 << ids[k]
            masks[name] = m
        n_coll = len(coll_idx)
        stats["n_collectives"] += n_coll
        for a in range(n_coll):
            for b in range(a + 1, n_coll):
                if not (anc[b] >> a) & 1 and not (anc[a] >> b) & 1:
                    stats["independent_pairs"] += 1
    return stats
