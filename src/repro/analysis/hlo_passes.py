"""Passes 1-3 — compiled-HLO text analysis (pure regex, no jax).

Built on repro.utils.hlo's parsing machinery (shape-bytes, computation
splitting, trip-count-weighted collective accounting). Each pass takes
compiled HLO text (`jit(f).lower(*args).compile().as_text()`) and
returns Findings anchored at the `metadata={source_file= source_line=}`
XLA carries for every instruction, so a gate failure points at the
Python line that built the bad op. Newer jax writes the location as
`stack_frame_id=N` into the module's FileNames / FileLocations /
StackFrames tables instead; `_inline_stack_frames` rewrites that form
into the inline one before a pass reads it.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from repro.analysis.findings import Finding
from repro.utils.hlo import (COLLECTIVES, _find_entry, _shape_bytes,
                             _split_computations, collective_bytes)

#: Constants smaller than this are assumed deliberate (iota tables,
#: interval clamps, gbdt thresholds); a closure-captured index shard is
#: megabytes. 64 KiB sits two orders of magnitude between the classes.
CONST_BYTES_THRESHOLD = 64 * 1024

_CONST_RE = re.compile(
    r"=\s*([a-z0-9]+\[[\d,]*\]\S*)\s+constant\(")
_PARAM_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\]\S*\s+parameter\((\d+)\)")
_META_FILE_RE = re.compile(r'source_file="([^"]+)"')
_META_LINE_RE = re.compile(r"source_line=(\d+)")
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"^(\d+) (.*)$")
_DEF_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _inline_stack_frames(hlo: str) -> str:
    """Replace every `stack_frame_id=N` with the innermost frame's
    `source_file="..." source_line=L` from the module's tables (a no-op
    on HLO that already carries inline locations)."""
    tables: Dict[str, Dict[int, str]] = {}
    cur: Optional[Dict[int, str]] = None
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = _TABLE_ROW_RE.match(line) if cur is not None else None
        if m:
            cur[int(m.group(1))] = m.group(2)
        else:
            cur = None
    frames = tables.get("StackFrames")
    if not frames:
        return hlo
    files = tables.get("FileNames", {})
    locs = tables.get("FileLocations", {})

    def field(row: str, key: str) -> int:
        return int(re.search(r"\b" + key + r"=(\d+)", row).group(1))

    def inline(m: "re.Match[str]") -> str:
        loc = locs.get(field(frames[int(m.group(1))], "file_location_id"))
        if loc is None:
            return m.group(0)
        path = files.get(field(loc, "file_name_id"), '""')
        return f"source_file={path} source_line={field(loc, 'line')}"

    return _FRAME_ID_RE.sub(inline, hlo)


def _source_loc(line: str) -> Tuple[Optional[str], Optional[int]]:
    fm = _META_FILE_RE.search(line)
    lm = _META_LINE_RE.search(line)
    return (fm.group(1) if fm else None,
            int(lm.group(1)) if lm else None)


def replicated_constants(entry: str, hlo: str,
                         threshold: int = CONST_BYTES_THRESHOLD
                         ) -> List[Finding]:
    """Pass 1: array constants above `threshold` baked into the program.

    A jax.Array captured by closure instead of passed as an argument
    compiles to a `constant(...)` instruction — replicated onto every
    device, silently undoing dist.place_index (the PR 3 bug class; see
    the Engine protocol docstring). Everything index-sized must cross
    the jit boundary as an argument.
    """
    out: List[Finding] = []
    hlo = _inline_stack_frames(hlo)
    for line in hlo.splitlines():
        m = _CONST_RE.search(line)
        if not m:
            continue
        nbytes = _shape_bytes(m.group(1))
        if nbytes < threshold:
            continue
        f, ln = _source_loc(line)
        if f is None:
            # jax 0.9 emits captured constants without op metadata:
            # anchor at the op that consumes the constant instead.
            nm = _DEF_NAME_RE.match(line)
            f, ln = _use_loc(hlo, nm.group(1) if nm else "")
        out.append(Finding(
            "replicated-constant", entry,
            f"{m.group(1)} ({nbytes} bytes) baked into the compiled "
            f"program as a constant — a closure-captured array "
            f"replicates onto every device; pass it as a jit argument",
            f, ln))
    return out


def _def_map(lines: List[str]) -> Dict[str, str]:
    defs: Dict[str, str] = {}
    for line in lines:
        m = _DEF_NAME_RE.match(line)
        if m:
            defs[m.group(1)] = line
    return defs


def _operands(line: str) -> List[str]:
    # operand list = everything after the opcode's '('; the leading
    # `%name = type` is cut off by splitting at the first '('
    return _OPERAND_RE.findall(line.split("(", 1)[-1])


def unpartitionable_topk(entry: str, hlo: str, *, max_hops: int = 6
                         ) -> List[Finding]:
    """Pass 2: TopK/sort custom-calls fed by a dim-0 all-gather.

    When GSPMD cannot partition a TopK custom-call whose operand
    carries a sharded dim, it materialises the full operand with an
    `all-gather` over the sharded (leading) dim right in front of it —
    the PR 6 bug class (`pin_merge=False`). Deliberate [B, k] merges
    gather dim 1 inside the shard_map and never match. The back-walk
    is bounded to `max_hops` def-use hops within one computation, so a
    dim-0 gather far upstream of an unrelated sort stays quiet.
    """
    out: List[Finding] = []
    for lines in _split_computations(_inline_stack_frames(hlo)).values():
        defs = _def_map(lines)
        gathers = {name for name, line in defs.items()
                   if ("all-gather" in line.split("=", 1)[-1][:64]
                       and "dimensions={0}" in line)}
        if not gathers:
            continue
        for name, line in defs.items():
            body = line.split("=", 1)[-1]
            is_topk = 'custom_call_target="TopK"' in body
            is_sort = re.search(r"\bsort(?:\.\d+)?\(", body) is not None
            if not (is_topk or is_sort):
                continue
            frontier = _operands(line)
            seen = set(frontier)
            for _ in range(max_hops):
                hit = [n for n in frontier if n in gathers]
                if hit:
                    f, ln = _source_loc(line)
                    out.append(Finding(
                        "unpartitionable-topk", entry,
                        f"{'TopK custom-call' if is_topk else 'sort'} "
                        f"fed by a dim-0 all-gather (%{hit[0]}): the "
                        f"merge's operand carries a sharded dim GSPMD "
                        f"cannot partition — keep the top-k inside the "
                        f"shard_map (pin_merge)",
                        f, ln))
                    break
                nxt = []
                for n in frontier:
                    for op in _operands(defs.get(n, "")):
                        if op not in seen:
                            seen.add(op)
                            nxt.append(op)
                frontier = nxt
                if not frontier:
                    break
    return out


def collective_n_independence(entry: str, hlo_small: str, hlo_large: str,
                              *, rel_tol: float = 1e-6) -> List[Finding]:
    """Pass 3: per-collective bytes must match across two index sizes.

    The sharded search steps move [B, k] candidate merges and [B, M]
    frontiers across shards — batch- and k-sized, never index-sized.
    If any collective kind's trip-count-weighted bytes differ between
    the small and large builds of the same entry, index rows are
    crossing the interconnect and the scan will not scale out.
    """
    small = collective_bytes(hlo_small)
    large = collective_bytes(hlo_large)
    out: List[Finding] = []
    for kind in COLLECTIVES:
        a, b = small.get(kind, 0.0), large.get(kind, 0.0)
        if abs(a - b) > rel_tol * max(a, b, 1.0):
            out.append(Finding(
                "collective-n-independence", entry,
                f"{kind} bytes scale with the index size "
                f"({a:.0f} -> {b:.0f} between the small and large "
                f"builds): collectives must move candidates, not "
                f"index rows"))
    return out


def _entry_params(hlo: str) -> Dict[int, Tuple[str, str, Tuple[int, ...]]]:
    """parameter number -> (instr name, dtype, dims) for the ENTRY
    computation's `parameter(n)` instructions (post-SPMD: per-device
    shapes)."""
    lines = _split_computations(hlo).get(_find_entry(hlo), [])
    params: Dict[int, Tuple[str, str, Tuple[int, ...]]] = {}
    for line in lines:
        m = _PARAM_RE.search(line)
        if not m:
            continue
        nm = _DEF_NAME_RE.match(line)
        dims = (tuple(int(x) for x in m.group(2).split(","))
                if m.group(2) else ())
        params[int(m.group(3))] = (nm.group(1) if nm else "",
                                   m.group(1), dims)
    return params


def _use_loc(hlo: str, name: str) -> Tuple[Optional[str], Optional[int]]:
    """Source anchor for a parameter or constant: the first instruction
    CONSUMING `%name` that carries metadata. Parameter (and captured
    constant) instructions have no source location — the array was
    built in Python, not by an op — so the finding points at the code
    that reads it."""
    if not name:
        return None, None
    pat = re.compile(r"%" + re.escape(name) + r"\b")
    for line in _inline_stack_frames(hlo).splitlines():
        if " parameter(" in line:
            continue
        if not pat.search(line.split("=", 1)[-1]):
            continue
        f, ln = _source_loc(line)
        if f:
            return f, ln
    return None, None


def _nelems(dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def resident_bytes(entry: str, hlo_small: str, hlo_large: str,
                   *, dim: int = 16) -> List[Finding]:
    """Pass 6: SQ8-resident entries must hold vector payloads as int8.

    Entries registered `resident_sq8=True` serve the compact residency
    format (index.residency): every N-scaled vector-payload parameter —
    one whose per-device element count GROWS between the small and
    large builds and whose trailing dim is the vector dim — must enter
    the compiled program at int8 width, and at least one such int8
    payload must exist. A float payload that scales with N means the
    manifest (or an engine refactor behind it) silently regressed to
    f32 residency: the program still computes the right answer, at
    4-4.4x the device bytes the residency contract budgets for.
    Batch-sized state (q, top-k buffers) and non-payload index arrays
    (ids, sqnorm, neighbor lists, the hashed visited filter) never
    match the payload test and stay unconstrained.
    """
    ps = _entry_params(hlo_small)
    pl = _entry_params(hlo_large)
    out: List[Finding] = []
    has_sq8 = False
    for num, (name, dt, dims) in sorted(ps.items()):
        other = pl.get(num)
        if other is None:
            continue
        _, dt_l, dims_l = other
        if dt != dt_l or len(dims) != len(dims_l):
            continue
        if _nelems(dims_l) <= _nelems(dims):
            continue                       # not N-scaled
        if len(dims) < 2 or dims[-1] != dim:
            continue                       # not a vector payload
        if dt == "s8":
            has_sq8 = True
            continue
        if dt not in ("f32", "f64", "bf16", "f16"):
            continue
        f, ln = _use_loc(hlo_large, name)
        out.append(Finding(
            "resident-bytes", entry,
            f"N-scaled vector payload parameter({num}) is device-"
            f"resident as {dt}[{','.join(map(str, dims_l))}]: "
            f"SQ8-resident entries must search int8 codes "
            f"(index.residency.quantize_*) and re-rank the final "
            f"top-k from the f32 store",
            f, ln))
    if not has_sq8:
        out.append(Finding(
            "resident-bytes", entry,
            "no N-scaled int8 vector-payload parameter reaches the "
            "program: the entry is registered resident_sq8 but is "
            "not serving the SQ8 view"))
    return out
