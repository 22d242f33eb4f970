"""Completeness check: jaxpr census vs compiled-HLO census.

The trace-time hook sees every *explicit* collective; the SPMD partitioner
then inserts more (resharding all-gathers, gradient all-reduces implied by
pjit shardings).  Those are this world's indirect jumps — invisible to
static analysis of the source program.  This module diffs the two censuses
so a deployment can assert "all collectives accounted for", and pins any
partitioner-inserted site by reporting the HLO op for manual conversion to
an explicit shard_map collective (the config-file fix of §3.3).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute")

# op keyword at its definition site: "... = f32[4,8]{1,0} all-reduce(...)";
# operand *references* are "%all-reduce.5" (no following paren) and never match
_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def hlo_collective_census(hlo_text: str) -> Dict[str, int]:
    """Count collective ops in (optimized) HLO text, by kind."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        m = _OP_RE.search(line.split("=", 1)[1])
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


_JAXPR_TO_HLO = {
    "psum": "all-reduce", "psum_invariant": "all-reduce",
    "pmax": "all-reduce", "pmin": "all-reduce",
    "all_gather": "all-gather", "all_gather_invariant": "all-gather",
    "reduce_scatter": "reduce-scatter", "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
}


@dataclasses.dataclass
class CompletenessReport:
    jaxpr_counts: Dict[str, int]
    hlo_counts: Dict[str, int]
    partitioner_inserted: Dict[str, int]  # HLO kind -> excess count

    @property
    def fully_hooked(self) -> bool:
        return not any(v > 0 for v in self.partitioner_inserted.values())


def completeness_report(jaxpr_census: Dict, hlo_text: str) -> CompletenessReport:
    """Diff explicit (hookable) sites against the compiled collective mix.

    HLO counts can legitimately be *lower* (fusion/elision) — only an excess
    marks partitioner-inserted, un-hookable sites.
    """
    hlo = hlo_collective_census(hlo_text)
    jx: Dict[str, int] = {}
    for prim, n in jaxpr_census.get("by_primitive", {}).items():
        kind = _JAXPR_TO_HLO.get(prim)
        if kind:
            jx[kind] = jx.get(kind, 0) + n
    excess = {k: max(0, hlo.get(k, 0) - jx.get(k, 0))
              for k in set(hlo) | set(jx)}
    return CompletenessReport(jaxpr_counts=jx, hlo_counts=hlo,
                              partitioner_inserted=excess)
