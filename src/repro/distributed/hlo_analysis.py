"""Collective-traffic analysis from lowered/compiled HLO text.

The roofline's collective term (task spec) is not in cost_analysis(): we
parse the HLO for all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute and sum operand sizes. The same parser powers the
paper-validation benchmark that *measures* ROUTE vs FETCH wire bytes on our
own compiled programs (§2.1/§5.2) — the byte asymmetry read off real HLO.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

import numpy as np


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "fp8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

# e.g.  %all-gather.3 = bf16[16,128,576]{2,1,0} all-gather(...)
#       ROOT %r = (f32[8,4]{...}, f32[8]{...}) all-to-all(...)
_INSTR_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]+)\[(?P<dims>[0-9,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """'bf16[16,128]{1,0}' or '(f32[8], f32[8,4])' -> total bytes."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue          # e.g. token[] / opaque
        dims = m.group("dims")
        n = int(np.prod([int(d) for d in dims.split(",")])) if dims else 1
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    """Byte totals per collective kind, from one HLO module.

    result_bytes: sum of result-shape sizes (the task-spec "operand sizes" —
        for these ops result size == the redistributed payload size; for
        all-gather the result is the post-gather size).
    wire_bytes: ring-model bytes actually crossing links per device:
        all-gather / reduce-scatter / all-to-all: B * (n-1)/n
        all-reduce: 2B * (n-1)/n ;  collective-permute: B.
    """
    counts: Dict[str, int]
    result_bytes: Dict[str, int]
    wire_bytes: float

    @property
    def total_result_bytes(self) -> int:
        return sum(self.result_bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str, n_devices: int = 1) -> CollectiveStats:
    counts: Dict[str, int] = defaultdict(int)
    rbytes: Dict[str, int] = defaultdict(int)
    wire = 0.0
    seen_start_ids = set()
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        # skip -done halves of async pairs (the -start carries the shape)
        if re.search(r"(all-gather|all-reduce|collective-permute|all-to-all)"
                     r"-done", line):
            continue
        op = m.group("op")
        b = shape_bytes(m.group("shape"))
        counts[op] += 1
        rbytes[op] += b
        frac = (n_devices - 1) / max(1, n_devices)
        if op == "all-reduce":
            wire += 2 * b * frac
        elif op == "collective-permute":
            wire += b
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            wire += b * frac
    return CollectiveStats(dict(counts), dict(rbytes), wire)


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}\(", hlo_text))


def flops_and_bytes(cost_analysis: Optional[dict]) -> tuple:
    """Extract (flops, bytes accessed) from compiled.cost_analysis()."""
    if not cost_analysis:
        return 0.0, 0.0
    ca = cost_analysis
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))
