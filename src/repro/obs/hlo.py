"""HLO cost accounting: per-program collective bytes / temp memory / flops.

PR 3 and PR 5 proved the sharded queries' collective-byte and temp-memory
formulas against the compiled HLO, but only inside ``bench_shard`` — the
numbers vanished the moment the bench exited.  The accountant here makes
them an always-on metric: the first time a (kind, shapes, mesh) program
signature is seen, the caller's ``compile_fn`` lowers and compiles the
very jitted program the query just ran, and the result is distilled into
one small dict

    {"collective_bytes": int, "collectives": {op: bytes, ...},
     "temp_bytes": int | None, "peak_bytes": int | None,
     "flops": float | None}

cached (by default process-wide, shared across accountant instances — a
re-created service must not recompile programs XLA already built this
process) and attached to every subsequent query's trace record for free.

The HLO text parser is also what ``launch/dryrun.py`` and
``benchmarks/bench_shard.py`` read their collective bytes with; this module
sets no XLA flag, so a serving process can import it safely.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional

__all__ = ["HLOCostAccountant", "account_jit", "analyze_compiled",
           "parse_collective_bytes"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes of every collective op in a per-device HLO dump."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
        out["total"] = out.get("total", 0) + nbytes
        out["count"] = out.get("count", 0) + 1
    return out


def analyze_compiled(compiled) -> dict:
    """Distill one jax ``Compiled`` into the accountant's cost dict.

    Every probe is individually guarded: backends without memory stats or
    cost analysis degrade to ``None`` fields instead of breaking serving.
    """
    cost = {"collective_bytes": 0, "collectives": {},
            "temp_bytes": None, "peak_bytes": None, "flops": None}
    try:
        coll = parse_collective_bytes(compiled.as_text())
        cost["collective_bytes"] = coll.pop("total", 0)
        coll.pop("count", None)
        cost["collectives"] = coll
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        cost["temp_bytes"] = int(ma.temp_size_in_bytes)
        cost["peak_bytes"] = (int(ma.temp_size_in_bytes)
                              + int(ma.argument_size_in_bytes)
                              + int(ma.output_size_in_bytes))
    except Exception:
        pass
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = ca.get("flops")
        if flops is not None:
            cost["flops"] = float(flops)
    except Exception:
        pass
    return cost


class HLOCostAccountant:
    """Cache of program-signature -> cost dict.

    ``shared=True`` (default) keys into one process-wide cache: compiled
    analysis depends only on the program signature, and re-lowering is the
    expensive step being amortized.  ``last`` always holds the cost of the
    most recent :meth:`account` call so host wrappers can deposit it and
    their caller (the service) can pick it up without widening return
    types.
    """

    _SHARED: Dict[tuple, dict] = {}

    def __init__(self, shared: bool = True):
        self._cache = HLOCostAccountant._SHARED if shared else {}
        self.last: Optional[dict] = None

    def account(self, key: tuple, compile_fn: Callable[[], object]) -> dict:
        cost = self._cache.get(key)
        if cost is None:
            try:
                cost = analyze_compiled(compile_fn())
            except Exception:  # never let accounting break the query
                cost = {"collective_bytes": 0, "collectives": {},
                        "temp_bytes": None, "peak_bytes": None, "flops": None}
            self._cache[key] = cost
        self.last = cost
        return cost

    def snapshot(self) -> dict:
        return {repr(k): v for k, v in self._cache.items()}


def account_jit(accountant: Optional[HLOCostAccountant], key: tuple,
                fn, *args) -> Optional[dict]:
    """Deposit the cost of one jitted program with the accountant.

    The local-engine twin of ``shard.queries._account``: ``fn`` is the
    ``jax.jit``-wrapped callable the caller just ran with ``args``; the
    first time ``key`` (the program signature — kind/mode plus the
    shape-determining dims) is seen, the program is re-lowered and
    compiled once for ``cost_analysis``, then every later call is a cache
    hit that only refreshes ``accountant.last``.  No-op without an
    accountant, so the untelemetered path pays one ``None`` check.
    """
    if accountant is None:
        return None
    return accountant.account(key, lambda: fn.lower(*args).compile())
