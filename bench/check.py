"""The comparison that decides ``correct``: what the timed path produced,
against the plain host reference.

Each number compared has its limit here, with the readings it was set
from in ``PERF.md`` ("Correctness limits").  ``value <= limit`` passes.

  * ``kinds_unanswered`` (query cells): query kinds whose clients got no
    reply in the window, so that nothing of theirs could be compared.
  * ``wrong_entries`` (query cells): BFS hop counts, SSSP distances and BC
    levels that differ from the reference, plus replies not ``ok``, plus
    sharded replies whose shards did not all compute from one version
    (``agree`` false).  Exact, so the limit is 0.
  * ``bc_rel_err`` (query cells): the largest gap of a BC path count or
    dependency from the reference, relative to ``max(|reference|, 1)``.
    float32 sums in another order than the reference's float64.
  * ``edges_wrong`` / ``alive_wrong`` / ``versions_wrong`` (update cells):
    committed edges missing, extra or with another weight, vertices whose
    liveness differs, and commits the ring lacks or has in excess.  Exact.
"""
from __future__ import annotations

import numpy as np

from .reference import HostGraph, ref_brandes, ref_hops, ref_sssp

LIMITS = {
    "kinds_unanswered": 0,
    "wrong_entries": 0,
    "bc_rel_err": 1e-3,
    "edges_wrong": 0,
    "alive_wrong": 0,
    "versions_wrong": 0,
}


def compare_reply(kind: str, g: HostGraph, src: int, res: dict,
                  brandes=ref_brandes):
    """``(wrong entries, BC relative error)`` of one host-side reply;
    ``brandes`` is the reference BC (the control passes its own)."""
    wrong = 0 if bool(res["ok"]) else 1
    if "agree" in res and not bool(res["agree"]):
        wrong += 1
    if kind == "bfs":
        wrong += int(np.sum(np.asarray(res["dist"], np.int64)
                            != ref_hops(g, src)))
        return wrong, 0.0
    if kind == "sssp":
        wrong += int(np.sum(np.asarray(res["dist"], np.float64)
                            != ref_sssp(g, src)))
        return wrong, 0.0
    level, sigma, delta = brandes(g, src)
    wrong += int(np.sum(np.asarray(res["level"], np.int64) != level))
    err = 0.0
    for got, want in ((res["sigma"], sigma), (res["delta"], delta)):
        gap = np.abs(np.asarray(got, np.float64) - want)
        err = max(err, float(np.max(gap / np.maximum(np.abs(want), 1.0))))
    return wrong, err


def control_reply(kind: str, g: HostGraph, src: int, rounding):
    """The control: the reference itself, put in the program's place and
    computed in the next lower precision, compared as a reply would be."""
    if kind != "bc":
        return 0, 0.0
    level, sigma, delta = ref_brandes(g, src, rounding=rounding)
    res = {"ok": True, "level": level, "sigma": sigma, "delta": delta}
    return compare_reply(kind, g, src, res)


def compare_state(keys: np.ndarray, w: np.ndarray, alive: np.ndarray,
                  want: HostGraph):
    """``(edges wrong, vertices wrong)`` of a committed version given as its
    live edge keys (sorted), their weights and the liveness vector."""
    common, gi, wi = np.intersect1d(keys, want.keys, assume_unique=True,
                                    return_indices=True)
    edges = (keys.size - common.size) + (want.keys.size - common.size)
    edges += int(np.sum(w[gi] != want.w[wi]))
    return int(edges), int(np.sum(alive != want.alive))


def verdict(numbers: dict) -> dict:
    """``{name: {"value", "limit"}}`` in the order compared."""
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
