"""The configurations' graphs, generated from the seed on the host.

One generation per run: the same arrays go to the program's bulk loader and
to the host reference.  The Kronecker/R-MAT recursion is the one of the
Graph500 specification (section 3) and of Chakrabarti et al.'s R-MAT; it
is kept here so that a change to the program's own generator cannot move
the benchmark's graphs.
"""
from __future__ import annotations

import json
import os

import numpy as np

CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "configs")


def load_config(name: str) -> dict:
    with open(os.path.join(CONFIG_DIR, f"{name}.json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed (graph, sources,
    updates, sampling), so that one use never shifts another's draws."""
    return np.random.default_rng([int(seed), stream])


def kronecker_edges(scale: int, edge_factor: int, initiator, weight_max: int,
                    rng: np.random.Generator):
    """``edge_factor * 2**scale`` directed edges of a Kronecker graph with
    initiator ``(a, b, c, d)``; self loops dropped, integer weights uniform
    in ``1..weight_max``.

    Returns ``(n, src int32, dst int32, w float32)``.
    """
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    a, b, c, _ = (float(x) for x in initiator)
    for level in range(scale):
        # quadrant of each edge at this level: [0, a) top-left, [a, a+b)
        # top-right, [a+b, a+b+c) bottom-left, the rest bottom-right
        r = rng.random(m, dtype=np.float32)
        half = np.int32(n >> (level + 1))
        lower = r >= a + b
        src += lower * half
        dst += (lower ^ (r >= a) ^ (r >= a + b + c)) * half
    w = rng.integers(1, weight_max + 1, size=m, dtype=np.int32).astype(
        np.float32)
    keep = src != dst
    return n, src[keep], dst[keep], w[keep]


def structure_seed(config: dict, seed: int) -> int:
    """The seed the graph's structure is drawn from: the configuration's
    fixed ``structure_seed`` where it has one, else the run's seed."""
    return int(config.get("structure_seed", seed))


def symmetric(n: int, src, dst, w):
    """An undirected graph's arcs: each generated tuple is an edge both
    ways; of tuples naming one vertex pair, the last one's weight holds,
    on both arcs."""
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key = lo.astype(np.int64) * n + hi
    _, last = np.unique(key[::-1], return_index=True)
    keep = key.size - 1 - last
    lo, hi, w = lo[keep], hi[keep], w[keep]
    return np.concatenate([lo, hi]), np.concatenate([hi, lo]), \
        np.concatenate([w, w])


def generate(config: dict, seed: int):
    """``(n, ecap, src, dst, w, labels)`` of the configuration's graph.

    The edges come from :func:`structure_seed`, both ways where the
    configuration is not ``directed``; with ``permute_labels`` the run's
    seed then permutes the vertex labels, so that a configuration with a
    fixed structure serves the same graph under other labels on every
    seed.  ``labels[i]`` is the label of structural vertex ``i``.
    """
    n, src, dst, w = kronecker_edges(
        config["scale"], config["edge_factor"], config["initiator"],
        config["weight_max"], rng_for(structure_seed(config, seed), 0))
    arcs = 1 if config["directed"] else 2
    if not config["directed"]:
        src, dst, w = symmetric(n, src, dst, w)
    labels = (rng_for(seed, 4).permutation(n).astype(np.int32)
              if config["permute_labels"] else np.arange(n, dtype=np.int32))
    ecap = int(arcs * config["edge_factor"] * n * config["slack"])
    return n, ecap, labels[src], labels[dst], w, labels
