"""Bytes one gossip round on a drawn graph HAS to move, from shapes: the
compulsory traffic, whatever implements the round.

Every worker's model is read once and its mixed model written once
(2 * N * D values of the state's 4 bytes), and each of the graph's edges is
used in both directions, each use reading one index and one weight
(2 * edges * 8 bytes). Padded slots, rows fetched once per neighbour
instead of once, an [N, k_max, D] stack, a relayout of the models for the
gather: none of it is counted, so a share of the memory's peak worked out
from this can only read low, never high, and reads the same work on every
program that mixes this graph.

N, D and the edge count of the pinned graph come from the configuration
file (``experiment`` and ``graph``), never from the program.
"""


def per_round_bytes(config):
    exp = config["experiment"]
    state = 2 * int(exp["n_workers"]) * (int(exp["n_features"]) + 1) * 4
    tables = 2 * int(config["graph"]["edges"]) * 8
    return state + tables
