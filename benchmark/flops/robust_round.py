"""Bytes one screened gossip round HAS to move, from shapes: the compulsory
traffic, whatever implements the round.

Every worker's transmitted model is read once and its aggregate written
once (2 * N * D values of the state's 4 bytes), and each slot of the
neighbor table is used once, reading one index and one liveness bit as the
float the rule takes it as (N * k_max * 8 bytes). The gathered
``[N, k_max, D]`` copy of the neighbours' rows, the closed neighbourhood's
``[N, k_max + 1, D]`` stack, its sort, a row-major copy of the models for the
gather, the attackers' benign mix: none of it is counted, so a share of the
memory's peak worked out from this can only read low, never high, and reads
the same work on every program that screens this graph.

N, D and the table's width come from the configuration file (``experiment``
and ``graph``), never from the program.
"""


def per_round_bytes(config):
    exp = config["experiment"]
    n = int(exp["n_workers"])
    state = 2 * n * (int(exp["n_features"]) + 1) * 4
    tables = n * int(config["graph"]["k_max"]) * 8
    return state + tables
