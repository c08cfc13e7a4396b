"""Bytes one gossip round on a torus blocked by grid rows over a worker mesh
HAS to move on ONE chip, from shapes: the compulsory traffic, whatever
implements the round.

A chip holds S = N / worker_mesh workers, whole grid rows of C columns. Its
block of models is read once and the mixed block written once (2 * S * D values
of the state's 4 bytes); each of a worker's k = 4 neighbour slots reads one
index and one weight (S * k * 8 bytes); and the h = 2 * C boundary rows that
arrive from the two neighbouring chips are received and written once before
the gather reads them (2 * h * D * 4 bytes). A row fetched once per slot
instead of once, the halo-extended copy of the block, the index arithmetic:
none of it is counted, so a share of the memory's peak worked out from this
can only read low, never high, and reads the same work on every program that
mixes this torus over this mesh (a stencil of shifts needs no index and no
weight: it would read a little high by S * k * 8 of 180 MB, 5%).

N, D, the mesh and the grid's shape come from the configuration file
(``experiment``), never from the program.
"""

import math

SLOTS = 4  # a torus: up, down, left, right


def per_round_bytes(config):
    exp = config["experiment"]
    n = int(exp["n_workers"])
    columns = math.isqrt(n)
    rows = n // int(exp["worker_mesh"])
    width = int(exp["n_features"]) + 1
    state = 2 * rows * width * 4
    tables = rows * SLOTS * 8
    halo = 2 * (2 * columns) * width * 4
    return state + tables + halo
