"""Bytes one iteration of a GLM cell HAS to move, from shapes: the
compulsory traffic, whatever implements the step.

Where the full-data objective is evaluated every iteration, every row of
every shard and its target are read once an iteration (N * L * (D + 1)
values of 4 bytes, D = n_features + 1 with the bias), and that one read can
serve the batch's gradient too, the batch being rows of the same shard. Each
gossip round reads a state leaf once and writes its mixed form once
(2 * N * D * 4 bytes a round). The draw, the gathered batch as a copy, a
second pass over the shards, the tracker's arithmetic as passes of its own:
none of it is counted, so a share of the memory's peak worked out from this
can only read low, never high, and reads the same work on every program
that runs the step.

N, L and D come from the configuration file (``experiment`` and
``dataset``); the rounds from the table below by the algorithm's name, as
its published rule states them. Never from the program.
"""

# Model-sized gossip exchanges an iteration, by the rule's own statement:
# D-SGD mixes x; gradient tracking mixes x and the tracker y.
GOSSIP_ROUNDS = {"dsgd": 1, "gradient_tracking": 2}


def compulsory_bytes(config):
    exp = config["experiment"]
    n = int(exp["n_workers"])
    rows = int(config["dataset"]["rows_per_worker"])
    d = int(exp["n_features"]) + 1
    shards = n * rows * (d + 1) * 4
    gossip = GOSSIP_ROUNDS[exp["algorithm"]] * 2 * n * d * 4
    return shards + gossip
