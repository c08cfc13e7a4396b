"""Bytes one ROUND of a GLM cell with local updates HAS to move, from shapes:
the compulsory traffic, whatever implements the round.

A round holds tau = ``local_steps`` gradient steps a worker. Each is taken at
models that only the step before it makes, on a batch of its own, so no one
read of a shard can serve two of them: every row of every shard and its
target are read tau times a round (tau * N * L * (D + 1) values of 4 bytes,
D = n_features + 1 with the bias). The full-data objective at the mean
model, evaluated once a round, rides on one of those reads, as
``glm_step``'s does on its one. The round's one gossip exchange reads the
state once and writes its mixed form once (2 * N * D * 4 bytes). The draws,
a second pass over the shards for a gradient's transpose product, the
freeze of the sampled-out rows as a pass of its own: none of it is counted,
so a share of the memory's peak worked out from this can only read low,
never high, and reads the same work on every program that runs the round.

tau, N, L and D come from the configuration file (``experiment`` and
``dataset``). Never from the program.
"""


def compulsory_bytes(config):
    exp = config["experiment"]
    n = int(exp["n_workers"])
    rows = int(config["dataset"]["rows_per_worker"])
    d = int(exp["n_features"]) + 1
    tau = int(exp.get("local_steps", 1))
    shards = tau * n * rows * (d + 1) * 4
    gossip = 2 * n * d * 4
    return shards + gossip
