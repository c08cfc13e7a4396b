"""Operations one D-SGD iteration of the multinomial tier needs, from shapes.

Forward logits X W and the weight gradient X^T (P - Y): two [b, d+1] x
[d+1, K] matmuls per worker, 2 flops per multiply-add. The inline evaluation
passes, the softmax, the mixing and the update are not counted, so a share
of peak worked out from this can only read low, never high.
"""


def per_iteration(config):
    exp = config["experiment"]
    rows = min(int(exp["local_batch_size"]), int(config["dataset"]["rows_per_worker"]))
    return 4 * int(exp["n_workers"]) * rows * (int(exp["n_features"]) + 1) * int(exp["n_classes"])
