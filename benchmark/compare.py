"""The comparison that decides ``correct``: what the timed program produced
against the plain reference, number by number, each with its own limit from
the configuration file. Every number is printed beside its limit."""

import numpy as np


def numbers(produced, reference):
    """The numbers compared. ``reference`` holds ``objective`` and ``consensus``
    for the evaluations of the iterations it followed; ``produced`` holds the
    program's rows for the whole experiment, of which the same first rows are
    compared.

    objective_max_rel    the loss of the mean model at every evaluation, worst
                         relative gap: an error all workers share shows here
    consensus_max_rel    the consensus error (mean squared distance of the
                         workers' models from their mean) at every evaluation;
                         its first row is eta^2 times the spread of the first
                         gradients as the update got them (x0 = 0): rounding
                         that differs from worker to worker shows here
    """
    out = {}
    for key, name in (("objective", "objective_max_rel"), ("consensus", "consensus_max_rel")):
        want = np.asarray(reference[key], dtype=np.float64)
        got = np.asarray(produced[key], dtype=np.float64)[:want.shape[0]]
        if got.shape != want.shape or not want.size or not np.all(np.isfinite(got)):
            out[name] = float("inf")
            continue
        out[name] = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))
    return out


def judge(nums, limits, say=print):
    """True if every number is within its limit; prints each beside it."""
    ok = True
    for name, limit in limits.items():
        value = nums[name]
        within = value <= limit
        ok = ok and within
        say(f"[check] {name} = {value:.6g}  limit {limit:.6g}  {'ok' if within else 'OVER'}")
    return ok
