"""Property tests for ops/compression.py (ISSUE-6 satellite).

Two contracts every operator must honor:

1. the CONTRACTION inequality E‖v − Q(v)‖² ≤ (1 − δ)‖v‖² with the
   operator's own reported δ — the condition the CHOCO/error-feedback
   convergence proofs rest on — checked empirically across dtypes and
   x64 on/off: per-instance for the deterministic top_k (where it holds
   for every input), as a fixed-seed Monte-Carlo mean for the randomized
   random_k/qsgd (a deterministic draw set, so the asserted slack is a
   one-time calibration, not a flakiness budget);
2. exact ``floats_per_edge`` accounting against hand counts (the number
   the comms benches and the RunTrace health block multiply realized
   edges by).

Hypothesis widens the input coverage where available (the
requirements-test.txt optional dep, same convention as
tests/test_properties.py); a seeded parametrized fallback keeps the
module meaningful without it.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import enable_x64
import pytest

from distributed_optimization_tpu.ops.compression import make_compressor

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # seeded fallback below
    HAVE_HYPOTHESIS = False

# Monte-Carlo draws for the randomized operators. The key stream is fixed
# (fold_in over a constant base), so the empirical mean is a deterministic
# function of (name, d, k, seed) — the slack absorbs Monte-Carlo error at
# this M once, forever.
N_DRAWS = 256
MC_SLACK = 5.0 / np.sqrt(N_DRAWS)  # ~0.31 on the error/δ-normalized ratio


def _contraction_ratio(name, d, k, v_row, dtype):
    """Empirical E‖v − Q(v)‖² / ‖v‖² for one row, at the given dtype."""
    comp = make_compressor(name, d, k)
    v = jnp.asarray(v_row.reshape(1, d), dtype=dtype)
    denom = float(np.linalg.norm(v_row) ** 2)
    if denom == 0.0:
        return 0.0, comp.delta
    if name == "top_k":  # deterministic: one application IS the expectation
        err = comp.apply(None, v) - v
        return float(jnp.sum(err * err)) / denom, comp.delta
    base = jax.random.key(1234)
    total = 0.0
    for i in range(N_DRAWS):
        q = comp.apply(jax.random.fold_in(base, i), v)
        total += float(jnp.sum((v - q) ** 2))
    return total / N_DRAWS / denom, comp.delta


def _check_contraction(name, d, k, v_row, dtype):
    ratio, delta = _contraction_ratio(name, d, k, v_row, dtype)
    assert 0.0 < delta <= 1.0
    bound = 1.0 - delta
    if name == "top_k":
        # Deterministic and per-instance: keeping the k largest-|v|
        # coordinates removes at most the (1 − k/d) mass fraction.
        assert ratio <= bound + 1e-6, (name, d, k, ratio, bound)
    else:
        # Monte-Carlo mean against the expectation bound, normalized
        # slack (random_k meets the bound with equality in expectation,
        # so the slack is genuinely load-bearing there).
        assert ratio <= bound + MC_SLACK * max(delta, 1e-3) + 1e-6, (
            name, d, k, ratio, bound,
        )


_SEEDED_CASES = [
    ("top_k", 16, 4, 0), ("top_k", 9, 9, 1), ("top_k", 40, 1, 2),
    ("random_k", 16, 4, 3), ("random_k", 9, 2, 4), ("random_k", 12, 11, 5),
    ("qsgd", 16, 4, 6), ("qsgd", 9, 2, 7), ("qsgd", 40, 8, 8),
]


def _row(d, seed, heavy_tail=False):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(d)
    if heavy_tail:
        v[:: max(d // 3, 1)] *= 1e3  # adversarial spread
    return v


@pytest.mark.parametrize("dtype_x64", [
    ("float32", False), ("float32", True), ("float64", True),
], ids=["f32", "f32-x64on", "f64-x64on"])
@pytest.mark.parametrize("name,d,k,seed", _SEEDED_CASES)
def test_contraction_seeded(name, d, k, seed, dtype_x64):
    dtype, x64 = dtype_x64
    v = _row(d, seed, heavy_tail=seed % 2 == 0)
    if x64:
        with enable_x64():
            _check_contraction(name, d, k, v, jnp.dtype(dtype))
    else:
        _check_contraction(name, d, k, v, jnp.dtype(dtype))


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        name=st.sampled_from(["top_k", "random_k", "qsgd"]),
        d=st.integers(min_value=2, max_value=48),
        data=st.data(),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_contraction_hypothesis(name, d, data, seed):
        k = data.draw(
            st.integers(min_value=1, max_value=16 if name == "qsgd" else d)
        )
        v = _row(d, seed, heavy_tail=seed % 3 == 0)
        _check_contraction(name, d, k, v, jnp.float32)


# ----------------------------------------------- floats_per_edge accounting

def test_floats_per_edge_hand_counts():
    """Exact payload accounting vs hand counts, the sparsification
    literature's convention: k values + k indices for the sparsifiers,
    (bits+1)·d/32 + the row norm for qsgd, d for identity."""
    assert make_compressor("none", 80).floats_per_edge == 80.0
    assert make_compressor("top_k", 80, 10).floats_per_edge == 20.0
    assert make_compressor("random_k", 80, 7).floats_per_edge == 14.0
    # qsgd at 4 bits: 80 coords × (4+1)/32 bits-as-floats + 1 norm float.
    assert make_compressor("qsgd", 80, 4).floats_per_edge == (
        80 * 5 / 32.0 + 1.0
    )
    # 1-bit signSGD-style extreme: 80 × 2/32 + 1.
    assert make_compressor("qsgd", 80, 1).floats_per_edge == 6.0
    # Identity keeps δ = 1, sparsifiers report k/d.
    assert make_compressor("none", 80).delta == 1.0
    assert make_compressor("top_k", 80, 10).delta == 10 / 80
    assert make_compressor("random_k", 80, 7).delta == 7 / 80


def test_qsgd_delta_formula():
    """δ = ω = 1/(1 + min(d/s², √d/s)) with s = 2^bits (Koloskova et al.
    '19 §2) — hand-evaluated cases."""
    comp = make_compressor("qsgd", 64, 4)  # s=16: min(64/256, 8/16)=0.25
    assert comp.delta == pytest.approx(1.0 / 1.25)
    comp = make_compressor("qsgd", 4, 8)  # s=256: min tiny → δ→1
    assert comp.delta == pytest.approx(1.0 / (1.0 + 4 / 256**2))


def test_compressor_rejects_bad_params():
    with pytest.raises(ValueError, match="compression_k"):
        make_compressor("top_k", 8, 0)
    with pytest.raises(ValueError, match="compression_k"):
        make_compressor("random_k", 8, 9)
    with pytest.raises(ValueError, match="qsgd bits"):
        make_compressor("qsgd", 8, 17)
    with pytest.raises(ValueError, match="Unknown compression"):
        make_compressor("signsgd", 8, 1)


# ------------------------------------------------------ ties at the threshold

@pytest.mark.parametrize("k,kept", [
    (1, [1]),            # three entries tie for the largest: the first
    (2, [1, 3]),
    (3, [1, 3, 4]),
    (4, [0, 1, 3, 4]),   # two tie for the fourth place: the lower index
    (5, [0, 1, 3, 4, 6]),
])
def test_top_k_ties_go_to_the_lower_index(k, kept):
    """Equal magnitudes at the threshold (signs do not matter): exactly k are
    kept, the ones at the lower indices, so a row's payload is 2k whatever
    ties (the benchmark's plain reference restates this rule)."""
    v = jnp.asarray([[1.0, -3.0, 0.25, 3.0, -3.0, 0.5, -1.0, 0.0]], jnp.float32)
    out = np.asarray(make_compressor("top_k", 8, k).apply(None, v))
    assert np.flatnonzero(out[0]).tolist() == kept
    np.testing.assert_array_equal(out[0, kept], np.asarray(v)[0, kept])
