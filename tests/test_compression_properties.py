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

from distributed_optimization_tpu.ops.compression import (
    make_compressor,
    select_top_scored,
    selection_label,
)

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


_DTYPES_X64 = [("float32", False), ("float32", True), ("float64", True)]
_DTYPE_IDS = ["f32", "f32-x64on", "f64-x64on"]


def _under(x64, fn):
    """``fn()``, under ``enable_x64`` where asked."""
    if x64:
        with enable_x64():
            return fn()
    return fn()


@pytest.mark.parametrize("dtype_x64", _DTYPES_X64, ids=_DTYPE_IDS)
@pytest.mark.parametrize("name,d,k,seed", _SEEDED_CASES)
def test_contraction_seeded(name, d, k, seed, dtype_x64):
    dtype, x64 = dtype_x64
    v = _row(d, seed, heavy_tail=seed % 2 == 0)
    _under(x64, lambda: _check_contraction(name, d, k, v, jnp.dtype(dtype)))


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


# ------------------------------------------- selection by a counted threshold

def _oracle_mask(scores, k):
    """The k largest of each row by a plain stable argsort: of equal scores
    the lower index first. Rows are everything behind the first axis."""
    flat = np.asarray(scores).reshape(scores.shape[0], -1)
    mask = np.zeros(flat.shape, bool)
    for row, keep in zip(flat, mask):
        keep[np.argsort(-row, kind="stable")[:k]] = True
    return mask.reshape(scores.shape)


def _tie_rows(kind, d, rng):
    """Three rows of d numbers whose magnitudes tie at the threshold."""
    if kind == "all_equal":
        return np.full((3, d), -2.5)
    if kind == "all_zero":
        return np.zeros((3, d))
    if kind == "signed_zeros":  # +0.0 and -0.0 tie; a few non-zeros
        v = np.where(rng.random((3, d)) < 0.5, 0.0, -0.0)
        v[:, ::5] = rng.standard_normal((3, len(range(0, d, 5))))
        return v
    if kind == "subnormals":  # ordered among themselves, above 0
        return rng.integers(-4, 5, (3, d)) * 1e-40
    if kind == "grid":  # few distinct magnitudes: ties wherever k falls
        return rng.integers(-3, 4, (3, d)) * 0.5
    assert kind == "continuous"
    return rng.standard_normal((3, d))


_TIE_KINDS = [
    "all_equal", "all_zero", "signed_zeros", "subnormals", "grid", "continuous",
]
@pytest.mark.parametrize("dtype_x64", _DTYPES_X64, ids=_DTYPE_IDS)
@pytest.mark.parametrize("k", [1, 5, 12, 24])
@pytest.mark.parametrize("kind", _TIE_KINDS)
def test_top_k_is_the_argsort_oracle(kind, k, dtype_x64):
    """``top_k`` keeps exactly the entries a stable argsort of the magnitudes
    keeps (k = 1, k inside a run of ties, k = d), and the kept values are the
    input's: on rows full of ties, zeros of both signs and subnormals."""
    dtype, x64 = dtype_x64
    d = 24

    def check():
        v = jnp.asarray(_tie_rows(kind, d, np.random.default_rng(k)), dtype)
        out = np.asarray(make_compressor("top_k", d, k).apply(None, v))
        keep = _oracle_mask(np.abs(np.asarray(v)), k)
        assert out.dtype == np.dtype(dtype)
        # v * mask, as the operator always computed it (a product flushes a
        # kept subnormal on some backends, with either mask).
        np.testing.assert_array_equal(
            out, np.asarray(v * jnp.asarray(keep, v.dtype))
        )
        np.testing.assert_array_equal(
            np.asarray(select_top_scored(jnp.abs(v), k)), keep
        )

    _under(x64, check)


@pytest.mark.parametrize("dtype_x64", _DTYPES_X64, ids=_DTYPE_IDS)
@pytest.mark.parametrize("k", [1, 7, 24])
def test_random_k_is_the_argsort_oracle_on_its_draws(k, dtype_x64):
    """``random_k`` keeps the k entries with the largest uniform draws, the
    draws being ``jax.random.uniform(key, v.shape)`` as they always were:
    exactly k non-zeros a row where the input has no zero."""
    dtype, x64 = dtype_x64
    d = 24

    def check():
        v = jnp.asarray(np.random.default_rng(k).standard_normal((3, d)), dtype)
        key = jax.random.key(k)
        out = np.asarray(make_compressor("random_k", d, k).apply(key, v))
        keep = _oracle_mask(jax.random.uniform(key, v.shape), k)
        np.testing.assert_array_equal(out, np.where(keep, np.asarray(v), 0))
        assert (np.count_nonzero(out, axis=1) == k).all()

    _under(x64, check)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("k", [1, 9, 40, 63, 64])
def test_select_top_scored_counts_exactly_k_whatever_ties(k, dtype):
    """The selection itself, on scores with long runs of ties (a grid of
    eight values over 64 entries, a run of ties at every k): the oracle's
    mask, exactly k a row, in every score width."""
    scores = jnp.asarray(
        np.random.default_rng(k).integers(0, 8, (4, 64)) / 8.0, dtype
    )
    mask = np.asarray(select_top_scored(scores, k))
    np.testing.assert_array_equal(
        mask, _oracle_mask(np.asarray(scores, np.float32), k)
    )
    assert (mask.sum(axis=1) == k).all()


@pytest.mark.parametrize("name", ["top_k", "random_k", "qsgd"])
@pytest.mark.parametrize("kind", ["grid", "continuous"])
def test_model_shaped_stack_is_the_flattened_stack(name, kind):
    """A compressor over [N, d, K] is the compressor over the stack
    flattened to [N, d·K]: "the lower index" is row-major over (d, K), and
    the randomized operators draw the same numbers for either shape."""
    n, d, k_classes, k = 3, 7, 5, 4 if name == "qsgd" else 11
    v = jnp.asarray(
        _tie_rows(kind, d * k_classes, np.random.default_rng(3)), jnp.float32
    )
    comp = make_compressor(name, d * k_classes, k)
    key = jax.random.key(17)
    shaped = comp.apply(key, v.reshape(n, d, k_classes))
    assert shaped.shape == (n, d, k_classes)
    np.testing.assert_array_equal(
        np.asarray(shaped).reshape(n, -1), np.asarray(comp.apply(key, v))
    )


@pytest.mark.parametrize("name,dtype,label", [
    ("top_k", "float32", "threshold:16"),
    ("top_k", "bfloat16", "threshold:8"),
    ("top_k", "float64", "threshold:32"),
    ("random_k", "bfloat16", "threshold:16"),  # its scores: float32 draws
    ("qsgd", "float32", "none"),
    ("none", "float32", "none"),
])
def test_selection_label(name, dtype, label):
    """The root span's ``select``: the method and its counting passes over a
    row, one per two bits of a score."""
    assert selection_label(name, dtype) == label
