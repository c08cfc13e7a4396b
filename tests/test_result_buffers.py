"""The harvest's float64 results go into host memory an earlier harvest
wrote, once nothing holds it (ISSUE 49): ``jax_backend._ResultBuffers``, the
``cast`` part's ``reused_bytes``, the root's ``result_buffers``, and the
honest mean read in place.
CPU, small N and T: what is checked is values, ownership and counts, never
a time.
"""

import sys
import threading
import weakref

import numpy as np
import pytest
from conftest import small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

STORE = jax_backend._RESULT_BUFFERS

# The leaves a harvest writes: rank-2 models, the rank-3 softmax stack, and
# under ``return_state`` every state leaf (CHOCO: x again and x-hat).
KINDS = {
    "glm": (dict(problem_type="logistic"), {}),
    "softmax": (dict(problem_type="softmax", n_classes=3), {}),
    "choco_state": (
        dict(algorithm="choco", compression="top_k", compression_k=4,
             choco_gamma=0.2),
        dict(return_state=True),
    ),
    "softmax_choco_state": (
        dict(algorithm="choco", compression="top_k", compression_k=4,
             choco_gamma=0.2, problem_type="softmax", n_classes=3),
        dict(return_state=True),
    ),
}


@pytest.fixture(autouse=True)
def empty_store():
    """The store is the process's: every test starts and leaves it empty."""
    STORE.clear()
    yield
    STORE.clear()


def reached_bytes(store=STORE):
    """The bytes of every buffer the store can still reach."""
    return sum(b.nbytes for b in store._kept + store._older)


def setup_of(kind, **replace):
    fields, kw = KINDS[kind]
    cfg = small_backend_config(
        n_iterations=20, eval_every=10, **{**fields, **replace})
    return cfg, generate_synthetic_dataset(cfg), kw


def run(cfg, ds, part="cast", **kw):
    """One call under a tracer of its own: (result, root's ``result_buffers``,
    the arguments of ``harvest``'s parts called ``part``, in order)."""
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, **kw)
    events = tracer.spans()
    (root,) = (e for e in events if e["name"] == "dopt.run")
    parts = [e["args"] for e in sorted(events, key=lambda e: e["start"])
             if e["name"] == "dopt.run.harvest." + part]
    return result, root["args"]["result_buffers"], parts


def copies_of(result):
    """What a result holds, as arrays of the test's own."""
    state = result.final_state or {}
    return {
        "final_models": result.final_models.copy(),
        "final_avg_model": result.final_avg_model.copy(),
        **{"final_state." + k: v.copy() for k, v in state.items()},
    }


def assert_bitwise(got, want):
    assert got.keys() == want.keys()
    for name in want:
        assert got[name].dtype == want[name].dtype == np.float64, name
        assert got[name].shape == want[name].shape, name
        assert got[name].tobytes() == want[name].tobytes(), name


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_dropped_result_is_written_over_and_the_values_are_a_fresh_runs(kind):
    """Two calls, the first result dropped: the second reads ``reused`` on
    every leaf and is bitwise a run with the store emptied. The two calls
    differ in their seed, so a cast that wrote nothing would show."""
    cfg, ds, kw = setup_of(kind)
    other = cfg.replace(seed=cfg.seed + 1)
    first, said, casts = run(cfg, ds, **kw)
    assert said == "fresh"
    assert all(c["reused_bytes"] == 0 < c["bytes"] for c in casts)
    want_first = copies_of(first)
    del first
    second, said, casts = run(other, ds, **kw)
    assert said == "reused"
    assert all(c["reused_bytes"] == c["bytes"] > 0 for c in casts)
    assert len(casts) == len(want_first) - 1  # all but the average
    got = copies_of(second)
    for leaf in (second.final_models, *(second.final_state or {}).values()):
        assert leaf.flags.writeable and leaf.flags.c_contiguous and leaf.ndim == 2
    del second
    STORE.clear()
    fresh, said, _ = run(other, ds, **kw)
    assert said == "fresh"
    assert_bitwise(got, copies_of(fresh))
    assert got["final_models"].tobytes() != want_first["final_models"].tobytes()


# What may still reach a result's memory: name -> (what the caller keeps of
# the result, the bytes it reads through it afterwards, the rows they are).
HOLDERS = {
    "the_result": (lambda r: r, lambda h: h.final_models.tobytes(), None),
    "three_rows": (lambda r: r.final_models[:3], lambda h: h.tobytes(), 3),
    "a_memoryview": (
        lambda r: memoryview(r.final_models), lambda h: h.tobytes(), None),
    "the_owning_array": (
        lambda r: r.final_models.base, lambda h: h.tobytes(), None),
    # Nothing strong, so the store alone keeps it alive (one more call):
    # never handed out while something may still ask the reference for it.
    "a_weak_reference": (
        lambda r: weakref.ref(r.final_models.base),
        lambda h: h().tobytes(), None),
}


@pytest.mark.parametrize("holder", list(HOLDERS))
@pytest.mark.parametrize("kind", ["glm", "softmax"])
def test_a_held_result_is_never_written_over(kind, holder):
    """Whatever still reaches the first result's memory (the object, a slice
    of it, a buffer export, the owning array, a weak reference to it), the
    second call reads ``fresh`` and the held numbers stay what they were."""
    hold, read, rows = HOLDERS[holder]
    cfg, ds, kw = setup_of(kind)
    first, _, _ = run(cfg, ds, **kw)
    want = first.final_models.copy()
    held = hold(first)
    del first
    second, said, _ = run(cfg.replace(seed=cfg.seed + 1), ds, **kw)
    assert said == "fresh"
    assert read(held) == want[:rows].tobytes()
    assert second.final_models.tobytes() != want.tobytes()


def test_a_release_is_seen_by_the_next_call():
    """Held, then dropped: ``fresh`` while held, ``reused`` after, and the
    buffer handed out is the one the LAST call wrote (the held one stays
    its holder's)."""
    cfg, ds, kw = setup_of("glm")
    held, _, _ = run(cfg, ds)
    second, said, _ = run(cfg, ds)
    assert said == "fresh"
    # By address: a reference to the owner would itself hold it.
    where = second.final_models.ctypes.data
    assert where != held.final_models.ctypes.data
    del second
    third, said, _ = run(cfg, ds)
    assert said == "reused" and third.final_models.ctypes.data == where


def test_another_shape_in_between_and_the_store_keeps_one_calls_bytes():
    """A call of another shape finds nothing to reuse, and lets the free
    leftover go: with every result dropped before the next call the store
    never reaches more than the last call wrote."""
    small, ds_small, _ = setup_of("glm")
    wide, ds_wide, kw = setup_of("choco_state", n_features=14)
    first, _, _ = run(small, ds_small)
    nbytes = first.final_models.nbytes
    del first
    assert reached_bytes() == nbytes
    second, said, casts = run(wide, ds_wide, **kw)
    assert said == "fresh"
    # The free leftover of another shape was let go:
    assert reached_bytes() == sum(c["bytes"] for c in casts)
    assert reached_bytes() == 3 * second.final_models.nbytes
    del second
    third, said, _ = run(small, ds_small)
    assert said == "fresh"
    assert reached_bytes() == nbytes == third.final_models.nbytes


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_loop_that_binds_its_result_again_reuses_from_its_third_call(kind):
    """``for ...: r = run(...)``, as every sweep under ``examples/`` is
    written: call n's result is held while call n + 1 harvests and let go
    when ``r`` is bound again, so the third call is the first to find a
    free buffer. From then on two buffers a leaf take turns, every result
    is bitwise a run's with the store emptied, and the store reaches the
    last two harvests' bytes, one of them the caller's."""
    cfg, ds, kw = setup_of(kind)
    said, where, got = [], [], []
    for k in range(5):
        r, buffers, casts = run(cfg.replace(seed=cfg.seed + k), ds, **kw)
        said.append(buffers)
        where.append(r.final_models.ctypes.data)
        got.append(copies_of(r))
        assert reached_bytes() == min(k + 1, 2) * sum(c["bytes"] for c in casts)
    assert said == ["fresh", "fresh", "reused", "reused", "reused"]
    assert len(set(where)) == 2 and where[2:] == [where[0], where[1], where[0]]
    del r
    for k in (2, 4):
        STORE.clear()
        alone, buffers, _ = run(cfg.replace(seed=cfg.seed + k), ds, **kw)
        assert buffers == "fresh"
        assert_bitwise(got[k], copies_of(alone))
        del alone
    assert got[2]["final_models"].tobytes() != got[4]["final_models"].tobytes()


def test_what_no_caller_holds_is_two_calls_bytes_until_the_next_harvest():
    """The retention bound. A result held through the next harvest is kept
    one more call; with both then dropped the store alone holds two calls'
    bytes, and the next harvest takes one buffer and lets the other go."""
    cfg, ds, _ = setup_of("glm")
    first, _, _ = run(cfg, ds)
    nbytes = first.final_models.nbytes
    second, _, _ = run(cfg, ds)
    assert reached_bytes() == 2 * nbytes  # both the caller's
    del first, second
    assert reached_bytes() == 2 * nbytes  # neither: the bound
    third, said, _ = run(cfg, ds)
    assert said == "reused" and reached_bytes() == nbytes
    assert third.final_models.base is STORE._kept[0] and not STORE._older
    del third
    STORE.clear()
    assert reached_bytes() == 0


def test_a_result_held_through_two_harvests_is_forgotten():
    """It is its holder's alone: the store no longer reaches it, and
    dropping it later frees the memory."""
    cfg, ds, _ = setup_of("glm")
    held = [run(cfg, ds)[0] for _ in range(3)]
    owner = weakref.ref(held[0].final_models.base)
    assert reached_bytes() == 2 * held[0].final_models.nbytes
    assert all(b is not owner() for b in STORE._kept + STORE._older)
    del held[0]
    assert owner() is None


def test_an_interpreter_whose_counts_do_not_say_keeps_nothing(monkeypatch):
    """Ownership is read off CPython's reference counts under its lock:
    without it (a free-threaded build) the store never hands a buffer out."""
    monkeypatch.setattr(sys, "_is_gil_enabled", lambda: False, raising=False)
    store = jax_backend._ResultBuffers()
    a, reused = store.take((4, 3))
    store.keep([a])
    del a
    assert not reused and reached_bytes(store) == 0
    assert store.take((4, 3))[1] is False


def test_some_leaves_reused_and_some_new_reads_mixed():
    """``return_state`` after a plain call of the same shape: the models go
    into the kept buffer, the state's leaves into new ones."""
    cfg, ds, kw = setup_of("choco_state")
    first, _, _ = run(cfg, ds)
    del first
    second, said, casts = run(cfg, ds, **kw)
    assert said == "mixed"
    assert [c["reused_bytes"] for c in casts] == [casts[0]["bytes"], 0, 0]


def test_the_store_alone_hands_out_what_is_free_and_of_the_shape():
    """No run: a new store allocates, a kept buffer is handed out once its
    views are gone and taken off the store with it, another shape is new."""
    store = jax_backend._ResultBuffers()
    a, reused = store.take((4, 3))
    assert not reused and a.dtype == np.float64 and a.flags.c_contiguous
    store.keep([a])
    view = a.reshape(2, 6)[:1]
    del a
    assert store.take((4, 3))[1] is False
    del view
    b, reused = store.take((4, 3))
    assert reused and reached_bytes(store) == 0
    store.keep([b])
    assert store.take((3, 4))[1] is False  # matched by shape


def test_two_threads_never_receive_one_buffer():
    """More harvesting threads than cores on one store, the interpreter
    switching between them as often as it can: every buffer a thread holds
    is its alone (it reads back the thread's own mark), and a hand-out is
    never of a buffer another thread still holds."""
    store = jax_backend._ResultBuffers()
    n_threads, rounds, shape = 16, 300, (8, 5)
    faults, reuses = [], []
    start = threading.Barrier(n_threads)

    def harvest(me):
        start.wait(timeout=30)
        reused_here = 0
        for k in range(rounds):
            mine = []
            for _ in range(2):  # two leaves of one shape a call
                buf, reused = store.take(shape)
                reused_here += reused
                buf[...] = me * rounds + k
                mine.append(buf)
            if mine[0] is mine[1]:
                faults.append((me, k, "one buffer twice"))
            store.keep(mine)
            if k % 3:  # hold every third round's result across the next take
                del buf, mine
                continue
            held = mine[0].reshape(-1)
            del buf, mine
            other, _ = store.take(shape)
            other[...] = -1.0
            if not np.all(held == me * rounds + k):
                faults.append((me, k, "a held buffer was written over"))
            del held, other
        reuses.append(reused_here)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=harvest, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert faults == []
    assert len(reuses) == n_threads and sum(reuses) > 0
    assert reached_bytes(store) <= 2 * 2 * 8 * 5 * 8  # two harvests' two leaves


def test_two_threads_running_at_once_each_get_their_own_results():
    """``run`` from threads, as the serving plane calls it: each result is
    bitwise the one the same seed gives alone."""
    cfg, ds, _ = setup_of("glm")
    seeds = [cfg.seed + k for k in range(4)]
    alone = {}
    for seed in seeds:
        STORE.clear()
        alone[seed] = copies_of(run(cfg.replace(seed=seed), ds)[0])
    got, errors = {}, []

    def sweep(seed):
        try:
            for _ in range(3):  # each drops its result and harvests again
                got[seed] = copies_of(
                    jax_backend.run(cfg.replace(seed=seed), ds, 0.0))
        except Exception as err:  # reported below, in the test's thread
            errors.append(err)

    threads = [threading.Thread(target=sweep, args=(s,)) for s in seeds]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads) and errors == []
    for seed in seeds:
        assert_bitwise(got[seed], alone[seed])


@pytest.mark.parametrize("placement", ["uniform", "within_budget"])
@pytest.mark.parametrize("reuse", ["fresh", "reused"])
def test_the_honest_mean_is_read_in_place_and_bitwise_the_copys(placement, reuse):
    """Under an adversary ``final_avg_model`` is bitwise the mean of the
    honest rows' indexed copy, the ``average`` part copies nothing, and it
    says how many rows it averaged: into a new buffer and a kept one."""
    from distributed_optimization_tpu.parallel.adversary import byzantine_set
    from distributed_optimization_tpu.parallel import build_topology

    cfg = small_backend_config(
        n_workers=16, n_iterations=10, eval_every=10, attack="sign_flip",
        n_byzantine=2, aggregation="trimmed_mean", robust_b=1,
        byzantine_placement=placement,
    )
    ds = generate_synthetic_dataset(cfg)
    if reuse == "reused":
        run(cfg.replace(seed=cfg.seed + 1), ds)  # its result dropped at once
    result, said, (average,) = run(cfg, ds, part="average")
    assert said == reuse
    assert average == {"rows": 14}
    byz = byzantine_set(cfg, build_topology(cfg.topology, cfg.n_workers))
    assert byz.sum() == 2
    want = result.final_models[~byz].mean(axis=0)
    assert result.final_avg_model.dtype == np.float64
    assert result.final_avg_model.tobytes() == want.tobytes()
    assert result.final_avg_model.tobytes() != (
        result.final_models.mean(axis=0).tobytes())


def test_the_masked_reduction_is_the_indexed_means_additions_at_size():
    """The cell's proportions at a size the sandbox holds: a tenth of the
    rows masked, float32 values widened as the harvest widens them."""
    rng = np.random.default_rng(49)
    models = rng.standard_normal((4096, 81)).astype(np.float32).astype(np.float64)
    honest = np.ones(4096, bool)
    honest[rng.choice(4096, 384, replace=False)] = False
    got = np.add.reduce(models, axis=0, where=honest[:, None]) / int(honest.sum())
    assert got.tobytes() == models[honest].mean(axis=0).tobytes()
