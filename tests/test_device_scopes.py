"""Device scopes (ISSUE 34): ``jax.named_scope`` round the phases of the one
scan, the compiled program's table from instruction to scope, and the
program noted on the ``dopt.run`` root (``observability/device_scopes.py``).

A scope is metadata only: it is in no StableHLO text (the hashes
``tests/test_fault_draws.py`` pins do not see it) and the compiled text with
its ``metadata={...}`` removed is the text of the same scan built with the
scopes patched to no-ops. CPU, N = 16: structure, never a time.
"""

import contextlib
import gzip
import os
import re

import jax
import jax.numpy as jnp
import pytest
from conftest import small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.serving import cache as serving_cache
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, ROWS, T = 16, 24, 12
BASE = {"sampling", "gradient", "gossip", "update", "eval"}
# configuration -> (overrides, the scopes its compiled scan must carry)
SCANS = {
    "dsgd_ring_logistic": (
        dict(problem_type="logistic", sampling_impl="dense"), BASE),
    # full batch: nothing is drawn, so nothing is traced under ``sampling``
    "dsgd_ring_softmax": (
        dict(problem_type="softmax", n_classes=4, local_batch_size=ROWS),
        BASE - {"sampling"}),
    "choco_top_k": (
        dict(problem_type="logistic", algorithm="choco", compression="top_k",
             compression_k=3, choco_gamma=0.2, sampling_impl="dense"),
        BASE | {"compress"}),
    "faulty_ring_shift": (
        dict(problem_type="logistic", sampling_impl="dense",
             topology_impl="neighbor", edge_drop_prob=0.3, straggler_prob=0.1),
        BASE | {"faults"}),
    "faulty_chain_gather": (
        dict(problem_type="logistic", sampling_impl="dense", topology="chain",
             topology_impl="neighbor", edge_drop_prob=0.3, straggler_prob=0.1),
        BASE | {"faults"}),
    "halo_ring_mesh4": (
        dict(problem_type="logistic", sampling_impl="dense",
             topology_impl="neighbor", worker_mesh=4), BASE),
}


def cfg_of(name):
    kw = dict(n_workers=N, n_samples=N * ROWS, n_features=12,
              n_informative_features=6, topology="ring", n_iterations=T,
              local_batch_size=8)
    kw.update(SCANS[name][0])
    return small_backend_config(**kw)


class _Traced(Exception):
    pass


def lowered_scan(cfg, ds, monkeypatch):
    """The call's one device program, lowered where ``_run`` hands it to the
    driver."""
    def grab(make_seg_scan, trips_per_eval, state0, data_args, mesh, config,
             n_evals, spans, **kw):
        raise _Traced(make_seg_scan(n_evals), (state0, jnp.int32(0), data_args))

    monkeypatch.setattr(jax_backend, "_drive_segments", grab)
    with pytest.raises(_Traced) as caught:
        jax_backend.run(cfg, ds, 0.0)
    seg_scan, args = caught.value.args
    return jax.jit(seg_scan).lower(*args)


def stripped(text):
    """Compiled text less what a scope can reach: each instruction's
    ``metadata={...}`` and the tables of files and stack frames in front of
    the first computation."""
    return re.sub(r", metadata=\{[^}]*\}", "", text[text.index("\n%"):])


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("name", sorted(SCANS))
def test_scopes_are_metadata_of_the_compiled_scan_and_nothing_else(name, monkeypatch):
    cfg = cfg_of(name)
    ds = generate_synthetic_dataset(cfg)
    lowered = lowered_scan(cfg, ds, monkeypatch)
    assert "dopt." not in lowered.as_text()
    compiled = lowered.compile()
    table = device_scopes.scope_table(compiled)
    assert table["module"] == "jit_seg_scan"
    found = {row["scope"] for row in table["rows"]} | {
        s for row in table["rows"] for s in row["also"]}
    assert found - {None} == SCANS[name][1]
    for row in table["rows"]:
        assert row["head"].startswith("%") and " = " in row["head"]
        assert row["scope"] not in row["also"]
    # the same scan with every scope a no-op: the same instructions
    monkeypatch.setattr(device_scopes, "scope", lambda name: _NoScope())
    bare = lowered_scan(cfg, ds, monkeypatch).compile()
    assert "dopt." not in bare.as_text()
    assert stripped(bare.as_text()) == stripped(compiled.as_text())


def test_the_flight_recorder_has_a_scope_of_its_own(monkeypatch):
    cfg = cfg_of("dsgd_ring_logistic").replace(telemetry=True)
    ds = generate_synthetic_dataset(cfg)
    table = device_scopes.scope_table(lowered_scan(cfg, ds, monkeypatch).compile())
    assert "recorder" in {row["scope"] for row in table["rows"]}


def test_an_unknown_scope_is_refused():
    with pytest.raises(ValueError, match="unknown device scope"):
        device_scopes.scope("mixing")
    assert all(device_scopes.scope(s) is not None for s in device_scopes.SCOPES)


HLO = """HloModule jit_seg_scan, is_scheduled=true

FileNames
1 "x.py"

%fused_a (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %m = f32[8,4]{1,0} multiply(%p0, %p0), metadata={op_name="jit(f)/while/body/dopt.update/dopt.gossip/mul"}
  ROOT %a = f32[8,4]{1,0} add(%m, %p0), metadata={op_name="jit(f)/while/body/dopt.update/add"}
}

%fused_b (p0: f32[8,4]) -> (f32[4], f32[8,4], f32[8,4]) {
  %p0 = f32[8,4]{1,0} parameter(0)
  %small = f32[4]{0} reduce(%p0), metadata={op_name="jit(f)/while/body/dopt.eval/reduce_sum"}
  %big = f32[8,4]{1,0} negate(%p0), metadata={op_name="jit(f)/while/body/dopt.update/neg"}
  %big2 = f32[8,4]{1,0} abs(%p0), metadata={op_name="jit(f)/while/body/dopt.faults/abs"}
  ROOT %t = (f32[4]{0}, f32[8,4]{1,0}, f32[8,4]{1,0}) tuple(%small, %big, /*index=2*/%big2)
}

%never_reached (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %lost = f32[4]{0} negate(%p0), metadata={op_name="jit(f)/dopt.eval/neg"}
}

%body (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[]{:T(128)}, f32[8,4]{1,0:T(8,128)}) parameter(0)
  %x = f32[8,4]{1,0:T(8,128)} get-tuple-element(%arg), index=1
  %add_fusion.3 = f32[8,4]{1,0:T(8,128)S(1)} fusion(%x), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/while/body/dopt.update/add" stack_frame_id=3}, backend_config={"x":"calls=%never_reached"}
  %fusion.7 = (f32[4]{0}, f32[8,4]{1,0}, f32[8,4]{1,0}) fusion(%add_fusion.3), kind=kLoop, calls=%fused_b
  %copy.2 = f32[8,4]{1,0} copy(%x)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %out = (s32[]{:T(128)}, f32[8,4]{1,0}) tuple(%i, %add_fusion.3)
}

%cond (arg: (s32[], f32[8,4])) -> pred[] {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main.4 (x: f32[8,4]) -> f32[8,4] {
  %x = f32[8,4]{1,0} parameter(0), metadata={op_name="x"}
  %first = f32[8,4]{1,0} negate(%x), metadata={op_name="jit(f)/dopt.eval/jit(inner)/neg"}
  %tuple.1 = (s32[], f32[8,4]{1,0}) tuple(%x, %first)
  %while.5 = (s32[], f32[8,4]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %r = f32[8,4]{1,0} get-tuple-element(%while.5), index=1
}
"""


def test_the_tables_rules_on_a_hand_written_program():
    table = device_scopes.table_from_text(HLO)
    assert table["module"] == "jit_seg_scan"
    rows = {row["head"].partition(" = ")[0]: row for row in table["rows"]}
    # containers are walked, not listed; unreached computations are not read
    assert "%while.5" not in rows and "%lost" not in rows
    # the head is the compiled text's own beginning of the instruction
    assert rows["%add_fusion.3"]["head"] == "%add_fusion.3 = f32[8,4]{1,0:T(8,128)S(1)}"
    assert rows["%fusion.7"]["head"].startswith("%fusion.7 = (f32[4]{0}, f32[8,4]")
    # innermost wins; a fusion takes its own op_name (its root's); also = the rest
    assert rows["%first"]["scope"] == "eval"
    assert (rows["%add_fusion.3"]["scope"], rows["%add_fusion.3"]["also"]) == (
        "update", ["gossip"])
    # no op_name of its own: the output with the most bytes, ties to the first
    assert (rows["%fusion.7"]["scope"], rows["%fusion.7"]["also"]) == (
        "update", ["faults", "eval"])
    # no dopt.* anywhere in the path: None
    assert rows["%copy.2"]["scope"] is None and rows["%lt"]["scope"] is None
    assert rows["%copy.2"]["also"] == []


@pytest.fixture
def recorded_trace(tmp_path):
    path = str(tmp_path / "v5e_short.xplane.pb")
    src = os.path.join(ROOT, "benchmark", "testdata", "v5e_short.xplane.pb.gz")
    with gzip.open(src) as fh, open(path, "wb") as out:
        out.write(fh.read())
    return path


def test_the_exact_join_on_a_recorded_chip_trace(recorded_trace):
    """Scopes and ``None`` sum to the op line's summed leaf durations, and a
    like-named instruction of another module is not billed to the scan."""
    from jax.profiler import ProfileData

    (plane,) = [p for p in ProfileData.from_file(recorded_trace).planes
                if p.name.startswith("/device:TPU:")]
    lines = {line.name: line for line in plane.lines}
    leaves = [(ev.name, float(ev.duration_ns) / 1e9) for ev in lines["XLA Ops"].events
              if not device_scopes._is_container(ev.name)]
    total = sum(sec for _, sec in leaves)
    names = sorted({name.partition(" = ")[0] for name, _ in leaves})
    # %copy.1 runs in the trace under jit_convert_element_type only
    assert "%copy.1" in names
    scopes = ("gradient", "eval", None)
    table = {"module": "jit_run_scan", "rows": [
        {"head": f"{name} = f32[]", "scope": scopes[k % 3], "also": []}
        for k, name in enumerate(names)]}
    got = device_scopes.device_time_by_scope(recorded_trace, table)
    assert set(got) <= {"gradient", "eval", None}
    assert sum(got.values()) == pytest.approx(total, rel=1e-12)
    assert got["gradient"] > 0 and got["eval"] > 0
    # bill %copy.1 to a scope of its own: it stays outside the scan's stretches
    table["rows"] = [dict(row, scope="update") if row["head"].startswith("%copy.1 =")
                     else row for row in table["rows"]]
    again = device_scopes.device_time_by_scope(recorded_trace, table)
    assert "update" not in again
    assert sum(again.values()) == pytest.approx(total, rel=1e-12)
    # another module's table bills nothing of this trace to a scope
    other = device_scopes.device_time_by_scope(
        recorded_trace, dict(table, module="jit_something_else"))
    assert other == {None: pytest.approx(total, rel=1e-12)}


def run_rooted(cfg, ds, **kw):
    tracer = Tracer()
    with tracer.activate():
        jax_backend.run(cfg, ds, 0.0, **kw)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return root["args"]


def test_the_root_names_its_program_and_the_table_waits_to_be_asked(monkeypatch):
    cfg = cfg_of("dsgd_ring_logistic")
    ds = generate_synthetic_dataset(cfg)
    built = []
    real = device_scopes.scope_table
    monkeypatch.setattr(
        device_scopes, "scope_table", lambda c: built.append(c) or real(c))
    cache = serving_cache.ExecutableCache()
    miss = run_rooted(cfg, ds, executable_cache=cache)
    hit = run_rooted(cfg, ds, executable_cache=cache)
    assert (miss["cache"], hit["cache"]) == ("miss", "hit")
    assert miss["program"] == hit["program"] and isinstance(miss["program"], str)
    (entry,) = cache._entries.values()
    sizes = device_scopes.memory(entry.executable)
    assert miss["temp_bytes"] == hit["temp_bytes"] == sizes["temp_size_in_bytes"]
    assert sizes["temp_size_in_bytes"] == (
        entry.executable.memory_analysis().temp_size_in_bytes)
    # the cache's estimate is the same one answer, summed
    assert entry.est_bytes == (sum(sizes.values()) or serving_cache.FALLBACK_ENTRY_BYTES)
    # two runs, nobody asked: no compiled text was read
    assert built == []
    table = device_scopes.table_for(miss["program"])
    assert built == [entry.executable]
    assert device_scopes.table_for(hit["program"]) is table and len(built) == 1
    assert {"gradient", "eval"} <= {row["scope"] for row in table["rows"]}
    assert device_scopes.table_for("no-such-program") is None


def test_with_the_cache_off_the_last_programs_are_held_here():
    cfg = cfg_of("dsgd_ring_softmax")
    ds = generate_synthetic_dataset(cfg)
    args = run_rooted(cfg, ds, executable_cache=False)
    assert args["cache"] == "off" and args["temp_bytes"] >= 0
    table = device_scopes.table_for(args["program"])
    assert table is not None and table["module"] == "jit_seg_scan"
    assert len(device_scopes._held) <= device_scopes.HELD_PROGRAMS


def test_memory_asks_xla_once_an_executable():
    class Analysis:
        temp_size_in_bytes = 7
        argument_size_in_bytes = 5
        output_size_in_bytes = 3
        generated_code_size_in_bytes = None

    class Executable:
        asked = 0

        def memory_analysis(self):
            self.asked += 1
            return Analysis()

    exe = Executable()
    assert serving_cache.estimate_executable_bytes(exe) == 15
    assert device_scopes.memory(exe)["temp_size_in_bytes"] == 7
    assert device_scopes.note_program(exe, held_elsewhere=True)["temp_bytes"] == 7
    assert exe.asked == 1
    # no analysis at all: the cache falls back, as it always did
    assert serving_cache.estimate_executable_bytes(object()) == (
        serving_cache.FALLBACK_ENTRY_BYTES)


def test_the_scans_compile_keys_the_persistent_cache_by_its_metadata():
    """JAX's persistent compilation cache strips debug information from its
    key, and a scope is debug information: without this an executable cached
    by a program without scopes is handed to the one with them."""
    flag = "jax_compilation_cache_include_metadata_in_key"

    class Lowered:
        def compile(self):
            return getattr(jax.config, flag)

    before = getattr(jax.config, flag)
    assert device_scopes.compile_keeping_scopes(Lowered()) is True
    assert getattr(jax.config, flag) == before

    class Broken:
        def compile(self):
            raise RuntimeError("no")

    with pytest.raises(RuntimeError):
        device_scopes.compile_keeping_scopes(Broken())
    assert getattr(jax.config, flag) == before


def test_the_shard_visit_is_billed_to_gradient(monkeypatch):
    """``forward`` = ``fused`` (ISSUE 41): the visit's call is built inside
    the eval, under ``dopt.gradient``; the innermost scope is an
    instruction's, so in the table of the program the root names every row
    the kernel left (here its interpreted body) says ``gradient``, and the
    batch weights drawn for it ``sampling``: none of it falls to ``eval`` or
    to no scope."""
    monkeypatch.setattr(
        jax_backend, "_visit_is_fused", lambda carried, X: bool(carried))
    cfg = cfg_of("dsgd_ring_logistic")
    args = run_rooted(cfg, generate_synthetic_dataset(cfg), executable_cache=False)
    assert args["forward"] == "fused"
    table = device_scopes.table_for(args["program"])
    scope_by_name = {row["head"].split(" = ")[0]: row["scope"] for row in table["rows"]}
    compiled = device_scopes._programs[args["program"]]["executable"]()
    visited = [
        ins[0] for ins in map(device_scopes._instruction, compiled.as_text().splitlines())
        if ins is not None and "glm_shard_visit" in ins[4] and ins[0] in scope_by_name
    ]
    assert visited and {scope_by_name[name] for name in visited} == {"gradient"}
