"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is a closed loop of experiments: after set-up and one warm-up call it
calls the program's normal entry point back to back — same configuration,
same seed, so the process's executable cache serves every call — until
``--seconds`` have passed, and lets the last call finish. ``--trace 1``
instead runs the mix's ``trace_calls`` calls under the profiler and reports
the per-layer metrics. Then, with the window closed, the plain reference
follows the first ``check_iterations`` of the same experiment and
``benchmark/compare.py`` decides ``correct`` from the program's rows up to there.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in ``BENCHMARK.json``; nothing
here names a cell. Without a TPU, or with another number of chips than the
cell asks for, the run exits non-zero and prints no result (``--rehearse``,
for the tests, allows the CPU at the files' ``rehearse`` sizes and marks the
line ``"rehearsal": true``).
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)  # first, whatever PYTHONPATH holds: this checkout's files


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(bench, workload, rehearse):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        for section, values in config.get("rehearse", {}).items():
            config[section].update(values)
        traffic.update(traffic.get("rehearse", {}))
    return cell, config, traffic


def load_reader(metric_name):
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def configure_jax(rehearse):
    """The persistent compile cache at a fixed path inside the checkout
    (unless whoever launched the process owns it), before the first compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and not rehearse:
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax


def device_block(jax, chips, rehearse):
    devices = jax.devices()
    backend = jax.default_backend()
    if not rehearse and (backend != "tpu" or len(devices) != chips):
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chip(s); JAX reports backend "
            f"{backend!r} with {len(devices)} device(s) - refusing to run")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(jax, rehearse):
    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if peaks:
        return max(peaks)
    if rehearse:
        return 1  # the CPU backend keeps no such count; the line is marked
    raise SystemExit("benchmark: the device reports no peak_bytes_in_use")


def gate_failures(result, traffic):
    """Why this experiment counts as failed, or []."""
    import numpy as np

    gates = traffic["gates"]
    hist = result.history
    why = []
    obj = np.asarray(hist.objective)
    cons = np.asarray(hist.consensus_error)
    if obj.shape != (int(traffic["n_iterations"]) // int(traffic["eval_every"]),):
        why.append(f"objective has shape {obj.shape}")
    elif not (np.all(np.isfinite(obj)) and np.all(np.isfinite(cons))
              and np.all(np.isfinite(result.final_models))):
        why.append("non-finite objective, consensus or model")
    else:
        for name, last in (("objective", obj[-1]), ("consensus", cons[-1])):
            if not last < gates[name + "_below"]:
                why.append(f"final {name} {last} not below {gates[name + '_below']}")
    return why


def produced_of(result):
    return {
        "objective": result.history.objective,
        "consensus": result.history.consensus_error,
        "final_models": result.final_models,
    }


def same_produced(a, b):
    import numpy as np

    return all(np.array_equal(a[k], b[k]) for k in a)


def run_calls(jax, program, cfg, dataset, traffic, *, seconds=None, n_calls=None):
    """The closed loop. Returns (call facts, distinct outputs, failures,
    window wall seconds)."""
    calls, distinct, failed = [], [], 0
    T = int(traffic["n_iterations"])
    begin = time.perf_counter()
    while True:
        now = time.perf_counter()
        if seconds is not None and now - begin >= seconds:
            break
        if n_calls is not None and len(calls) + failed >= n_calls:
            break
        with jax.profiler.TraceAnnotation(f"bench.call.{len(calls) + failed}"):
            t1 = time.perf_counter()
            try:
                result = program.run_experiment(cfg, dataset)
            except Exception as err:  # an experiment that raises is a failed one
                say(f"[window] experiment raised: {type(err).__name__}: {err}")
                failed += 1
                continue
            wall = time.perf_counter() - t1
        why = gate_failures(result, traffic)
        if why:
            say(f"[window] experiment failed its gates: {'; '.join(why)}")
            failed += 1
            continue
        calls.append({"wall_s": wall, "scan_s": T / result.history.iters_per_second,
                      "iterations": T})
        out = produced_of(result)
        if not any(same_produced(out, seen) for seen in distinct):
            distinct.append(out)
        del result, out
    return calls, distinct, failed, time.perf_counter() - begin


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU allowed, rehearsal sizes, line marked")
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    # Whatever the program, a logger or a library writes to stdout goes to
    # stderr; the result line alone is written to the real stdout, last.
    sys.stdout.flush()
    real_stdout = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = load_cell(bench, args.workload, args.rehearse)

    from benchmark import compare, datasets, emit, program, trace_reduce

    jax = configure_jax(args.rehearse)
    device = device_block(jax, int(cell["chips"]), args.rehearse)
    say(f"[setup] {args.workload} seed={args.seed} trace={args.trace} "
        f"jax {jax.__version__} on {device['platform']} ({device['kind']}, {device['count']})")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device["kind"] not in peaks and not args.rehearse:
        raise SystemExit(f"benchmark: no published peaks for device kind {device['kind']!r}")

    program_seed = program.seed_for(args.seed)
    t = time.perf_counter()
    X, y, rows_per_worker = datasets.make(config, args.seed)
    say(f"[setup] data {X.shape} {X.dtype} in {time.perf_counter() - t:.2f} s")
    cfg, dataset = program.build(config, traffic, X, y, rows_per_worker, program_seed)

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        warm = program.run_experiment(cfg, dataset)
    say(f"[setup] warm-up call (compile or cache load, one experiment) "
        f"{time.perf_counter() - t:.2f} s; final objective {warm.history.objective[-1]:.6g}, "
        f"consensus {warm.history.consensus_error[-1]:.6g}")
    why = gate_failures(warm, traffic)
    if why:
        say(f"[setup] warm-up experiment failed its gates: {'; '.join(why)}")
    warm_out = produced_of(warm)
    del warm
    setup_s = time.perf_counter() - _PROCESS_START

    trace_summary = None
    if traced:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        # The Python tracer would add millions of host events: off. The
        # harness's own TraceAnnotation spans are host-tracer events.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            calls, distinct, failed, window_s = run_calls(
                jax, program, cfg, dataset, traffic, n_calls=int(traffic["trace_calls"]))
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        trace_summary = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        if trace_summary is None and not args.rehearse:
            raise SystemExit("benchmark: the trace holds no device plane")
        shutil.rmtree(os.path.join(ROOT, ".bench_trace"), ignore_errors=True)
        say(f"[trace] reduced in {time.perf_counter() - t:.2f} s")
    else:
        calls, distinct, failed, window_s = run_calls(
            jax, program, cfg, dataset, traffic, seconds=args.seconds)
    device["memory_peak_bytes"] = memory_peak(jax, args.rehearse)
    attempted = len(calls) + failed
    iterations = sum(c["iterations"] for c in calls)
    say(f"[window] {attempted} experiments ({failed} failed), {iterations} iterations "
        f"in {window_s:.3f} s; call walls "
        + " ".join(f"{c['wall_s']:.3f}" for c in calls[:12])
        + "; scan s " + " ".join(f"{c['scan_s']:.3f}" for c in calls[:12])
        + (" ..." if len(calls) > 12 else ""))
    say(f"[window] setup_s {setup_s:.3f}; memory_peak_bytes {device['memory_peak_bytes']}; "
        f"{len(distinct)} distinct output(s) over the window's experiments")

    # ---- correct: the reference follows the same experiment, window closed ----
    t = time.perf_counter()
    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    ref_out = reference.run(config, traffic, X, y, program_seed)
    say(f"[check] reference followed the first "
        f"{traffic.get('check_iterations', traffic['n_iterations'])} of "
        f"{traffic['n_iterations']} iterations in {time.perf_counter() - t:.2f} s")
    limits = config["limits"][cell["traffic"]]
    correct = not why and failed == 0 and bool(calls)
    outputs = [warm_out] + [d for d in distinct if not same_produced(d, warm_out)]
    for k, out in enumerate(outputs):
        say(f"[check] output {k} ({'warm-up and window' if k == 0 else 'window, differs from warm-up'})")
        correct = compare.judge(compare.numbers(out, ref_out), limits, say) and correct
    say(f"[check] correct = {correct}")

    # ---- the line ----
    values, section = {}, "per_layer" if traced else "end_to_end"
    units = emit.expected_metrics(bench, args.workload, traced)
    if traced:
        facts = {
            "calls": calls, "iterations": iterations, "window_s": window_s,
            "n_devices": device["count"],
            # a rehearsal has no chip: any entry will do to drive the readers
            "peaks": peaks.get(device["kind"]) or (args.rehearse and peaks["TPU v5 lite"]),
        }
        if trace_summary is None:  # rehearsal on the CPU: no device plane to read
            trace_summary = {"busy_s": sum(c["scan_s"] for c in calls), "device_ops": [],
                             "idle_gaps": []}
        for name in units:
            values[name] = load_reader(name)(trace_summary, facts, config)
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = window_s
        breakdown = {"device_ops": trace_summary["device_ops"],
                     "idle_gaps": trace_summary["idle_gaps"]}
    else:
        values = {"iters_per_s": iterations / window_s, "setup_s": setup_s}
        breakdown = None
    for name in units:
        say(f"[{section}] {name} = {values.get(name)} {units[name]}")
    line = emit.build(correct=correct, attempted=attempted, failed=failed, values=values,
                      units=units, device=device, breakdown=breakdown)
    if args.rehearse:
        line["rehearsal"] = True
    emit.emit(line, bench, args.workload, traced, out=real_stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
