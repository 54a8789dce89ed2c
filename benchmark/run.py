"""One run of one cell of BENCHMARK.json, in a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (boot, data from the seed, warm-up units; everything up to the
measured window) is timed as ``setup_s``. Then units of work run back to
back; a further one starts only if the time so far plus the median unit
still fits into ``--seconds``, and at least one always runs. After the
window the last unit's result is held against the cell's plain reference.
The last line of standard output is the result; everything else goes to
standard error. ``--trace 1`` runs a shorter window under the profiler and
reports the per-layer metrics instead of the end-to-end ones.

Nothing here knows a cell, a configuration or a metric by name: the cell's
entry in BENCHMARK.json names a configuration file and a traffic mix, the
mix names its driver, ``checks/<cell>.json`` names the reference, and each
per-layer metric has a file under ``layer_metrics/`` that names its
reduction. See README.md.
"""

import time

T0 = time.perf_counter()        # before the heavy imports: they are set-up

import argparse
import contextlib
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TRACE_SECONDS = 10.0            # a traced window holds whole units up to this


def say(**fields):
    print("# " + json.dumps(fields, default=str), file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base, over):
    """``base`` with ``over`` laid on top, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def in_background(fn, **kwargs):
    """Start ``fn(**kwargs)`` in a daemon thread (a refused run exits without
    waiting for it); returns the function that waits for its value."""
    box = {}

    def work():
        try:
            box["value"] = fn(**kwargs)
        except BaseException as e:      # handed to the caller of result()
            box["error"] = e

    thread = threading.Thread(target=work, daemon=True)
    thread.start()

    def result():
        thread.join()
        if "error" in box:
            raise box["error"]
        return box["value"]
    return result


def resolve_cell(manifest, workload, rehearse):
    """The cell's entry, its configuration and traffic mix (tiny under
    ``rehearse``) and its check."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = read_json(ROOT, config["file"])
    mix = read_json(HERE, "traffic", cell["traffic"] + ".json")
    check = read_json(HERE, "checks", workload + ".json")
    if rehearse:
        cfg, mix, check = (merged(d, d.get("rehearse", {})) for d in (cfg, mix, check))
    return cell, cfg, mix, check


def metrics_of(manifest, group, workload):
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


def run_window(driver, state, seconds, annotate):
    """Units back to back for ``seconds``. Returns (units completed, units
    that raised, wall seconds from the first start to the last end, the last
    result)."""
    walls, failed, last = [], 0, None
    start = time.perf_counter()
    while True:
        t = time.perf_counter()
        try:
            with annotate(driver.ANNOTATION):
                last = driver.unit(state)
        except Exception as e:      # a failed unit is counted, the window goes on
            failed += 1
            say(unit_failed=repr(e))
        walls.append(time.perf_counter() - t)
        elapsed = time.perf_counter() - start
        if elapsed + statistics.median(walls) > seconds or failed == len(walls) >= 3:
            return len(walls) - failed, failed, elapsed, last


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds; prints no result line")
    args = ap.parse_args(argv)

    manifest = read_json(ROOT, "BENCHMARK.json")
    cell, cfg, mix, check = resolve_cell(manifest, args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None else manifest["run_seconds"]

    # the host draws the data while JAX, the package and the chip come up
    # (the generators need numpy alone; drivers._common would import JAX)
    generator = importlib.import_module(f"benchmark.datagen.{cfg['data']['generator']}")
    data = in_background(generator.generate, seed=args.seed, **cfg["data"]["args"])

    # the compile cache: where the environment says, else at a fixed path in
    # the checkout (the path is part of the cache's key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    import jax
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: needs {cell['chips']} tpu device(s), JAX found {devices}",
              file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    peaks = read_json(HERE, "peaks.json")
    if not args.rehearse and devices[0].device_kind not in peaks:
        print(f"benchmark: no peaks for device_kind {devices[0].device_kind!r} in peaks.json",
              file=sys.stderr)
        return 2

    import h2o3_tpu
    from h2o3_tpu.runtime import observability as obs
    from benchmark.drivers import _common
    h2o3_tpu.init(devices=devices)
    say(phase="init", t=time.perf_counter() - T0, devices=[str(d) for d in devices],
        cache=os.environ["JAX_COMPILATION_CACHE_DIR"])

    driver = _common.load("drivers", mix["driver"])
    state = driver.set_up(cfg, mix, args.seed, data())
    say(phase="set_up", t=time.perf_counter() - T0, rows=state["rows"])
    for i in range(cfg.get("warmup_units", 1)):
        t = time.perf_counter()
        driver.unit(state)
        say(phase="warm_up", unit=i, s=time.perf_counter() - t)
    setup_s = time.perf_counter() - T0

    line = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}}
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host function events slow the host path
        before = obs.metrics_wire()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                units, failed, elapsed, last = run_window(
                    driver, state, min(seconds, TRACE_SECONDS), jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        after = obs.metrics_wire()
        try:
            from benchmark import reduce as R
            trace = R.Trace.from_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window = trace.window()
        ctx = {"trace": trace, "window": window, "window_s": (window[1] - window[0]) / 1e9,
               "units": max(units, 1), "counters_before": before, "counters_after": after,
               "state": state, "peaks": peaks.get(devices[0].device_kind)}
        say(phase="traced", units=units, host_s=elapsed, window_s=ctx["window_s"],
            covers=f"{elapsed:.1f} s of the {seconds:g} s a --trace 0 run measures")
        for m in metrics_of(manifest, "per_layer", args.workload):
            spec = read_json(HERE, "layer_metrics", m["name"] + ".json")["reduction"]
            value = _common.load("reductions", spec["kind"]).reduce(spec, ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if trace.devices:
            line["device"].update(busy_s=R.busy_seconds(trace, window), window_s=ctx["window_s"])
            line["breakdown"] = R.breakdown(trace, window)
    else:
        units, failed, elapsed, last = run_window(
            driver, state, seconds, lambda name: contextlib.nullcontext())
        values = {"setup_s": setup_s, **(driver.metrics(state, units, elapsed) if units else {})}
        for m in metrics_of(manifest, "end_to_end", args.workload):
            if m["name"] in values:
                line["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    say(phase="window", units=units, failed=failed, elapsed=elapsed, setup_s=setup_s)

    ok, detail = False, {"check": "no unit completed"}
    if last is not None:
        t = time.perf_counter()
        ok, detail = _common.load("refs", check["ref"]).check(state, last, check["tol"])
        detail["check_s"] = time.perf_counter() - t
    say(phase="check", ref=check["ref"], ok=ok, **detail)
    stats = [d.memory_stats() or {} for d in devices]
    line["device"]["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0) for s in stats)
    line.update(correct=bool(ok), attempted=units + failed, failed=failed)
    say(phase="done", t=time.perf_counter() - T0, memory_peak_bytes=line["device"]["memory_peak_bytes"],
        bytes_limit=stats[0].get("bytes_limit"))
    if args.rehearse:
        print(json.dumps({"rehearsal": line}))      # a shape to look at, never a result
        return 0
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
