"""Run one cell of the benchmark once and print its result.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is built from files found by name: ``BENCHMARK.json`` gives the
cell's configuration and traffic mix, ``configs/<config>.json`` and
``traffic/<mix>.json`` hold them, the mix names its generator
(``generators/<kind>.py``) and its runner (``runners/<runner>.py``),
``cells/<workload>.json`` holds what belongs to the pair (the deployment's
sizes, the limits of the correctness check), and every metric that lists the
cell is read by ``end_to_end/<metric>.py`` or ``layer_metrics/<metric>.py``.
Nothing in this file names a cell, a configuration, a mix or a metric.

Without the accelerator the cell asks for it exits non-zero and prints no
result.  ``--rehearse`` is the benchmark's own switch for the CPU: it applies
the files' ``rehearse`` overrides (tiny shapes), prints no device metric and
always ``"correct": false``; what the check found is under ``rehearsal``.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_MARKER = "bench.trace_window"


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, rehearse):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    sizes = json.loads((HERE / "cells" / f"{name}.json").read_text())
    if rehearse:
        config = merge(config, config.get("rehearse", {}))
        mix = merge(mix, mix.get("rehearse", {}))
        sizes = merge(sizes, sizes.get("rehearse", {}))
    return manifest, cell, config, mix, sizes


def find_reader(directory, name):
    """``<name>.py``; a metric split by the end-to-end metric it moves
    (``x.y.<cell part>``) shares the reader of its stem, ``x.y.py``."""
    parts = name.split(".")
    while parts:
        path = HERE / directory / (".".join(parts) + ".py")
        if path.exists():
            return path
        parts.pop()
    raise SystemExit(f"no reader for metric {name!r} under {directory}/")


class Tracer:
    """Traces a few seconds in the middle of the window from a thread of its
    own, under one host annotation that marks the traced part."""

    def __init__(self, enabled, directory):
        self.enabled, self.directory = enabled, str(directory)
        self.thread, self.span = None, None

    def start(self, t0, seconds):
        if not self.enabled:
            return
        lead, length = 0.3 * seconds, min(5.0, 0.4 * seconds)

        def work():
            import jax

            time.sleep(max(0.0, t0 + lead - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(TRACE_MARKER):
                time.sleep(length)
            b = time.perf_counter()
            jax.profiler.stop_trace()
            self.span = (a, b)

        shutil.rmtree(self.directory, ignore_errors=True)
        self.thread = threading.Thread(target=work, daemon=True)
        self.thread.start()

    def stop(self):
        if self.thread is not None:
            self.thread.join()

    def summary(self):
        """The trace, reduced; None where nothing was traced or no device
        operation was found."""
        if self.span is None:
            return None
        from benchmark.lib import trace_reduce

        trace = trace_reduce.load(trace_reduce.find_xplane(self.directory))
        found = trace_reduce.device_summary(trace, TRACE_MARKER)
        if found is not None:
            found["host_span"] = self.span
        return found


def find_devices(cell, rehearse):
    """The devices the cell asked for, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        return found
    if found["platform"] != "tpu" or found["count"] < cell["chips"]:
        print(f"this cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{found}", file=sys.stderr)
        raise SystemExit(3)
    from benchmark.lib import peaks

    peaks.peak(found["kind"], "hbm_bytes_per_s")  # unknown kind: an error
    found["count"] = cell["chips"]
    return found


def breakdown(summary, run):
    from benchmark.lib import trace_reduce

    plane = max(summary["planes"].values(), key=lambda p: p["busy_s"])
    a, b = summary["host_span"]
    records = run.get("records", [])

    def in_flight(t_ns):
        t = a + (t_ns - summary["t0"]) / 1e9
        return sum(1 for r in records
                   if r["sent"] <= t and r.get("done", t) >= t)

    gaps = {}
    for start, dur, before in trace_reduce.idle_gaps(
            plane["ops"], summary["t0"], summary["t1"]):
        mid = start + dur // 2
        if records and in_flight(mid) == 0:
            label = "no request in flight"
        else:
            label = ("host loop, unattributed, after "
                     + trace_reduce.short_name(before))
        gaps[label] = gaps.get(label, 0.0) + dur / 1e9
    return {"device_ops": [[n, s] for n, s in
                           trace_reduce.top_ops(plane["ops"], 10)],
            "idle_gaps": sorted(([n, s] for n, s in gaps.items()
                                 if s >= 1e-4), key=lambda g: -g[1])[:10]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    metavar="FILE.PATH=JSON",
                    help="the benchmark's own switch for a sweep: change "
                         "one value of the cell's files for this run, e.g. "
                         "mix.params.rate_per_s=6; the result line names "
                         "every override and is no result of the cell")
    ap.add_argument("--control", default=None,
                    help="also read the correctness control: the "
                         "reference with its weights in this lower "
                         "precision (fp8, int8, or both with a comma) over "
                         "the same prompts and tokens; the driver's runs "
                         "never pass it.  The program's own lower "
                         "precisions are read with --override "
                         'sizes.engine.loader.quantize=\'"int8"\'')
    ap.add_argument("--records", default=None, metavar="FILE",
                    help="also write one line of JSON per request (times "
                         "and sizes, no tokens) for looking at a run by hand")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    manifest, cell, config, mix, sizes = load_cell(args.workload,
                                                   args.rehearse)
    import kubeflow_tpu  # noqa: F401  (the system under test: fail early)

    files = {"config": config, "mix": mix, "sizes": sizes}
    for item in args.override:
        path, _, value = item.partition("=")
        *parents, last = path.split(".")
        node = files
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = json.loads(value)

    device = find_devices(cell, args.rehearse)

    def log(text):
        print(text, flush=True)

    tracer = Tracer(bool(args.trace), ROOT / ".bench_trace" / cell["name"])
    runner = load_module(HERE / "runners" / f"{mix['runner']}.py")
    generator = load_module(HERE / "generators" / f"{mix['kind']}.py")
    run = runner.run({
        "cell": cell, "config": config, "mix": mix, "sizes": sizes,
        "args": args,
        "generator": generator, "tracer": tracer, "log": log,
        "process_start": PROCESS_START, "device": device, "root": ROOT,
        "control": args.control})
    run["device"] = device

    if args.records:
        t0 = run["window"]["t0"]
        with open(args.records, "w") as f:
            for r in run.get("records", []):
                f.write(json.dumps({
                    "due": None if r["due"] is None else r["due"] - t0,
                    "sent": r["sent"] - t0,
                    "first": None if r["first"] is None else r["first"] - t0,
                    "last": None if r["last"] is None else r["last"] - t0,
                    "done": r["done"] - t0, "tokens": len(r["tokens"]),
                    "prompt": len(r["request"]["prompt"]),
                    "max_new": r["request"]["max_new"],
                    "cut": bool(r.get("cut")), "error": r["error"],
                    "tags": r["request"]["tags"]}) + "\n")
        if run.get("gaps"):
            with open(args.records + ".gaps.json", "w") as f:
                json.dump(run["gaps"], f)

    # End-to-end metrics come from the runner's records and the host clock,
    # per-layer metrics from readers found by name.
    which = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    summary = tracer.summary() if args.trace else None
    run["trace"] = summary
    for m in manifest[which]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        reader = find_reader(
            "layer_metrics" if args.trace else "end_to_end", m["name"])
        value = load_module(reader).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"metric {m['name']} = {value!r} {m['unit']}")

    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": dict(device,
                             memory_peak_bytes=run["memory_peak_bytes"])}
    if args.override:
        result["overrides"] = args.override
    if args.rehearse:
        # A CPU run gives no number under a device metric's name.
        result["rehearsal"] = {"correct": run["correct"], "metrics": metrics}
        result["correct"], result["metrics"] = False, {}
        result["device"].pop("memory_peak_bytes")
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = breakdown(summary, run)
    elif args.trace and not args.rehearse:
        print("traced run found no device operation", file=sys.stderr)
        raise SystemExit(4)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # Daemon threads of a closed engine must not hold the exit.
    os._exit(code)
