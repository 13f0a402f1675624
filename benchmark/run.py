#!/usr/bin/env python3
"""One cell of the benchmark, once, in one new process.

    python benchmark/run.py --workload <config>.<traffic> --seed <n>
                            --seconds <s> --trace <0|1>

Builds the cell through the program's normal entry points, seeds its
state from ``--seed``, warms up the one call length the window uses,
measures whole units for ``--seconds``, holds probed blocks of the
field to the plain float64 reference, and prints ONE JSON object as the
last line of stdout (``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``).  Everything else it
has to say goes on earlier lines.

It fails -- non-zero, no result line -- without a TPU whose
``device_kind`` is in ``peaks.json`` and without the chips the cell
asks for.  ``--tiny`` with ``JAX_PLATFORMS=cpu`` set by name is the dry
run for tests: toy domain, Pallas interpreted, every line labelled, no
number of it a device number.

Everything that belongs to one configuration, traffic mix, stencil or
per-layer metric is a file found by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json`` (naming a kind in
``traffic_kinds/<kind>.py``), ``stencils/<stencil>.py``,
``metrics/<metric>.py``.
"""

import time

_T0 = time.perf_counter()           # set-up counts from process start

import argparse                     # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402
import types                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# this directory (check, trace_reduce, metric_alias), the traffic
# kinds' helpers, and the checkout that holds the system under test
sys.path[:0] = [p for p in (HERE, os.path.join(HERE, "traffic_kinds"), ROOT)
                if p not in sys.path]

import check                        # noqa: E402
import trace_reduce                 # noqa: E402

TRACED_UNITS = 3        # units of the window that the profiler sees
WARM_UNITS = 2          # the compiling call and one steady one

_label = ""


def say(msg: str) -> None:
    print(_label + msg, flush=True)


def fail(msg: str):
    raise SystemExit(f"benchmark: FAILED: {msg}")


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` by file name (names may hold
    dots) -- how a later PR's files are found without a registry."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        fail(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        fail(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, seed: int, tiny: bool):
    """The cell, from ``BENCHMARK.json`` and the files it names."""
    bench = load_json(ROOT, "BENCHMARK.json")
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        fail(f"no workload '{workload}' in BENCHMARK.json")
    row = rows[0]
    cfg_row = [c for c in bench["configs"] if c["name"] == row["config"]]
    if not cfg_row:
        fail(f"no config '{row['config']}' in BENCHMARK.json")
    config = load_json(ROOT, cfg_row[0]["file"])
    traffic = load_json(HERE, "traffic", row["traffic"] + ".json")
    cell = types.SimpleNamespace(
        name=workload, row=row, bench=bench, config=config,
        traffic=traffic, seed=seed, tiny=tiny, chips=int(row["chips"]))
    key = "tiny_domain" if tiny else "domain"
    cell.domain = [int(n) for n in traffic.get(key, config[key])]
    cell.stencil = load_module("stencils", config["stencil"])
    cell.block = 4 if tiny else int(config["probe_block"])
    cell.fill = check.draw_fill(seed, cell.domain, config["ranks"],
                                cell.block)
    cell.kind = load_module("traffic_kinds", traffic["kind"]).Kind(cell)

    def reports(metric):
        return "workloads" not in metric or workload in metric["workloads"]
    cell.end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    cell.per_layer = [m for m in bench["per_layer"] if reports(m)]
    return cell


def find_device(cell):
    """The device this run may use, or no run at all."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    peaks = load_json(HERE, "peaks.json")
    if cell.tiny:
        if d0.platform != "cpu":
            fail(f"--tiny is the CPU dry run, JAX found '{d0.platform}'")
        peak = None
    else:
        if d0.platform != "tpu":
            fail(f"no TPU: JAX found platform '{d0.platform}' "
                 f"({d0.device_kind}); the benchmark does not fall back")
        if d0.device_kind not in peaks:
            fail(f"device kind '{d0.device_kind}' is not in "
                 f"benchmark/peaks.json ({', '.join(sorted(peaks))})")
        peak = peaks[d0.device_kind]
    if len(devs) < cell.chips:
        fail(f"the cell asks for {cell.chips} chip(s), JAX sees "
             f"{len(devs)}")
    return devs[:cell.chips], peak


class Compiles:
    """Programs handed to the compiler, from JAX's own monitoring
    events: ``lowered`` counts every one, cached or not; ``compiled``
    those the backend really compiled (persistent-cache misses)."""

    def __init__(self):
        self.lowered = self.compiled = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def peak_bytes(devs):
    stats = [d.memory_stats() for d in devs]
    if any(s is None for s in stats):
        return None
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def read_probes(cell):
    return {name: cell.kind.read_box(lo, [a + cell.block for a in lo])
            for name, lo in check.probes(cell.domain, cell.fill,
                                         cell.block).items()}


def run_window(cell, seconds: float, trace_dir, run):
    """Whole units, back to back, from the first unit's start until a
    unit ends at or after ``seconds``.  With ``trace_dir`` the first
    ``TRACED_UNITS`` run under the profiler, each in the kind's span;
    what lies between two spans is ``bench.between``."""
    import jax
    kind = cell.kind
    units = []

    def one():
        t0 = time.perf_counter()
        try:
            steps = kind.run_unit()
        except Exception as e:      # a failed unit is counted, not fatal
            say(f"unit failed: {type(e).__name__}: {e}")
            run.failed += 1
            steps = 0
        units.append((t0, time.perf_counter(), steps))

    def going():
        return not units or (units[-1][1] - units[0][0] < seconds
                             and run.failed < 3)

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            while going() and len(units) < TRACED_UNITS:
                with jax.profiler.TraceAnnotation(kind.SPAN):
                    one()
        finally:
            jax.profiler.stop_trace()
        run.steps_traced = sum(u[2] for u in units)
    while going():
        one()
    return units


def main(argv=None) -> int:
    global _label
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at toy size (JAX_PLATFORMS=cpu)")
    ap.add_argument("--control", action="store_true",
                    help="put the reference computed in bfloat16 in the "
                         "program's place: `correct` must come out false")
    args = ap.parse_args(argv)
    if args.tiny:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            fail("--tiny is refused unless JAX_PLATFORMS=cpu is set by "
                 "name")
        _label = "cpu dry-run: "

    cell = load_cell(args.workload, args.seed, args.tiny)
    devs, peak = find_device(cell)
    say(f"cell {cell.name}: {cell.config['stencil']} r="
        f"{cell.config['radius']} domain {cell.domain} chips "
        f"{cell.chips} seed {args.seed} fill {json.dumps(cell.fill)}")
    compiles = Compiles()
    run = types.SimpleNamespace(
        cell=cell, peak=peak, failed=0, steps_traced=0, trace=None,
        points=float(cell.domain[0] * cell.domain[1] * cell.domain[2]))
    cell.scratch = tempfile.mkdtemp(prefix="yask_bench_")
    try:
        return measure(args, cell, devs, compiles, run)
    finally:
        shutil.rmtree(cell.scratch, ignore_errors=True)


def warm_up(cell, run, compiles):
    """The first unit compiles (or loads the cache) and is the one the
    reference follows; one steady unit more and nothing else is
    warmed.  Returns the steps that first unit advanced and the probe
    blocks read after it."""
    kind = cell.kind
    run.first_call_s = []
    blocks = steps = None
    for i in range(WARM_UNITS):
        t0 = time.perf_counter()
        n = kind.run_unit()
        run.first_call_s.append(time.perf_counter() - t0)
        if i == 0:
            steps, blocks = n, read_probes(cell)
    say(f"warm-up units {[round(s, 3) for s in run.first_call_s]} s; "
        f"set-up lowered {compiles.lowered} programs, the backend "
        f"compiled {compiles.compiled} (persistent-cache misses)")
    plan = kind.plan()
    if plan is not None:
        say("plan: " + json.dumps(plan, default=str))
        if plan.get("interpret") != cell.tiny:
            fail(f"pallas kernel interpret={plan.get('interpret')}")
    return steps, blocks


def read_trace(cell, run, trace_dir):
    """What the per-layer readers take from the traced slice."""
    xplanes = [os.path.join(base, f)
               for base, _dirs, files in os.walk(trace_dir)
               for f in files if f.endswith(".xplane.pb")]
    if not xplanes:
        fail("the profiler wrote no .xplane.pb")
    events = trace_reduce.load_xplane(xplanes[0], dry_run=cell.tiny)
    run.trace = trace_reduce.reduce(events, max(run.steps_traced, 1))
    if not run.trace:
        fail("the trace holds no device operation inside the spans")
    run.need_bytes_pp = cell.stencil.need_bytes_per_point_step(
        int(cell.traffic.get("wf_steps", cell.config["wf_steps"])))


def measure(args, cell, devs, compiles, run) -> int:
    cell.kind.build()
    say(f"built and seeded in {time.perf_counter() - _T0:.1f} s since "
        f"process start")
    steps_checked, device_blocks = warm_up(cell, run, compiles)
    limit = float(cell.config["tolerance"])
    compare = (cell.stencil, cell.config, cell.domain, cell.block,
               steps_checked, cell.fill)
    run.setup_s = time.perf_counter() - _T0
    run.units, run.compiles_in_window, run.peak_bytes = [], None, None
    if args.control:
        ok = check.compare(*compare, None, limit, say, control=True)
    else:
        trace_dir = (os.path.join(cell.scratch, "trace")
                     if args.trace else None)
        lowered = compiles.lowered
        run.units = run_window(cell, args.seconds, trace_dir, run)
        run.compiles_in_window = compiles.lowered - lowered
        run.peak_bytes = peak_bytes(devs)
        final = read_probes(cell)
        t0 = time.perf_counter()
        ok = check.compare(*compare, device_blocks, limit, say)
        say(f"reference took {time.perf_counter() - t0:.1f} s "
            f"(after the window, not in setup_s)")
        if not check.alive(final):
            say("final field: non-finite or all zero where probed")
            ok = False
        ok = ok and not run.failed
        if args.trace:
            read_trace(cell, run, trace_dir)

    done = sorted(b - a for a, b, n in run.units if n > 0)
    if done:
        say(f"window: {len(done)} whole units in "
            f"{run.units[-1][1] - run.units[0][0]:.3f} s (unit seconds: "
            f"min {done[0]:.4f} median {statistics.median(done):.4f} max "
            f"{done[-1]:.4f}); failed {run.failed}; compilations in "
            f"window {run.compiles_in_window}")

    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in (wanted if run.units else []):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
            say(f"metric {m['name']} = {float(value)!r} {m['unit']}")

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.peak_bytes or peak_bytes(devs) or 0}
    result = {"correct": bool(ok), "attempted": len(run.units),
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    if cell.tiny:
        result["dry_run"] = True
    cell.kind.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
