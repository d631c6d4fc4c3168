#!/usr/bin/env python3
"""run.py — THE command of the benchmark: one cell, one process, one line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It checks `BENCHMARK.json` (`check_manifest.py`), finds the cell's files
*by name, by listing directories* — `workloads/<cell>.json`, then
`configs/<config>.json`, `traffic/<traffic>.json`, `jobs/<job>.py`, and
every `layer_metrics/*.json` whose `jobs` hold that job — hands them to
the job module's `run(ctx)`, and prints the account of the run followed,
as the last line of standard output, by the contract's one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with `--trace 1`).  With `--trace 0` the metrics are the cell's
end-to-end ones, taken with the profiler off; with `--trace 1` a few
seconds of the steady window are wrapped in `jax.profiler.trace`, the
`.xplane.pb` is reduced by `reducers/xplane.py`, and the metrics are the
cell's per-layer ones.

No chip, no result: unless jax finds a TPU that `peaks.py` knows, with as
many chips as the cell asks for, the process exits non-zero and prints no
JSON.  `--rehearse-cpu` runs the same code at the tiny sizes of each
file's `rehearse` block on the CPU; every line of its account is labelled,
and its last line names the CPU as the device and carries `null` for every
value: a CPU number is never written under the name of a device metric.

Nothing here is specific to a cell, a configuration or a metric: a later
PR adds files (see `README.md`) and never edits this one.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import contextlib                      # noqa: E402
import functools                       # noqa: E402
import glob                            # noqa: E402
import importlib.util                  # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0       # how much of the steady window a traced run wraps
_LABEL = "[CPU-REHEARSAL] "


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, loaded from its file (once): adding
    a file adds a job or a reducer."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metrics_for(job: str, cell: str) -> list:
    """The per-layer metric files that apply to this job and cell, found
    by listing the directory."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            lm = json.load(f)
        lm["name"] = os.path.splitext(os.path.basename(path))[0]
        if job in lm.get("jobs", ()) and cell in lm.get("workloads", [cell]):
            out.append(lm)
    return out


def with_rehearsal(d: dict, rehearse: bool) -> dict:
    """A file's values, with its `rehearse` block laid over them in a
    rehearsal (tiny sizes live beside the real ones, as data)."""
    out = {k: v for k, v in d.items() if k != "rehearse"}
    if rehearse:
        out.update(d.get("rehearse", {}))
    return out


class CompileCounter:
    """Counts, through jax's own monitoring events, every compile request
    that consulted the persistent cache, its hits, and every call of jax's
    compile step (which fires with the cache on or off, hit or miss).  The
    benchmark's own count: a window must move none of them."""

    def __init__(self):
        from jax._src import monitoring
        self.requests = self.hits = self.backend = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def count(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "compile_calls": self.backend}

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.count().items()}


class Context:
    """What a job gets: its files, the arguments, and the harness's
    services (printing, compile counts, the traced window, checks)."""

    def __init__(self, args, cell, workload, config, traffic, compiles):
        self.cell = cell
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse_cpu
        self.trace_seconds = min(TRACE_SECONDS, self.seconds / 2)
        self.compiles = compiles
        self.checks = []             # (ok, what)
        self.setup_s = None
        self.setup_parts = {}
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell)

    def say(self, msg: str) -> None:
        print(f"{_LABEL if self.rehearse else ''}{msg}", flush=True)

    def check(self, ok, what: str) -> bool:
        self.checks.append((bool(ok), what))
        self.say(f"check {'ok  ' if ok else 'FAIL'}: {what}")
        return bool(ok)

    def part(self, name: str, t0: float) -> float:
        """Record a part of set-up that began at `t0`; returns now."""
        now = time.perf_counter()
        self.setup_parts[name] = now - t0
        return now

    def window_opens(self) -> float:
        """The job calls this at the first measured step or request:
        everything before it is set-up."""
        now = time.perf_counter()
        self.setup_s = now - T_START
        return now

    @contextlib.contextmanager
    def profile(self):
        """Wrap the traced part of the window: starts jax's profiler, and
        opens the span `bench_window` that the reducer clips to."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # jax's own spans and ours only
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench_window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def xplane_path(self):
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (default: the "
                        "manifest's run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU, labelled; no device metric")
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="override a number of the workload file for this "
                        "run (a sweep for the knee); the driver never "
                        "passes it")
    p.add_argument("--keep-trace", default="",
                   help="copy the .xplane.pb of a traced run to this path")
    return p.parse_args(argv)


def fail(msg: str, code: int = 3):
    print(f"benchmark/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import check_manifest, peaks

    manifest_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(manifest_path):
        fail(f"no BENCHMARK.json beside {HERE}")
    faults = check_manifest.check_file(manifest_path)
    if faults:
        fail("BENCHMARK.json is refused:\n  " + "\n  ".join(faults), 2)
    with open(manifest_path) as f:
        manifest = json.load(f)
    cell = next((c for c in manifest["workloads"]
                 if c["name"] == args.workload), None)
    if cell is None:
        fail(f"--workload {args.workload!r} is not in BENCHMARK.json "
             f"({[c['name'] for c in manifest['workloads']]})", 2)
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    rehearse = args.rehearse_cpu
    workload = with_rehearsal(load_json("workloads", cell["name"] + ".json"),
                              rehearse)
    for kv in args.set:
        k, _, v = kv.partition("=")
        workload[k] = json.loads(v)
        print(f"override: {k} = {workload[k]!r}", flush=True)
    config = with_rehearsal(load_json("configs", cell["config"] + ".json"),
                            rehearse)
    traffic = with_rehearsal(load_json("traffic", cell["traffic"] + ".json"),
                             rehearse)

    if rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    try:
        import paddle_tpu
    except ImportError as e:
        fail(f"paddle_tpu is not importable from {ROOT}: {e}")
    if os.path.dirname(os.path.dirname(
            os.path.realpath(paddle_tpu.__file__))) != os.path.realpath(ROOT):
        fail(f"paddle_tpu was imported from {paddle_tpu.__file__}, not from "
             f"the checkout {ROOT}")
    import jax
    compiles = CompileCounter()
    if not rehearse:
        # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
        from paddle_tpu.core import compile_cache
        cache_dir = compile_cache.configure()
    else:
        cache_dir = None          # a rehearsal leaves no cache behind
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse:
        if device["platform"] != "tpu":
            fail(f"jax found no accelerator (platform "
                 f"{device['platform']!r}): no chip, no result")
        if len(devs) < cell["chips"]:
            fail(f"the cell needs {cell['chips']} chips, jax sees "
                 f"{len(devs)}")
        peaks.peak_flops(device["kind"])      # raises on an unknown kind
    t_import = time.perf_counter()

    ctx = Context(args, cell["name"], workload, config, traffic, compiles)
    ctx.devices = devs[:cell["chips"]]
    ctx.device = device
    ctx.setup_parts["import_and_device"] = t_import - T_START
    ctx.say(f"cell {cell['name']}: config {cell['config']}, traffic "
            f"{cell['traffic']}, job {workload['job']}, {cell['chips']} "
            f"chip(s); seed {args.seed}, window {args.seconds:g} s, "
            f"trace {args.trace}")
    ctx.say(f"device {device}; compile cache {cache_dir}")
    job = load_module("jobs", workload["job"])
    result = job.run(ctx)
    if ctx.setup_s is None:
        fail("the job never opened its window (ctx.window_opens)")

    parts = ", ".join(f"{k} {v:.2f}" for k, v in ctx.setup_parts.items())
    c = compiles.count()
    ctx.say(f"setup {ctx.setup_s:.2f} s = {parts}; compile requests "
            f"{c['requests']} (cache hits {c['hits']}, misses "
            f"{c['requests'] - c['hits']})")
    # correct = every check the job made held (and it made some)
    correct = bool(ctx.checks) and all(ok for ok, _ in ctx.checks)
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in ctx.devices)
    device["memory_peak_bytes"] = int(peak_mem)
    out = {"correct": correct, "attempted": int(result["attempted"]),
           "failed": int(result["failed"]), "metrics": {}, "device": device}

    if not args.trace:
        values = dict(result["metrics"], setup_s=ctx.setup_s)
        for m in manifest["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            if m["name"] not in values:
                fail(f"the job reported no {m['name']!r}, which the "
                     f"manifest promises for {cell['name']}")
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    else:
        xplane = load_module("reducers", "xplane")
        path = ctx.xplane_path()
        trace = xplane.load(path) if path else None
        if path and args.keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace)),
                        exist_ok=True)
            shutil.copyfile(path, args.keep_trace)
        counters = result.get("counters", {})
        for lm in layer_metrics_for(workload["job"], cell["name"]):
            mod, _, fn = lm["reducer"].partition(":")
            value = getattr(load_module("reducers", mod), fn)(
                trace, counters, lm.get("args", {}))
            if value is not None:
                out["metrics"][lm["name"]] = {"value": value,
                                              "unit": lm["unit"]}
        if trace is not None and trace.devices:
            device["busy_s"] = xplane.busy_s(trace)
            device["window_s"] = trace.window_s
            out["breakdown"] = {"device_ops": xplane.top_ops(trace),
                                "idle_gaps": xplane.idle_gaps(trace)}
            ctx.say(f"traced window {trace.window_s:.3f} s, device busy "
                    f"{device['busy_s']:.3f} s; programs "
                    f"{ {k: round(v, 4) for k, v in xplane.module_times(trace).items()} }")
            for name, s in out["breakdown"]["device_ops"]:
                ctx.say(f"  device op  {s:9.4f} s  {name}")
            for name, s in out["breakdown"]["idle_gaps"]:
                ctx.say(f"  idle gap   {s:9.4f} s  {name}")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    if rehearse:
        # the line keeps the contract's keys so the tests can hold it to
        # them, but a CPU number is never written under a device metric
        ctx.say(f"values on the CPU (no device numbers): "
                f"{ {k: v['value'] for k, v in out['metrics'].items()} }")
        for m in out["metrics"].values():
            m["value"] = None
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
