#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Everything is found by name, nothing by a central list: ``BENCHMARK.json``
names a cell's config and traffic; the config file names its family
(``families/<family>.py``), the traffic file its kind
(``drivers/<kind>.py``), a per-layer metric (``metrics/<metric>.json``) its
reader (``readers/<reader>.py``); the cell's limits are
``limits/<workload>.json``. A later PR adds files and entries and edits none.

One process does everything (a chip belongs to one process). Without a TPU
the run fails, unless ``--rehearse`` asks for the CPU rehearsal: a tiny
config, interpret-mode kernels, the platform stamped on the result, and no
device metric printed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str, here: str = HERE) -> dict:
    with open(os.path.join(here, *parts)) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str, here: str = HERE,
            root: str = ROOT) -> dict:
    """A cell's files, by the names the manifest gives."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, config["file"])) as f:
        conf = json.load(f)
    conf["name"] = cell["config"]
    traffic = load_json("traffic", f"{cell['traffic']}.json", here=here)
    limits_path = os.path.join(here, "limits", f"{workload}.json")
    limits = load_json("limits", f"{workload}.json", here=here) \
        if os.path.isfile(limits_path) else {}
    return {"cell": cell, "conf": conf, "traffic": traffic, "limits": limits}


def cell_metrics(manifest: dict, workload: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class CompileMeter:
    """JAX's own compile events (``jax.monitoring``): seconds in the backend
    compile (on a persistent-cache hit, the retrieval), programs, and the
    cache's hits and misses. Copied from ``chip_smoke.py`` (PR 21)."""

    def __init__(self):
        import jax.monitoring as mon

        self.w = {"backend_compile_s": 0.0, "programs": 0, "cache_hits": 0,
                  "cache_misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.w["backend_compile_s"] += duration
            self.w["programs"] += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.w["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.w["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.w)


class Run:
    """What a driver and the readers are handed: the cell's files, the
    family, the clock, the profiler and a place for readings."""

    def __init__(self, args, files, manifest, family, peaks, dev, meter,
                 here: str = HERE):
        self.args, self.manifest, self.family = args, manifest, family
        self.here = here
        self.cell, self.conf = files["cell"], files["conf"]
        self.traffic, self.limits = dict(files["traffic"]), files["limits"]
        if args.rehearse:
            self.traffic.update(self.traffic.get("rehearse", {}))
        for kv in getattr(args, "set", []):
            key, value = kv.split("=", 1)
            self.traffic[key] = float(value)
        self.spec = family.spec_from_config(self.conf, args.rehearse)
        self.peaks, self.dev, self.meter = peaks, dev, meter
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.chips = self.cell["chips"]
        self.out_dir = os.path.join(
            args.out, self.cell["name"], f"seed{args.seed}_trace{args.trace}")
        os.makedirs(self.out_dir, exist_ok=True)
        self.readings: dict = {"series": {}, "counts": {}}
        self.bench_spans: list = []     # (name, t0_s, t1_s), host clock
        self.clock_sync = None          # (perf_counter, observe _now_us)

    def load(self, kind: str, name: str):
        """A sibling module by name, as ``run.py`` itself finds them."""
        return load_module(kind, name, self.here)

    # -- clock and spans ----------------------------------------------------
    def log(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}",
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """A span of the harness's own (``bench:*``): into the profiler's
        trace when one is being taken, and onto the host clock's list."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.bench_spans.append((name, t0, time.perf_counter()))

    # -- the profiler -------------------------------------------------------
    def start_trace(self) -> None:
        import jax
        from thunder_tpu import observe

        self.trace_dir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.clock_sync = (time.perf_counter(), observe.registry._now_us())
        with jax.profiler.TraceAnnotation("bench:sync"):
            pass
        self.t_trace_open = time.perf_counter()

    def stop_trace(self) -> None:
        import jax

        self.t_trace_close = time.perf_counter()
        jax.profiler.stop_trace()

    # -- the window's edges: what compiled before, what inside ---------------
    def open_window(self) -> float:
        self.readings["compile"] = {"at_open": self.meter.snapshot()}
        return time.perf_counter()

    def close_window(self) -> float:
        t = time.perf_counter()
        at_open = self.readings["compile"]["at_open"]
        self.readings["compile"]["programs_in_window"] = \
            self.meter.snapshot()["programs"] - at_open["programs"]
        return t

    def write_json(self, name: str, obj) -> str:
        path = os.path.join(self.out_dir, name)
        with open(path, "w") as f:
            json.dump(obj, f)
        return path


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def place_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says, or
    the program's fixed ``.jax_cache`` in the checkout; every program kept,
    however quickly it compiled."""
    import jax
    import thunder_tpu as tt

    directory = tt.enable_compilation_cache(min_compile_secs=0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; prints no device metric")
    ap.add_argument("--control", action="store_true",
                    help="also read the control and the planted faults "
                         "(for setting limits; the driver's runs never do)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a number of the traffic file (for the "
                         "one sweep that finds a rate; never in a check)")
    ap.add_argument("--out", default=os.path.join(HERE, "out"),
                    help="directory for group times and the trace")
    return ap.parse_args(argv)


def run_cell(args, here: str = HERE, root: str = ROOT, patch=None) -> dict:
    """One run of one cell; returns the result line as a dict. ``patch`` is
    for the tests that break the timed path underneath."""
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    files = resolve(manifest, args.workload, here, root)
    chips = files["cell"]["chips"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["THUNDER_TPU_PALLAS_INTERPRET"] = "1"
        if chips > 1 and "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
    if root not in sys.path:
        sys.path.insert(0, root)
    import jax

    dev = device_info()
    if not args.rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"benchmark: JAX found no accelerator (platform "
                         f"{dev['platform']!r}); --rehearse is the CPU run")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: cell {args.workload!r} needs {chips} "
                         f"chips, JAX sees {dev['count']}")
    peaks = load_json("peaks.json", here=here)
    if not args.rehearse and dev["kind"] not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind {dev['kind']!r}")
    cache_dir = place_compile_cache()
    meter = CompileMeter()
    family = load_module("families", files["conf"]["family"], here)
    driver = load_module("drivers", files["traffic"]["kind"], here)
    ctx = Run(args, files, manifest, family, peaks.get(dev["kind"]), dev,
              meter, here)
    ctx.log(f"{args.workload} seed {args.seed} on {dev}; cache {cache_dir}")
    if ctx.trace:
        from thunder_tpu import observe

        observe.enable(clear=True)          # the program's spans, traced run
    if patch is not None:
        patch(ctx)

    out = driver.run(ctx)                   # set-up, warm-up, the window
    setup_s = out["t_open"] - T_START
    peak = memory_peak_bytes()
    ctx.readings["memory_peak_bytes"] = peak
    ctx.readings["compile"]["setup_s"] = setup_s
    ctx.log(f"window closed; set-up {setup_s:.2f} s; comparing")
    compared = out["compare"]()             # the reference, state freed first
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())

    metrics: dict = {}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": chips, "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    unit = lambda m, v: {"value": v, "unit": m["unit"]}
    if not ctx.trace:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in cell_metrics(manifest, args.workload, "end_to_end"):
            metrics[m["name"]] = unit(m, values[m["name"]])
    else:
        if not args.rehearse:
            reduce = load_module("readers", "device_trace", here)
            ctx.readings["trace"] = reduce.reduce_run(ctx)
            device["busy_s"] = ctx.readings["trace"]["busy_s"]
            device["window_s"] = ctx.readings["trace"]["window_s"]
            line["breakdown"] = ctx.readings["trace"]["breakdown"]
        for m in cell_metrics(manifest, args.workload, "per_layer"):
            if args.rehearse and m["source"] == "device_trace":
                continue                    # no device number from a CPU run
            how = load_json("metrics", f"{m['name']}.json", here=here)
            reader = load_module("readers", how["reader"], here)
            value = reader.read(ctx, **how.get("args", {}))
            if value is not None:
                metrics[m["name"]] = unit(m, value)
    if args.rehearse:                       # never under a device metric's name
        line["rehearsal_metrics"], line["metrics"] = metrics, {}
    if args.control:
        line["control"] = out["control"]()
        print(f"control: {json.dumps(line['control'])}", file=sys.stderr)
    line["compared"] = compared             # last, as the contract asks
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    line = run_cell(args)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
