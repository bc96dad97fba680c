#!/usr/bin/env python3
"""One cell of the benchmark, on the chip(s) this process is started on.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found BY NAME from ``BENCHMARK.json``: the
workload names a configuration (its ``file``) and a traffic mix
(``benchmark/traffic/<traffic>.json``); the traffic file names its driver
(``benchmark/drivers/<driver>.py``); each per-layer metric is read by
``benchmark/layer_metrics/<name>.py``, or, where one quantity is split by
the end-to-end metric it moves (``dispatch_ms.train``), by the reader of the
name before the last dot (``dispatch_ms.py``).  This file holds no list of cells,
configurations, mixes, drivers or metrics.

One process; every worker of the program is a thread of it.  It FAILS
(exit code != 0, no result line) unless jax's first device is a TPU whose
``device_kind`` is in ``benchmark/lib/peaks.py`` and jax sees exactly the
chips the cell asks for.  The LAST line of stdout is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (a few seconds in the middle of the
window are profiled).  Every path leaves through ``os._exit``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # as close to process start as python gets

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(HERE, "out")


def note(**fields):
    """A line worth reading that is not the result."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots and
    dashes, so this goes by path and not by import)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + "".join(c if c.isalnum() else "_" for c in name),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of a per-layer metric: the file of its own name, else of
    the name before its last dot."""
    for name in (metric, metric.rpartition(".")[0]):
        if name and os.path.isfile(os.path.join(HERE, "layer_metrics", name + ".py")):
            return load_module("layer_metrics", name)
    raise FileNotFoundError(f"no layer_metrics file reads {metric!r}")


def resolve_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}"
        )
    cell = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of_cell(entries, workload: str):
    return [
        m for m in entries if "workloads" not in m or workload in m["workloads"]
    ]


@dataclasses.dataclass
class RunContext:
    """What a driver and a per-layer reader may look at."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    device_kind: str
    n_devices: int
    peaks: dict
    clock: object  # lib.compile_clock.CompileClock
    work_dir: str
    #: filled by run.py after ``measure``: the driver's window record
    #: (``seconds``, ``counters``, ...) and, in a traced run, the reduced trace
    window: dict = dataclasses.field(default_factory=dict)
    trace: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0


class TraceSlice:
    """Profiles ``length`` seconds starting ``offset`` seconds from
    ``start()``, in a thread of its own (the driver's window runs
    meanwhile)."""

    def __init__(self, trace_dir: str, offset: float, length: float):
        self.trace_dir, self.offset, self.length = trace_dir, offset, length
        self.error = None
        self._thread = threading.Thread(target=self._run, name="trace-slice")

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.offset)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # python frames: huge, and not read
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - reported by join()
            self.error = e

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise RuntimeError("the profiler slice failed") from self.error


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks))


def check_device(cell: dict):
    """The first device, its peaks — or a message and no device: off a TPU,
    on a chip that is not in the peak table, or with another number of chips
    than the cell asks for."""
    import jax

    from benchmark.lib.peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return None, None, (
            f"benchmark/run.py needs a TPU: jax.devices()[0] is "
            f"{dev.platform!r} ({dev.device_kind})"
        )
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        return None, None, e.args[0]
    if len(devices) != cell["chips"]:
        return None, None, (
            f"workload {cell['name']} needs {cell['chips']} chip(s); "
            f"jax sees {len(devices)}"
        )
    return dev, peaks, None


def execute(spec, cell, config, traffic, *, seed, seconds, traced, dev, peaks):
    """Build the cell, warm it, measure, check; returns the result object."""
    import jax

    from benchmark.lib.compile_clock import CompileClock
    from benchmark.lib.trace_reduce import find_xplane, reduce_trace

    n_devices = len(jax.devices())
    clock = CompileClock()
    work_dir = os.path.join(OUT_DIR, "work")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    ctx = RunContext(
        cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=float(seconds), traced=bool(traced),
        device_kind=dev.device_kind, n_devices=n_devices, peaks=peaks,
        clock=clock, work_dir=work_dir,
    )
    driver = load_module("drivers", traffic["driver"]).build(ctx)
    try:
        driver.warm()
        trace_dir = os.path.join(OUT_DIR, "trace")
        tracer = None
        if ctx.traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            length = min(float(traffic["trace_seconds"]), ctx.seconds / 2)
            tracer = TraceSlice(trace_dir, (ctx.seconds - length) / 2, length)
        t_window = time.perf_counter()
        setup = clock.between(T_PROCESS, t_window)
        note(
            event="window_open", setup_s=t_window - T_PROCESS,
            setup_compile_seconds=setup["compile_seconds"],
            setup_compiles=setup["compiles"],
            setup_cache_hits=setup["cache_hits"],
            setup_cache_misses=setup["cache_misses"],
        )
        if tracer:
            tracer.start()
        ctx.window = driver.measure(ctx.seconds)
        t_close = time.perf_counter()
        ctx.memory_peak_bytes = memory_peak_bytes()
        if tracer:
            tracer.join()
        in_window = clock.between(t_window, t_close)
        note(event="window_closed", **in_window, **ctx.window.get("notes", {}))
        correct, details = driver.check()
        note(event="check", correct=correct, **details)
        if in_window["compiles"]:
            correct = False
            note(
                event="compiled_inside_the_window", **in_window,
                verdict="a shape the warm-up did not cover; correct=false",
            )
    finally:
        driver.close()

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": n_devices,
        "memory_peak_bytes": ctx.memory_peak_bytes,
    }
    result = {
        "correct": bool(correct),
        "attempted": int(ctx.window["attempted"]),
        "failed": int(ctx.window["failed"]),
    }
    values = dict(ctx.window["end_to_end"], setup_s=t_window - T_PROCESS)
    if not ctx.traced:
        wanted = metrics_of_cell(spec["end_to_end"], cell["name"])
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            raise RuntimeError(f"driver reported no value for {missing}")
    else:
        xplane = find_xplane(trace_dir)
        if xplane is None:
            raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
        ctx.trace = reduce_trace(
            xplane, default_gap_owner=ctx.window.get("gap_owner", "host")
        )
        if not ctx.trace or ctx.trace["busy_s"] <= 0:
            raise RuntimeError("no operation ran on the device in the trace")
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
        note(
            event="trace", xplane=xplane, busy_s=ctx.trace["busy_s"],
            window_s=ctx.trace["window_s"],
            modules=sorted(
                ctx.trace["module_seconds"].items(), key=lambda kv: -kv[1]
            )[:10],
        )
        wanted = metrics_of_cell(spec["per_layer"], cell["name"])
        values = {}
        for m in wanted:
            v = load_reader(m["name"]).value(ctx)
            if v is not None:
                values[m["name"]] = float(v)
        note(event="end_to_end_of_the_traced_run", **ctx.window["end_to_end"])
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted
        if m["name"] in values
    }
    result["device"] = device
    return result


def run(args) -> int:
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = resolve_cell(spec, args.workload)

    # the compile cache sits where the program puts it: the directory
    # JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache
    from areal_tpu.base.compile_cache import cache_entry_count, setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    # keep EVERY program in the cache, not only those that took a second to
    # compile: a cell meets a few hundred small ones, in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    dev, peaks, refusal = check_device(cell)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    note(
        event="start", workload=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, device_kind=dev.device_kind,
        devices=len(jax.devices()), jax=jax.__version__,
        compile_cache_dir=cache_dir,
        compile_cache_entries=cache_entry_count(cache_dir),
    )
    result = execute(
        spec, cell, config, traffic, seed=args.seed, seconds=args.seconds,
        traced=args.trace, dev=dev, peaks=peaks,
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


#: the first run of a cell in a checkout compiles and may take 1200 s
WATCHDOG_SECONDS = 1150.0


def _give_up():
    print(
        f"benchmark/run.py: not done after {WATCHDOG_SECONDS:.0f} s; giving up",
        file=sys.stderr, flush=True,
    )
    stop_children()
    os._exit(3)


def stop_children():
    """Stop every process this one started and that is still running."""
    import signal

    me = os.getpid()
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid == me:
                os.kill(int(entry), signal.SIGKILL)
                os.waitpid(int(entry), 0)
        except (OSError, ValueError, IndexError):
            continue  # gone already, or not ours


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    # a run that hangs holds its chip until someone else's limit ends it
    watchdog = threading.Timer(WATCHDOG_SECONDS, _give_up)
    watchdog.daemon = True
    watchdog.start()
    return run(args)


if __name__ == "__main__":
    # worker threads must not keep a finished OR FAILED run alive: a failure
    # is printed, no result line follows, and the process leaves at once
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code:
            print(e.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 - reported, then exit != 0
        traceback.print_exc()
        rc = 1
    stop_children()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
