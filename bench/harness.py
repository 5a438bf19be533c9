"""Pieces every cell shares: the device check, host spans, the compile
counter, the trace window, the per-layer readers and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
``run.py`` resolves a cell through ``BENCHMARK.json`` and the files named
after it, and each driver (``drivers/<kind>.py``) fills a :class:`Run`.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------- device
def require_device(chips: int, allow_cpu: bool = False) -> dict:
    """The devices JAX found, as the result line names them.  Anything but
    a TPU with at least ``chips`` devices ends the run with no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise SystemExit(f"bench: JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def compile_counter():
    """``(count, seconds)`` of XLA backend compiles in this process so far;
    a program found in the persistent cache compiles nothing."""
    import jax
    state = [0, 0.0]

    def on_event(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            state[0] += 1
            state[1] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return lambda: (state[0], state[1])


def memory_peak_bytes(devices, programs=()) -> int:
    """Peak bytes on the fullest chip.  The allocator's own peak leaves out
    a program's temp buffers on this runtime, so each compiled program's
    arguments, outputs and temp (``memory_analysis``) are reckoned too, and
    the larger reading is taken."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    for compiled in programs:
        m = compiled.memory_analysis()
        if m is None:
            continue
        peak = max(peak, int(m.argument_size_in_bytes + m.output_size_in_bytes
                             + m.temp_size_in_bytes - m.alias_size_in_bytes))
    return peak


# ----------------------------------------------------------------- spans
class Spans:
    """Host spans around the benchmark's calls into the program.  Each is
    kept in memory as ``(name, start, end)`` on ``time.perf_counter`` and,
    while a trace is being taken, written into it as a TraceAnnotation so
    the device's idle gaps can be named by what the host was doing."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, since: float = -math.inf,
              until: float = math.inf) -> float:
        return sum(b - a for n, a, b in self.records
                   if n == name and a >= since and b <= until)


class Trace:
    """The profiler over the last ``seconds`` of a window of ``window``
    seconds (``--trace 1``), so that writing the trace out falls after the
    window; off, it does nothing."""

    def __init__(self, on: bool, spans: Spans, tag: str, window: float,
                 seconds: float):
        self.on, self.spans = on, spans
        self.begin = max(0.0, window - seconds)
        self.dir = BENCH / ".traces" / tag
        self.t0 = self.t1 = None
        self.path = None

    def poll(self, elapsed: float) -> None:
        """Start tracing once ``elapsed`` seconds of the window have gone."""
        if not self.on or self.t0 is not None or elapsed < self.begin:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        # no Python tracer, and only the host's annotations (level 1): the
        # benchmark's spans name the idle gaps, and the host loop the
        # window times runs at the speed it runs untraced
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.spans.tracing = True
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        import jax
        self.t1 = time.perf_counter()
        self.spans.tracing = False
        jax.profiler.stop_trace()
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        self.path = found[-1] if found else None

    def reduce(self, n_devices: int):
        from bench import trace_reduce
        if self.path is None:
            return None
        red = trace_reduce.reduce(self.path, n_devices=n_devices,
                                  window_s=self.t1 - self.t0)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


# -------------------------------------------------------------- metrics
def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile by the nearest-rank method, over every
    observation (``serving/metrics.py``'s rule)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(1, math.ceil(q / 100 * len(xs)))
    return xs[k - 1]


def load_reader(name: str):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What one run hands to the result line: counts, end-to-end values,
    the record the per-layer readers read, and each number compared."""

    def __init__(self):
        self.attempted = 0
        self.failed = 0
        self.end_to_end: dict[str, float] = {}
        self.record: dict = {}
        self.checks: dict[str, tuple[float, float]] = {}
        self.samples: dict[str, int] = {}     # observations behind a metric
        self.memory_peak_bytes = 0

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for v, lim in self.checks.values())


def result_line(run: Run, spec: dict, cell: dict, device: dict,
                trace: bool) -> dict:
    name = cell["name"]
    metrics = {}
    if trace:
        rec = dict(run.record, device_kind=device["kind"])
        for m in spec["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if name in m.get("workloads", [name]) and m["name"] in \
                    run.end_to_end:
                metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    red = run.record.get("trace")
    if trace and red is not None:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
    if not trace and run.samples:
        out["samples"] = run.samples
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out


def print_checks(run: Run) -> None:
    for k, (v, lim) in run.checks.items():
        ok = "ok" if math.isfinite(v) and v <= lim else "FAIL"
        print(f"check {k} = {v!r} limit {lim!r} {ok}", file=sys.stderr,
              flush=True)
