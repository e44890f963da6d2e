"""What every cell shares: finding a cell's files by name, the device
checks, the compile clock, and the result line.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json`` is found as
``configs/<config>.json``, ``traffic/<traffic>.json`` (whose ``kind`` names
``loops/<kind>.py``), ``cells/<cell>.json`` (the limits of its correctness
check) and, for each per-layer metric, ``metrics/<metric>.py``.

A configuration file states its whole architecture: every model key is a
field of the program's ``ArchConfig`` (``arch``), and its ``reference``
names ``references/<reference>.py``, the plain reference that also owns the
weight layout (``weights``) and the FLOP counts (``flops``).  So a later
configuration, whatever its architecture, adds files and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import typing
from typing import Callable, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(kind: str, name: str, ext: str = ".json") -> str:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    return path


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (names may hold dots), executed
    once per file."""
    return _exec_file(find(kind, name, ".py"), f"benchmarks.chip.{kind}."
                      f"{name.replace('.', '_').replace('-', '_')}")


@functools.lru_cache(maxsize=None)
def _exec_file(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: Optional[dict] = None) -> dict:
    """Everything one cell runs with, found by name."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    config = load_json(find("configs", w["config"]))
    traffic = load_json(find("traffic", w["traffic"]))
    limits_path = os.path.join(HERE, "cells", name + ".json")
    limits = (load_json(limits_path)["limits"]
              if os.path.isfile(limits_path) else {})
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"name": name, "workload": w, "config": config,
            "traffic": traffic, "limits": limits, "per_layer": per_layer,
            "end_to_end": end_to_end, "loop": load_module("loops",
                                                          traffic["kind"])}


# the configuration file's keys that describe it and are no model field
DESCRIPTIVE_KEYS = frozenset({"source_part", "reference", "context_length",
                              "optimizer", "reduced", "assumed", "deployment"})
# the file's name for a field where the two differ
FIELD_OF_KEY = {"head_dim": "d_head"}


def _as_field_type(tp, value):
    """``value`` converted to the field type ``tp`` (``Optional[t]`` as
    ``t``; a list to a tuple)."""
    if typing.get_origin(tp) is typing.Union:
        if value is None:
            return None
        tp, = (t for t in typing.get_args(tp) if t is not type(None))
    return tp(value)


def arch(config: dict):
    """The program's ``ArchConfig`` for a configuration file: every key
    but the descriptive ones is a field, converted to the field's type.  A
    key that is neither, or a file with no ``reference``, is an error."""
    from repro.configs.base import ArchConfig
    _reference_name(config)
    types = typing.get_type_hints(ArchConfig)
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {}
    for key, value in config.items():
        if key in DESCRIPTIVE_KEYS:
            continue
        field = FIELD_OF_KEY.get(key, key)
        if field not in fields or field in kw:
            raise KeyError(f"configuration {config.get('name')!r}: key "
                           f"{key!r} is no ArchConfig field, or names one "
                           f"twice")
        kw[field] = _as_field_type(types[field], value)
    return ArchConfig(**kw)


def _reference_name(config: dict) -> str:
    if "reference" not in config:
        raise KeyError(f"configuration {config.get('name')!r} has no key "
                       f"'reference' naming its plain reference module")
    return config["reference"]


def reference(config: dict):
    """The configuration's plain reference, ``references/<reference>.py``."""
    return load_module("references", _reference_name(config))


# --------------------------------------------------------------- device --

def require_tpu(count: int) -> dict:
    """The device record; exits non-zero where JAX finds no TPU or fewer
    chips than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{dev['platform']!r}")
    if dev["count"] < count:
        raise SystemExit(f"the cell needs {count} TPU chips; JAX found "
                         f"{dev['count']}")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache retrievals
    included), and keeps the name of each compiled function and the count
    of persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.names = []
        self.cache_hits = 0

        def on_duration(event, duration, fun_name="", **_):
            if event == self.EVENT:
                self.seconds += duration
                self.names.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lap(self):
        return len(self.names), self.seconds, self.cache_hits


# --------------------------------------------------------------- result --

def emit(result: dict, checks: Dict[str, dict]) -> None:
    """The numbers compared beside their limits as the last lines of
    standard error, then the result as the last line of standard output
    (the checks under the key that comes last)."""
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def judge(readings: Dict[str, float], limits: Dict[str, dict]
          ) -> Dict[str, dict]:
    """Each reading beside its limit; a reading without a limit, or not a
    number, fails."""
    out = {}
    for k, v in readings.items():
        lim = limits.get(k, {}).get("limit")
        out[k] = {"value": v, "limit": lim,
                  "ok": (lim is not None and v is not None and v == v
                         and v <= lim)}
    return out


def per_layer(metrics: list, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric's reader on the run's context; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader: Callable = load_module("metrics", m["name"]).read
        v = reader(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ------------------------------------------------------- window and spans --

class Spans:
    """Host-clock spans the benchmark puts around calls into the program,
    on the instances it built.  While ``on``, each call's duration is
    added to ``seconds[name]``; while tracing, the call also runs inside a
    profiler ``TraceAnnotation`` of that name."""

    def __init__(self):
        self.on = False
        self.tracing = False
        self.seconds: Dict[str, float] = {}

    def wrap(self, obj, attr: str, name: str) -> None:
        import time

        from jax.profiler import TraceAnnotation
        inner = getattr(obj, attr)

        def timed(*a, **kw):
            if not self.on:
                return inner(*a, **kw)
            t0 = time.perf_counter()
            try:
                if self.tracing:
                    with TraceAnnotation(name):
                        return inner(*a, **kw)
                return inner(*a, **kw)
            finally:
                self.seconds[name] = (self.seconds.get(name, 0.0)
                                      + time.perf_counter() - t0)

        setattr(obj, attr, timed)


class Window:
    """The measured window: host clock, compiles inside it, and with
    ``trace`` a profiler trace of the whole window, reduced on close."""

    def __init__(self, spans: Spans, clock: CompileClock, trace: bool):
        self.spans, self.clock, self.trace = spans, clock, trace
        self.reduced = None
        self.breakdown = None

    def open(self) -> float:
        import time
        if self.trace:
            import tempfile

            import jax
            from jax.profiler import TraceAnnotation
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._ann = TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.spans.on, self.spans.tracing = True, self.trace
        self.c0 = self.clock.lap()
        self.t0 = time.perf_counter()
        return self.t0

    def close(self) -> float:
        import time
        self.t1 = time.perf_counter()
        c1 = self.clock.lap()
        self.spans.on = self.spans.tracing = False
        self.compiled = self.clock.names[self.c0[0]:c1[0]]
        self.compiles = len(self.compiled)
        self.compile_s = c1[1] - self.c0[1]
        if self.trace:
            import shutil

            import jax

            from benchmarks.chip import trace as tr
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            try:
                raw = tr.load(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
            self.reduced = tr.reduce(raw)
            self.breakdown = {"device_ops": tr.top_ops(raw),
                              "idle_gaps": tr.idle_gaps(raw)}
        return self.t1

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0
