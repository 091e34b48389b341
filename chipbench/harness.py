"""What every cell shares: its files, the chip check, host spans, the
compile counter, the per-layer readers and the result line.

A cell is found by name: its entry in ``BENCHMARK.json`` names a
configuration (``configs/<file>``) and a traffic mix
(``traffic/<traffic>.json``); the traffic names the driver
(``drivers/<driver>.py``) that runs it.  A per-layer metric is read by
``metrics/<name>.py``, or, for a quantity split by the cells' end-to-end
metric (``idle_pct.array``), by the reader of its first part
(``metrics/idle_pct.py``) where it has none of its own.  Adding a cell, a configuration, a mix or a
metric adds files; none of these is edited.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent                          # the checkout


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration file
    traffic: dict               # the traffic file
    end_to_end: List[dict]      # the cell's end-to-end metrics
    per_layer: List[dict]       # the cell's per-layer metrics

    @property
    def driver(self):
        return load_module(HERE / "drivers" / f"{self.traffic['driver']}.py")


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT /
                                                       "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"chipbench: no workload {name!r}; there are "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    return Cell(name=name, chips=w["chips"],
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def log(*args) -> None:
    print("[chipbench]", *args, file=sys.stderr, flush=True)


# -- the chip -------------------------------------------------------------
def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices; exits without a result where JAX
    finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip so far (a process's peak
    never falls)."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


# -- spans ------------------------------------------------------------------
class Spans:
    """Host spans around the benchmark's calls into the program.  Each
    is kept in memory as (name, start, end, attrs) on the host clock
    (seconds); with ``tracing`` it is also a
    ``jax.profiler.TraceAnnotation``, on the device trace's clock."""

    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str, **attrs):
        import jax

        ann = (jax.profiler.TraceAnnotation(name) if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield attrs
        self.records.append((name, t0, time.perf_counter(), attrs))

    def named(self, name: str) -> List[tuple]:
        return [r for r in self.records if r[0] == name]


class CompileCounter:
    """Counts the programs JAX lowers and compiles while ``counting``:
    a warm window lowers none."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counting = False
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if self.counting:
            self.lowered += name == self.LOWER
            self.compiled += name == self.COMPILE


# -- the run ------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a driver hands back to the harness."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # name -> value
    checks: List[tuple]                   # (name, value, limit) compared
    correct: bool
    spans: Spans
    facts: Dict[str, Any]                 # what metric readers need
    devices: list
    trace: Any = None                     # trace.Reduced or None
    memory_peak_bytes: int = 0


def find_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``,
    else that of the name's first part."""
    own = HERE / "metrics" / f"{name}.py"
    return load_module(own if own.exists() else
                       HERE / "metrics" / f"{name.split('.')[0]}.py")


def read_per_layer(cell: Cell, run: Run) -> Dict[str, dict]:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    peaks = load_json(HERE / "peaks.json")
    out = {}
    for m in cell.per_layer:
        value = find_reader(m["name"]).read(run, cell, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell: Cell, run: Run, tracing: bool) -> dict:
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed}
    if tracing:
        line["metrics"] = read_per_layer(cell, run)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown(
            {r[0] for r in run.spans.records})
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in run.end_to_end.items()
                           if k in units}
    line["device"] = device
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in run.checks}
    return line


def within(checks: List[tuple]) -> bool:
    """Every compared number at or under its limit (a limit not yet
    set fails)."""
    return all(lim is not None and v <= lim for _, v, lim in checks)


def report_checks(checks: List[tuple]) -> None:
    """Each compared number beside its limit: the last lines on
    standard error."""
    for name, value, limit in checks:
        log(f"check {name}: {value!r} (limit {limit!r})")
