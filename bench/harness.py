"""Everything a run shares, whatever its cell: finding a cell's files by
name, seeds, the device check, the compile cache, spans, the comparison
helpers and the result line.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
and a traffic mix.  Its files are found by those names:

  bench/configs/<config>.json     the sizes, as run
  bench/configs/<config>.py       the system under test built from them
  bench/configs/<config>.ref.py   the plain reference (imports nothing of
                                  the program)
  bench/traffic/<traffic>.json    the mix's parameters; its ``kind`` names
                                  the one runner bench/drive/<kind>.py
  bench/limits/<workload>.json    the limit of each number compared
  bench/metrics/<metric>.py       one reader per per-layer metric
  bench/work/<kernel>.py          the work of a Pallas kernel, by its name
                                  in the trace
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def process_age_s() -> float:
    """Seconds since this process started (Linux), so that set-up counts
    the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) - start
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


_T_IMPORT = time.perf_counter()


def note(msg: str) -> None:
    """A progress line on standard error, stamped with the process age."""
    print(f"[{process_age_s():8.2f}s] {msg}", file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    sizes: dict
    traffic: dict
    limits: dict
    system: object
    reference: object
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(workload: str, spec_path: Path | None = None) -> Cell:
    spec = load_json(spec_path or ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(wl)}")
    w = wl[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload, names)]
    limits_path = BENCH / "limits" / f"{workload}.json"
    return Cell(
        name=workload, workload=w, config=cfg,
        sizes=load_json(ROOT / cfg["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(limits_path) if limits_path.exists() else {},
        system=load_module(BENCH / "configs" / f"{cfg['name']}.py"),
        reference=load_module(BENCH / "configs" / f"{cfg['name']}.ref.py"),
        end_to_end=e2e, per_layer=per_layer)


def runner(cell: Cell):
    return load_module(BENCH / "drive" / f"{cell.traffic['kind']}.py")


# ------------------------------------------------------------------ seeds
def key(seed: int, *tags: str):
    """A JAX key from ``seed`` (any non-negative integer; all its bits
    count) and a purpose, so that weights, inputs and samples never share
    a stream."""
    import jax.numpy as jnp
    import numpy as np
    words = [int.from_bytes(t.encode(), "little") % (2 ** 32) for t in tags]
    state = np.random.SeedSequence([int(seed), *words]).generate_state(2)
    return jnp.asarray(state, dtype=jnp.uint32)


def rng(seed: int, *tags: str):
    import numpy as np
    words = [int.from_bytes(t.encode(), "little") % (2 ** 32) for t in tags]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *words]))


# ------------------------------------------------------------------ device
def devices(chips: int):
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (device 0 is "
                     f"{devs[0].platform if devs else 'missing'})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    from bench.peaks import peaks_for
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else a fixed directory in the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks)) if peaks else 0


def free():
    """Drop what the program held, before the reference runs."""
    gc.collect()


# ------------------------------------------------------------------ spans
def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def window(trace_dir):
    """The measured window: profiled when ``trace_dir`` is given, and
    marked with the ``bench.window`` span.  Tracing or compiling inside it
    is reported on standard error: nothing should.  So are the garbage
    collector's full collections inside it, with the longest pause, so
    that a slow tick or step can be told from a collection."""
    import jax
    from bench.trace import WINDOW_SPAN
    seen, pauses, started = [], [], []

    def listener(event, duration, **_):
        if event in _COMPILE_EVENTS:
            seen.append((event.rsplit("/", 1)[-1], duration))

    def collected(phase, info):
        if phase == "start":
            started[:] = [time.perf_counter()]
        elif info["generation"] == 2 and started:
            pauses.append(time.perf_counter() - started[0])
    jax.monitoring.register_event_duration_secs_listener(listener)
    gc.callbacks.append(collected)
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        with span(WINDOW_SPAN):
            yield
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.callbacks.remove(collected)
        jax.monitoring.unregister_event_duration_listener(listener)
        note(f"traced or compiled inside the window: {len(seen)} "
             f"({sum(d for _, d in seen):.3f}s) {sorted({e for e, _ in seen})}")
        note(f"full collections inside the window: {len(pauses)} "
             f"(longest {max(pauses, default=0.0):.4f}s)")


# ------------------------------------------------------------------ checks
def _norms(xs):
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


def leaf_norms(tree) -> dict:
    """Float32 norm of every leaf, keyed by its path."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(_norms)([x for _, x in leaves])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(leaves, norms)}


def norm_gap(program: dict, reference: dict, keep=None) -> float:
    """Worst leaf of |norm_program - norm_reference| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    keys = [k for k in reference if keep is None or k in keep]
    med = statistics.median(reference[k] for k in keys)
    gaps = [abs(program.get(k, math.nan) - reference[k]) / max(reference[k], med, 1e-30)
            for k in keys]
    return math.inf if not all(math.isfinite(g) for g in gaps) else max(gaps)


def moved_leaves(reference_grad: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's: the others move by round-off alone under Adam."""
    med = statistics.median(reference_grad.values())
    return {k for k, v in reference_grad.items() if v >= share * med}


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    checks: list                   # (name, value, limit)
    memory_peak_bytes: int
    counts: dict = dataclasses.field(default_factory=dict)
    trace_dir: object = None


def judge(checks) -> bool:
    return all(math.isfinite(v) and limit is not None and v <= limit
               for _, v, limit in checks)


# ------------------------------------------------------------------ output
def per_layer_values(cell: Cell, outcome: Outcome, devs):
    from bench import trace as T
    from bench.peaks import peaks_for
    summary = T.load(T.trace_file(outcome.trace_dir))
    ctx = {"cell": cell, "summary": summary, "counts": outcome.counts,
           "peaks": peaks_for(devs[0].device_kind)}
    values = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device_extra = {"busy_s": summary.busy_ns / 1e9,
                    "window_s": summary.window_ns / 1e9}
    own = {f.name for d in (ROOT / "src", BENCH) for f in d.rglob("*.py")}
    breakdown = {"device_ops": T.top_ops(summary),
                 "idle_gaps": T.idle_gaps(summary, own_files=own)}
    return values, device_extra, breakdown


def result_line(cell: Cell, outcome: Outcome, devs, traced: bool) -> dict:
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": bool(outcome.correct), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed)}
    if traced:
        metrics, extra, breakdown = per_layer_values(cell, outcome, devs)
        device.update(extra)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        line.update(metrics={m["name"]: {"value": outcome.end_to_end[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end},
                    device=device)
    line["checks"] = {name: {"value": v, "limit": limit}
                      for name, v, limit in outcome.checks}
    return line


def print_checks(outcome: Outcome):
    for name, v, limit in outcome.checks:
        print(f"check {name}: {v!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {outcome.correct}", file=sys.stderr)
