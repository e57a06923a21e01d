"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device plane ``/device:TPU:<n>`` carries an ``XLA Ops`` line whose
event names are the HLO instruction text (``%name = shape op(operands)``,
nested: a ``while`` contains its body's ops) and an ``XLA Modules`` line
with one event per program run.  Host threads carry the Python tracer's
frames (``$file.py:line function``) and the benchmark's own spans
(``bench.*``).  All timestamps share one clock, in nanoseconds.

A Pallas kernel is an op whose text names ``tpu_custom_call``, or a
``kind=kCustom`` fusion that XLA built around one (named after the
kernel, not ``fusion``).  Its name in the trace is the instruction name
without its numeric suffix and without the autodiff and jit prefixes
JAX adds (``transpose_jvp_jit__approx_gemm_impl___.4`` ->
``approx_gemm_impl``).
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
_PREFIXES = ("transpose_", "jvp_", "jit_", "vmap_", "remat_", "checkpoint_")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|f16|bf16|f32|f64)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "f64": 8}


@dataclasses.dataclass
class Op:
    text: str
    start: float      # ns
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Summary:
    window: tuple            # (start, end) ns of the benchmark's window span
    ops: list                # leaf device ops inside the window
    modules: list            # (name, start, dur) program runs inside the window
    host: list               # (name, start, dur) host events inside the window
    busy_ns: float           # union of device op intervals inside the window
    gaps: list               # (start, end) idle intervals inside the window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def instruction_name(text: str) -> str:
    m = re.match(r"%?([^\s=]+)\s*=", text)
    return m.group(1) if m else text.split(" ")[0]


def kernel_name(text: str) -> str:
    """Base name of a kernel op: suffixes and JAX's prefixes removed."""
    name = re.sub(r"(\.[\w\-]+)+$", "", instruction_name(text))
    changed = True
    while changed:
        changed = False
        for p in _PREFIXES:
            if name.startswith(p):
                name, changed = name[len(p):], True
    return name.strip("_")


def is_kernel(text: str) -> bool:
    if 'custom_call_target="tpu_custom_call"' in text:
        return True
    return "kind=kCustom" in text and not kernel_name(text).startswith("fusion")


def shapes(text: str):
    """(operands, results): lists of (dtype, dims) read from the op text."""
    head, _, rest = text.partition("=")
    m = re.search(r"[\]\}\)]\s+([a-z][\w\-]*)\(", rest)
    if not m:
        return [], []
    result = rest[:m.start()]
    depth, i = 1, m.end()
    while i < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    args = rest[m.end():i - 1]
    parse = lambda s: [(d, tuple(int(x) for x in dims.split(",") if x))
                       for d, dims in _SHAPE.findall(s)]
    return parse(args), parse(result)


def nbytes(shape) -> int:
    dtype, dims = shape
    n = _BYTES[dtype]
    for x in dims:
        n *= x
    return n


def _union(intervals):
    total, cur_s, cur_e, merged = 0.0, None, None, []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
    for s, e in merged:
        total += e - s
    return total, merged


def _leaves(ops):
    """Ops that contain no other op (a while loop's body ops, not the loop)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    leaves = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end:
            continue
        leaves.append(o)
    return leaves


def trace_file(log_dir) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path, device: int = 0, window=None) -> Summary:
    """Reduce the trace at ``path`` for device ``device``.  The window is
    the benchmark's ``bench.window`` span when it is there, else
    ``window`` (ns), else the extent of the device ops."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    dev_name = f"/device:TPU:{device}"
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name == dev_name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [Op(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if spans:
        window = (spans[-1][1], spans[-1][1] + spans[-1][2])
    elif window is None:
        window = ((min(o.start for o in ops), max(o.end for o in ops))
                  if ops else (0.0, 0.0))
    lo, hi = window
    inside = lambda s, d: s < hi and s + d > lo
    ops = [o for o in ops if inside(o.start, o.dur)]
    busy, merged = _union((max(o.start, lo), min(o.end, hi)) for o in ops)
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    return Summary(window=window, ops=_leaves(ops),
                   modules=[m for m in modules if inside(m[1], m[2])],
                   host=[h for h in host if inside(h[1], h[2])],
                   busy_ns=busy, gaps=gaps)


def kernels(summary: Summary):
    return [o for o in summary.ops if is_kernel(o.text)]


def op_label(text: str) -> str:
    """Short name of an op for the breakdown: the kernel's name, or the
    HLO instruction name without its number."""
    if is_kernel(text):
        return kernel_name(text)
    return re.sub(r"(\.[\w\-]+)+$", "", instruction_name(text))


def top_ops(summary: Summary, n: int = 10):
    agg = {}
    for o in summary.ops:
        k = op_label(o.text)
        agg[k] = agg.get(k, 0.0) + o.dur
    return [[k, v / 1e9] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(summary: Summary, n: int = 10, own_files=()):
    """The longest idle gaps, each named by the innermost host event that
    covers its middle: a Python frame of one of ``own_files`` (basenames of
    the program's and the benchmark's sources) or a ``bench.*`` span when
    one covers it, else any host event."""
    def own(name):
        if name.startswith("bench.") and name != WINDOW_SPAN:
            return True
        return name.startswith("$") and name[1:].split(":")[0] in own_files
    out = []
    for s, e in sorted(summary.gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        cover = [h for h in summary.host
                 if h[1] <= mid <= h[1] + h[2] and h[0] != WINDOW_SPAN]
        mine = [h for h in cover if own(h[0])] or cover
        name = min(mine, key=lambda h: h[2])[0] if mine else "host (untraced)"
        out.append([name, (e - s) / 1e9])
    return out

