"""What the program reports about itself in a profiler trace: the
``(site, pass)`` tag of each LUT kernel launch, and the serving engine's
``engine.*`` spans.

A kernel's tag is the ``kernel_metadata`` in its op text
(``frontend_attributes={kernel_metadata={"pass":"dx","site":"head"}}``,
the JSON spread over several lines), or, on a ``kind=kCustom`` fusion
built around the kernel, which keeps no attributes, the dotted suffix of
its instruction name (``%_approx_gemm_impl.wd.dw.11``); both are written
by ``repro.kernels.common.tagged_pallas_call``.  The engine's spans are host
events (``repro.obs.span``); the fields given to a span are the event's
stats, which ``bench.trace.Summary`` does not keep, so ``span_stats``
reads them again from the trace file.

A program that reports neither reads as nothing here: every function
returns None, 0 or an empty list, and none raises.
"""
from __future__ import annotations

import json
import re
import warnings

from bench import harness
from bench import trace as T

_TAG = re.compile(r"kernel_metadata=\{([^{}]*)\}")
_NAMED = re.compile(r"\.(\w+?)\.(fwd|dx|dw)_*(?:\.|$)")


def kernel_tag(text: str):
    """``(site, pass)`` of a kernel op, or None when it carries no tag."""
    m = _TAG.search(text)
    if m and m.group(1).strip():
        try:
            tag = json.loads("{" + m.group(1) + "}")
            return tag.get("site"), tag.get("pass")
        except ValueError:
            pass
    m = _NAMED.search(T.instruction_name(text))
    return (m.group(1), m.group(2)) if m else None


def kernel_time_by_tag(summary) -> dict:
    """Device ns of the window's kernel ops by tag (None: untagged)."""
    out = {}
    for op in T.kernels(summary):
        tag = kernel_tag(op.text)
        out[tag] = out.get(tag, 0.0) + op.dur
    return out


def kernel_share(summary, keep) -> float | None:
    """Percent of the window's kernel device time whose tag ``keep``
    accepts (``keep(None)`` for untagged ops); None when no kernel op
    carries a tag."""
    by_tag = kernel_time_by_tag(summary)
    total = sum(by_tag.values())
    if total <= 0 or all(tag is None for tag in by_tag):
        return None
    return 100.0 * sum(ns for tag, ns in by_tag.items() if keep(tag)) / total


def host_spans(summary, match) -> list:
    """(name, start, dur) of the window's host events whose name
    ``match`` accepts."""
    return [h for h in summary.host if match(h[0])]


def completion_lags(summary) -> list:
    """For each ``engine.*.wait`` span in the window, ns from the end of
    the device's last program run started before the wait ended to the
    wait's end: how long the host took to see a result the device had
    finished.  0 when no run started before it, or when that run ends
    after the wait (the two clocks' skew)."""
    runs = [(s, s + d) for _, s, d in summary.modules]
    lags = []
    for _, s, d in host_spans(summary, lambda n: n.startswith("engine.")
                              and n.endswith(".wait")):
        end = s + d
        done = max((e for rs, e in runs if rs < end), default=None)
        lags.append(0.0 if done is None else max(0.0, end - done))
    return lags


def span_stats(ctx, name: str) -> list:
    """The stats of each host event ``name`` in the window, as dicts.

    Read from the trace ``bench/run.py`` keeps under
    ``.bench_traces/<workload>-<seed>/`` until the result line is made:
    the file whose ``bench.window`` span starts where the summary's
    window does."""
    from jax.profiler import ProfileData
    lo, hi = ctx["summary"].window
    for d in sorted((harness.ROOT / ".bench_traces").glob(f"{ctx['cell'].name}-*")):
        try:
            path = T.trace_file(d)
        except FileNotFoundError:
            continue
        events = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(str(path)).planes:
                if not plane.name.startswith("/host:CPU"):
                    continue
                for line in plane.lines:
                    events += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                               for e in line.events
                               if e.name in (name, T.WINDOW_SPAN)]
        if any(n == T.WINDOW_SPAN and s == lo for n, s, _, _ in events):
            return [st for n, s, dur, st in events
                    if n == name and s < hi and s + dur > lo]
    return []
