"""Host time the serving engine spends handing work to the device, per
scheduler tick, in ms: the summed time of its ``engine.prefill.upload``,
``engine.decode.upload`` (control arrays to the device) and
``engine.*.launch`` spans (the jitted call until it returns) in the
window, over the number of ``engine.tick`` spans."""
from bench.program_trace import host_spans

_PARTS = ("engine.prefill.upload", "engine.prefill.launch",
          "engine.decode.upload", "engine.decode.launch")


def read(ctx):
    s = ctx["summary"]
    ticks = host_spans(s, lambda n: n == "engine.tick")
    if not ticks:
        return None
    return 1e-6 * sum(d for _, _, d in host_spans(s, lambda n: n in _PARTS)) / len(ticks)
