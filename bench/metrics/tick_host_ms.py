"""Host time of a scheduler tick: the wall time of ``engine.step()`` minus
the change in the engine's ``busy_seconds`` (its prefill and decode calls,
each waited to completion), averaged over the window's ticks, in ms."""


def read(ctx):
    ticks = ctx["counts"].get("ticks")
    if not ticks:
        return None
    host = [k["end"] - k["start"] - k["busy"] for k in ticks]
    return 1e3 * sum(host) / len(host)
