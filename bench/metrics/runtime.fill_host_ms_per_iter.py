"""Host milliseconds per iteration that the fill spent not blocked on the
device: the wall time of the program's ``runtime.fill`` spans less that of
the engine's blocking fetches (``engine.fetch``, all inside the fill), from
the ``host_ns/*`` counters, over the window's iterations."""


def read(w):
    fill = w.counters.get("host_ns/runtime.fill")
    fetch = w.counters.get("host_ns/engine.fetch")
    if fill is None or fetch is None or not w.iterations:
        return None
    return (fill - fetch) / w.iterations / 1e6
