"""Host milliseconds per scheduling quantum: the wall time of the program's
``core.step`` spans less the engine's blocking fetches inside them
(``engine.fetch``), over the quanta stepped (``core/quanta``): planning,
admission, the block tables, packing and dispatch, absorbing the outputs
and the trace records."""


def read(w):
    step = w.counters.get("host_ns/core.step")
    fetch = w.counters.get("host_ns/engine.fetch")
    quanta = w.counters.get("core/quanta")
    if step is None or fetch is None or not quanta:
        return None
    return (step - fetch) / quanta / 1e6
