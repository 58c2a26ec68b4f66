"""Device microseconds of the engine's prefill programs (every program
whose name starts ``jit_prefill``: the chunk waves, and the monolithic and
prefix-suffix prefills, from the profiler trace) per prefilled token the
engine metered in the window (``engine/prefill_metered_tokens``)."""
import devtrace

PROGRAM = "jit_prefill"


def read(w):
    tokens = w.counters.get("engine/prefill_metered_tokens", 0)
    if w.trace is None or not tokens:
        return None
    secs = devtrace.seconds(w.trace["program_s"], PROGRAM)
    return secs / tokens * 1e6 if secs > 0 else None
