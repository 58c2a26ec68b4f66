"""Device microseconds of the engine's programs (every program but the
train step: decode loops, prefill chunks, the small cache updates) per
inference token, prefilled or generated."""
import devtrace

TRAIN_PROGRAM = "jit_train_step"


def read(w):
    if w.trace is None:
        return None
    progs = w.trace["program_s"]
    engine_s = sum(progs.values()) - devtrace.seconds(progs, TRAIN_PROGRAM)
    tokens = (w.counters.get("engine/prefill_metered_tokens", 0)
              + w.counters.get("engine/generated_tokens", 0))
    if not tokens or engine_s <= 0:
        return None
    return engine_s / tokens * 1e6
