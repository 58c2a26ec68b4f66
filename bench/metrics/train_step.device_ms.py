"""Device milliseconds of the train step's program per step, from the
profiler trace of the window."""
import devtrace

TRAIN_PROGRAM = "jit_train_step"


def read(w):
    if w.trace is None or not w.train_steps:
        return None
    secs = devtrace.seconds(w.trace["program_s"], TRAIN_PROGRAM)
    return secs / w.train_steps * 1e3 if secs > 0 else None
