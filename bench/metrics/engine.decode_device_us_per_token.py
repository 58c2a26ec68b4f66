"""Device microseconds of the engine's fused decode loop (the program
``jit_decode_loop``, from the profiler trace) per token it decoded in the
window."""
import devtrace

PROGRAM = "jit_decode_loop"


def read(w):
    if w.trace is None or not w.decoded:
        return None
    secs = devtrace.seconds(w.trace["program_s"], PROGRAM)
    return secs / len(w.decoded) * 1e6 if secs > 0 else None
