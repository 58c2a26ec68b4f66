"""Device milliseconds per train step of the collectives the trace names
as such: every operation named an all-gather, all-reduce, reduce-scatter,
collective-permute or all-to-all, with their asynchronous start/done halves
(``async-collective-*``), as the TPU v5e compile of the sharded train step
names them.  A lower bound on the step's collective time: the collectives
XLA fuses with the matmuls they feed, or into a reduce-scatter fusion, are
operations named ``fusion.<n>`` in the summary and are not counted (on the
OLMo-1B 2 x 2 step, 168 + 15 of its ≈ 220 ms of collectives).  The
summary's average over the chips is the time on each, since every chip runs
the same step.  None when the window ran no such operation."""
import devtrace

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all", "async-collective")


def read(w):
    if w.trace is None or not w.train_steps:
        return None
    secs = devtrace.seconds(w.trace["op_s"], COLLECTIVES)
    return secs / w.train_steps * 1e3 if secs > 0 else None
