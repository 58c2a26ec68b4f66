"""What the engine did in the window, from the program's own per-quantum
spans and the requests' token streams, in the terms the readers of
``metrics/`` count work in: the attended length of every decoded token, the
prompt positions of every prefill, the decode microsteps.  A reader turns
these into operations and bytes with ``flops.py`` (or a function of its
own) and into a roofline share with ``roofline``."""
from __future__ import annotations

import devtrace


def decoded_lengths(reqs: dict, start_outs: dict, end_outs: dict) -> list:
    """The positions each token decoded in the window attends: a request's
    output token j >= 1 attends ``len(prompt) + j`` positions (token 0
    comes from its prefill)."""
    out = []
    for rid, b in end_outs.items():
        n = len(reqs[rid].prompt)
        out.extend(n + j for j in range(max(start_outs.get(rid, 0), 1), b))
    return out


def prefill_ranges(spans: list, ev0: int, reqs: dict, outs_hist: dict) -> list:
    """``(start, end)`` prompt positions computed by each ``prefill_chunk``
    span of the window: the span names a request and the tokens computed for
    it in one quantum (the whole remaining prompt: the fill's prefill is not
    metered); they end where the prompt, with the tokens served before the
    admission, ends."""
    out = []
    for i, ev in enumerate(spans):
        if ev.get("type") != "span" or ev.get("name") != "prefill_chunk":
            continue
        rid = ev["args"].get("request_id")
        if rid not in reqs:
            continue
        done = 0
        for e_idx, outs in outs_hist.get(rid, []):
            if e_idx <= ev0 + i:
                done = outs
        end = len(reqs[rid].prompt) + done
        out.append((end - ev["args"]["tokens"], end))
    return out


def decode_microsteps(spans: list) -> int:
    """Decode steps the engine's quanta ran (the ``k`` of every quantum)."""
    return sum(ev["args"].get("k", 0) for ev in spans if ev.get("type") == "quantum")


def roofline(w, prefixes, flops: float, nbytes: float):
    """Share (%) of its roofline a kernel reached: the least time its work
    allows (operations at peak FLOP/s or bytes at peak HBM bandwidth,
    whichever binds) over its device time, its operations found in the
    trace by the prefixes of their names.  None where nothing was read."""
    if w.peaks is None or w.trace is None or not nbytes:
        return None
    secs = devtrace.seconds(w.trace["op_s"], prefixes)
    if secs <= 0:
        return None
    least = max(flops / w.peaks["bf16_flops_per_s"], nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
