"""Share of the window's decode loops whose outputs the host read back after
it had dispatched the next quantum: the program's counter
``engine/overlapped_readbacks`` over the quanta that ran a decode loop (the
``quantum`` records with ``k`` > 0).  A program without the counter reads
nothing."""


def read(w):
    overlapped = w.counters.get("engine/overlapped_readbacks")
    loops = sum(1 for ev in w.spans
                if ev.get("type") == "quantum" and ev["args"].get("k", 0) > 0)
    if overlapped is None or not loops:
        return None
    return overlapped / loops
