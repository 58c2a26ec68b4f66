"""Offline tokens the fill produced per training iteration (Algorithm 1's
grants at work): the ``core/generated_tokens/offline`` counter over the
window's iterations."""


def read(w):
    if not w.iterations:
        return None
    return w.counters.get("core/generated_tokens/offline", 0) / w.iterations
