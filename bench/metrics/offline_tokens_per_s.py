"""Offline tokens generated in the window (the ``core/generated_tokens/offline``
counter) over the window's wall time."""


def read(w):
    return w.counters.get("core/generated_tokens/offline", 0) / w.window_s
