"""Training tokens of every step completed in the window over the window's
wall time, the fill between steps included."""


def read(w):
    return w.train_steps * w.tokens_per_step / w.window_s
