"""Process start to the first timed iteration: weights, warm-up, compile
cache loads and the checked training iterations."""


def read(w):
    return w.setup_s
