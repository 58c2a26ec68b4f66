"""Host milliseconds per iteration spent outside the train step: the
iteration's wall time less the wrapped train-step call (which returns once
the step's loss is on the host)."""


def read(w):
    if not w.iterations:
        return None
    return (sum(w.iteration_s) - sum(w.train_s)) / w.iterations * 1e3
