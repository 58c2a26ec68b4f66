"""Share of the decode batch's slot-steps that produced a token: tokens
decoded over (decode microsteps x slots), from the engine core's
per-quantum records."""


def read(w):
    if not w.decode_microsteps:
        return None
    return 100.0 * len(w.decoded) / (w.decode_microsteps * w.max_slots)
