"""p95 wall-clock queue wait of the online requests submitted in the traced
window: from each request's submission (``arrival_wall_ns``) to its first
admission into a slot (``admit_wall_ns``), the same requests as
``scheduler.online_ttft_p95_ms``."""
import program_stamps


def read(w):
    return program_stamps.online_p95_ms(w, "admit_wall_ns")
