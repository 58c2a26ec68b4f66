"""p95 time to first token of the online requests submitted in the traced
window, on the program's own wall stamps: from each request's submission
(``arrival_wall_ns``) to the host's receipt of its first token, right after
the fetch that delivered it (``first_token_wall_ns``).  A request counts if
it was submitted before the window's last quantum record ended."""
import program_stamps


def read(w):
    return program_stamps.online_p95_ms(w, "first_token_wall_ns")
