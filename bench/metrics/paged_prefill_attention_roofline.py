"""Share of its roofline the paged chunked-prefill attention kernel
reached: the least time the window's prefills allow (every prompt row
against its earlier positions, chunk by chunk, in every layer) over the
device time of the kernel's operations, which the trace names after the
function that builds the Pallas call."""
import flops
import work

KERNEL = "paged_prefill_attention"


def read(w):
    c, L = w.config, w.config["num_hidden_layers"]
    f = b = 0
    for start, end in w.prefilled:
        for s, m in flops.chunks(start, end, w.chunk):
            cf, cb = flops.prefill_chunk_attention(c, s, m, w.page)
            f, b = f + cf * L, b + cb * L
    return work.roofline(w, KERNEL, f, b)
