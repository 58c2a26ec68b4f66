"""Share of its roofline the paged decode attention kernel reached: the
least time the window's decoded tokens allow (each token's query against
its cached positions, through whole pages, in every layer; bytes bind at
decode) over the device time of the kernel's operations, which the trace
names after the function that builds the Pallas call."""
import flops
import work

KERNEL = "paged_decode_attention"


def read(w):
    c, L = w.config, w.config["num_hidden_layers"]
    f = b = 0
    for n in w.decoded:
        df, db = flops.decode_attention(c, n, w.page)
        f, b = f + df * L, b + db * L
    return work.roofline(w, KERNEL, f, b)
