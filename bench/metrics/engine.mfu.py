"""The inference model operations the window needed (every decoded token's
layers, attention and vocabulary projection; every prefilled row, and one
vocabulary projection per prefill) over the window's time at the chips'
peak."""
import flops


def read(w):
    c = w.config
    total = sum(flops.decode_token(c, n) for n in w.decoded)
    total += sum(flops.prefill_rows(c, s, e) + flops.unembed(c) for s, e in w.prefilled)
    if w.peaks is None or not total:
        return None
    return 100.0 * total / (w.window_s * w.chips * w.peaks["bf16_flops_per_s"])
