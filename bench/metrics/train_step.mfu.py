"""Training model operations of the steps completed in the window (forward
and backward, causal attention, recomputation not counted) over the
window's time at the chips' peak."""
import flops


def read(w):
    if w.peaks is None or not w.train_steps:
        return None
    t = w.config["train"]
    step = flops.train_step(w.config, t["global_batch"], t["seq_len"])
    return 100.0 * w.train_steps * step / (w.window_s * w.chips * w.peaks["bf16_flops_per_s"])
