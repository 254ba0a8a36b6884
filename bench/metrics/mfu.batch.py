"""The served batch's share of the chip's bf16 peak: dense model operations
per token (configured depth and one LM-head pass, whatever the program
skips or repeats) times the tokens delivered in the window, over the
window."""
from bench import roofline
from bench.metrics._common import window_tokens


def read(run):
    if run.trace is None:
        return None
    n, context = window_tokens(run)
    ws, we = run.window
    if not n:
        return None
    flops = n * roofline.decode_token_flops(run.arch, context)
    return 100.0 * flops / (we - ws) / run.peaks["bf16_flops"]
