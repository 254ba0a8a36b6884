"""The decode step's share of the chip's bf16 peak: dense model operations
per decoded token (configured depth and one LM-head pass, whatever the
program skips or repeats) times the tokens decoded in the window, over the
device time of the programs that decode (those that run paged attention)."""
from bench import roofline
from bench.metrics._common import window_tokens


def read(run):
    if run.trace is None or not run.trace.decode_program_s:
        return None
    n, context = window_tokens(run, decode_only=True)
    if not n:
        return None
    flops = n * roofline.decode_token_flops(run.arch, context)
    return 100.0 * flops / run.trace.decode_program_s / \
        run.peaks["bf16_flops"]
