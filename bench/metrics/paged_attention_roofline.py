"""Paged decode attention's share of its roofline: kernel time from the
trace against the bytes and operations that each call needs for the
contexts of the rows holding a slot."""
from bench import roofline
from bench.metrics._common import slotted_context
from bench.trace import DECODE_KERNEL


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.kernel(DECODE_KERNEL)
    context, rows = slotted_context(run)
    if not calls or not seconds or not context:
        return None
    flops, nbytes = roofline.paged_attention(run.arch, context, round(rows))
    return roofline.share(calls * flops, calls * nbytes, seconds, run.peaks)
