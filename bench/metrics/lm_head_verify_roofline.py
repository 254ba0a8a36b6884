"""The LM-head streaming kernels' share of their roofline (the argmax
verify and the draft's top-k): kernel time from the trace against one
pass of the whole batch over the head per call."""
from bench import roofline

KERNELS = ("argmax_verify", "topk_verify")


def read(run):
    if run.trace is None:
        return None
    seconds = calls = 0
    for k in KERNELS:
        s, n = run.trace.kernel(k)
        seconds, calls = seconds + s, calls + n
    if not calls or not seconds:
        return None
    rows = run.arch.cfg["serving"]["max_batch"]
    flops, nbytes = roofline.lm_head_pass(run.arch, rows)
    return roofline.share(calls * flops, calls * nbytes, seconds, run.peaks)
