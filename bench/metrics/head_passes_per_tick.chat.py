"""Full LM-head passes per device tick in the window: the program's
``head_passes`` counter (the draft's top-k, each verify the loop ran, the
final emit) over its ``ticks``, as the ``serve.collect`` spans of the traced
window carry them."""
from bench import spans


def read(run):
    p = spans.of_run(run)
    if p is None or not p.counters.get("ticks"):
        return None
    return p.counters["head_passes"] / p.counters["ticks"]
