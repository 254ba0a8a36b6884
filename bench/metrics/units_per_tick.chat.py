"""Units the SpecEE layer loop ran per device tick in the window: the
program's ``units_run`` counter over its ``ticks``, as the ``serve.collect``
spans of the traced window carry them. A batch runs to its deepest row, so
this sits at or above the mean exit (``exit_unit_mean``)."""
from bench import spans


def read(run):
    p = spans.of_run(run)
    if p is None or not p.counters.get("ticks"):
        return None
    return p.counters["units_run"] / p.counters["ticks"]
