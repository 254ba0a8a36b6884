"""Share of the window's device program time spent outside the decode
programs: chunked prefill, the admission tail and the serving loop's other
small programs, over all programs (device trace, programs by name)."""
from bench import spans


def read(run):
    p = spans.of_run(run)
    if p is None:
        return None
    total = sum(p.program_s.values())
    decode = sum(p.program_s.get(n, 0.0) for n in spans.DECODE_PROGRAMS)
    if not total or not decode:
        return None
    return 100.0 * (1.0 - decode / total)
