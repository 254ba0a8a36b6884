"""Tiny sizes of the benchmark's configurations and mixes, for CPU tests.

Widths shrink and the vocabulary with them; the logit scale stays about 1
(initializer_range = d^-1/2), as it is at the published widths, so the
pinned exits fire at their stated share.
"""
import time

from bench import harness, sut, traffic, weights  # noqa: F401 (sut: the program on sys.path)


def config(name: str, d: int = 64, vocab: int = 512) -> dict:
    cfg = weights.load_config(name)
    kv = 4 if cfg["num_key_value_heads"] == cfg["num_attention_heads"] else 2
    return dict(cfg, hidden_size=d, intermediate_size=2 * d,
                num_attention_heads=4, num_key_value_heads=kv,
                num_hidden_layers=3, vocab_size=vocab,
                initializer_range=d ** -0.5,
                serving=dict(max_batch=2, max_seq_len=256, page_size=16,
                             prefill_chunk=32, megatick=2))


def mix(name: str) -> dict:
    m = traffic.load(name)
    if m["loop"] == "open":
        return dict(m, prompt=dict(m["prompt"], min=16, max=96, median=48,
                                   grid=16),
                    output=dict(m["output"], min=8, max=40, median=20),
                    rate_per_s=2.0, ramp_s=1.0)
    return dict(m, prompt=dict(m["prompt"], min=16, max=64, grid=16),
                output=dict(m["output"], min=16, max=32),
                requests_per_client=16)


def run(workload: str, seed: int = 3, seconds: float = 3.0,
        limit: float = 0.05, **kw) -> dict:
    """One CPU run of a cell at tiny size, the chip check skipped."""
    cfg_name, traf = workload.split(".", 1)
    cell = {"name": workload, "config": cfg_name, "traffic": traf,
            "chips": 1}
    seconds = seconds if traffic.load(traf)["loop"] == "open" else 0.5
    return harness.run_cell(workload, seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            config=kw.pop("config", None) or config(cfg_name),
                            mix=kw.pop("mix", None) or mix(traf), cell=cell,
                            limit=limit, **kw)
