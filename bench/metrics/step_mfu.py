"""Model FLOPs done in the traced window over the window times the
chip's peak: decode tokens (weights, attention over the attended
positions, LM head), prompt tokens fed by prefill chunks, and the LM
head of each first token (harness/work.py)."""
from harness import work as w


def read(run):
    if run.trace is None:
        return None
    conf = run.cell.config
    flops = 0.0
    for p in run.traced_polls:
        flops += sum(w.decode_flops(conf, c) + w.head_flops(conf)
                     for c in p.decode_ctx)
        flops += sum(w.prefill_flops(conf, s, n) for s, n in p.chunks)
        flops += p.first * w.head_flops(conf)
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * flops / (run.trace["window_s"] * peak)
