"""Share of its roofline that the decode program reaches on reuse steps:
the least time the step could take (the larger of the bytes it must move
over HBM bandwidth and the operations it must do over peak, counted from
shapes by harness/work.py: every weight once, the attended KV once)
over its device time, summed over the traced reuse steps."""
from harness import work as w
from harness.readers import DECODE, polls_of


def read(run):
    if run.trace is None:
        return None
    conf, pk = run.cell.config, run.peaks
    least = busy = 0.0
    for p, dev in polls_of(run, "reuse"):
        if DECODE not in dev or not p.decode_ctx:
            continue
        nbytes = w.decode_step_bytes(conf, p.decode_ctx)
        flops = sum(w.decode_flops(conf, c) + w.head_flops(conf)
                    for c in p.decode_ctx)
        least += max(nbytes / pk["hbm_bytes_per_s"],
                     flops / pk["bf16_flops_per_s"])
        busy += dev[DECODE]
    return 100.0 * least / busy if busy else None
