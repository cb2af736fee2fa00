"""mfu.prefill: the operations of every prompt and output token the engine
processed in the traced window (prefills at their real length, decode
rows at their positions; ``bench/counts.py``) over the time the engine
had work, times the chip's peak. That time is the window less the open
loop's ``bench/idle`` (nothing in the engine, the next arrival not yet
due), so the reading does not follow the offered load."""

from bench import counts


def read(trace, facts):
    conf = facts["conf"]
    flops = sum(counts.prefill_flops(conf, n) for n in facts["prefill_lens"])
    flops += sum(counts.decode_step_flops(conf, rows)
                 for rows in facts["decode_rows"])
    engaged = trace.engaged_s()
    if flops <= 0 or engaged <= 0:
        return None
    return 100.0 * flops / (engaged * facts["chips"]
                            * facts["peak"]["flops_per_s"])
