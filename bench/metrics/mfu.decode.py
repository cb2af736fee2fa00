"""mfu.decode: the operations of every prompt and output token the engine
processed in the traced window (prefills at their real length, decode
rows at their positions; ``bench/counts.py``) over the window times the
chip's peak."""

from bench import counts


def read(trace, facts):
    conf = facts["conf"]
    flops = sum(counts.decode_step_flops(conf, rows)
                for rows in facts["decode_rows"])
    flops += sum(counts.prefill_flops(conf, n) for n in facts["prefill_lens"])
    return 100.0 * flops / (trace.mean_window_s() * facts["chips"]
                            * facts["peak"]["flops_per_s"])
