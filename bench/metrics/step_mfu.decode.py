"""FLOP utilization of the decode step on the device: the operations the
model's equations need for the decode steps of the traced span (every
GEMM and the LM head over the resident rows, attention over their cached
positions; bench/work/) over the busy device time of the decode
executions, times the chip's bf16 peak."""


def read(record):
    from metrics.common import busy_s, program_runs
    from work import common as wc

    m, work = record["m"], record["work"]
    head = wc.gemm_flops(1, m["vocab"], m["d_model"])
    att = m["n_layers"] * wc.attention_flops(m, 1)
    flops = spent = 0.0
    for st, dec, _ in program_runs(record):
        if dec is None:
            continue
        flops += (st.decode_rows * (work.row_flops(m) + head)
                  + att * st.kv_positions)
        spent += busy_s(dec)
    if spent <= 0:
        return None
    return 100.0 * flops / (spent * record["peaks"]["bf16_flops_per_s"])
