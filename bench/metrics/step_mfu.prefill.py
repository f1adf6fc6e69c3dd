"""FLOP utilization of the chunked-prefill steps on the device: the GEMM
operations the model's equations need for the prompt tokens the traced
steps prefilled, and the LM head for the prompts they finished
(bench/work/; attention over the cached prefix is not counted, so this
is a lower bound), over the busy device time of the chunk executions,
times the chip's bf16 peak."""


def read(record):
    from metrics.common import busy_s, program_runs
    from work import common as wc

    m, work = record["m"], record["work"]
    head = wc.gemm_flops(1, m["vocab"], m["d_model"])
    flops = spent = 0.0
    for st, _, chunks in program_runs(record):
        if not chunks:
            continue
        flops += st.prompt_tokens * work.row_flops(m) + st.first_tokens * head
        spent += sum(busy_s(r) for r in chunks)
    if spent <= 0:
        return None
    return 100.0 * flops / (spent * record["peaks"]["bf16_flops_per_s"])
