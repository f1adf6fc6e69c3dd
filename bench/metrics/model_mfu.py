"""Model FLOP utilization of the whole serving step: the operations that
the model's equations (bench/work/) need for every request due in the
window, prompt and output tokens alike (GEMMs, causal attention over the
cached positions, the LM head for each emitted token), over the seconds
from the window's opening to the last of them finishing, times the
chip's bf16 peak."""


def read(record):
    from metrics.common import finished
    from work import common as wc

    m, work = record["m"], record["work"]
    run = record["run"]
    done = finished(record)
    if not done:
        return None
    row = work.row_flops(m)
    head = wc.gemm_flops(1, m["vocab"], m["d_model"])
    att = m["n_layers"] * wc.attention_flops(m, 1)
    flops, end = 0.0, run["t0"]
    for a, r in done:
        P, n = len(a.prompt), r.n_tokens
        flops += P * row + wc.prompt_attention_flops(m, P)
        # decode token k (1..n-1) attends over P + k positions
        flops += (n - 1) * row + att * ((n - 1) * P + (n - 1) * n / 2)
        flops += n * head
        end = max(end, run["t0"] + a.due_s + r.ttft_s + r.decode_s)
    return 100.0 * flops / ((end - run["t0"])
                            * record["peaks"]["bf16_flops_per_s"])
