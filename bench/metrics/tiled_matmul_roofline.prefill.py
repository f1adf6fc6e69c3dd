"""Roofline share of the tiled GEMM kernel in the chunked-prefill
programs: the least time of the GEMMs it runs there (every projection
over the prompt tokens the step prefilled, the LM head over the prompts
it finished), over the device time of the kernel's calls inside the
chunk executions of the traced span, each call with the operations that
stage its weight (`metrics.common.kernel_time`).

The least time of each GEMM is the larger of its operations at the bf16
peak and its HBM bytes at the HBM bandwidth (bench/work/), each chunk
execution reading every weight once. A step's chunk GEMMs run some
hundreds of rows, near the v5e's 240 operations per byte, so either
bound can hold."""


def read(record):
    from metrics.common import (kernel_k_min, kernel_least_s, kernel_time,
                                program_runs)

    k_min = kernel_k_min(record)
    need = spent = 0.0
    for st, _, chunks in program_runs(record):
        t = sum(kernel_time(r, k_min) for r in chunks)
        if t <= 0:
            continue
        need += kernel_least_s(record, st.prompt_tokens, st.first_tokens,
                               len(chunks))
        spent += t
    if spent <= 0:
        return None
    return 100.0 * need / spent
