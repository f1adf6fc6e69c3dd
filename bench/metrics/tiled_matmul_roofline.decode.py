"""Roofline share of the tiled GEMM kernel in the decode program: the
least time of the GEMMs it runs there (every projection and the LM head
over the resident rows), over the device time of the kernel's calls
inside the decode executions of the traced span, each call with the
operations that stage its weight (`metrics.common.kernel_time`).

The least time of each GEMM is the larger of its operations at the bf16
peak and its HBM bytes (the weight once, input and result) at the HBM
bandwidth (bench/work/). At decode's few rows the bytes bound every GEMM:
a GEMM over M rows does about M operations per weight byte, and the v5e
needs 240 to be bound by its operations."""


def read(record):
    from metrics.common import (kernel_k_min, kernel_least_s, kernel_time,
                                program_runs)

    k_min = kernel_k_min(record)
    need = spent = 0.0
    for st, dec, _ in program_runs(record):
        if dec is None:
            continue
        t = kernel_time(dec, k_min)
        if t <= 0:
            continue
        need += kernel_least_s(record, st.decode_rows, st.decode_rows, 1)
        spent += t
    if spent <= 0:
        return None
    return 100.0 * need / spent
