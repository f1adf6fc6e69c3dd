"""What several metric readers share: the window's counters, per-request
numbers and the traced executions of the decode and chunk programs."""

from __future__ import annotations

import math
import re

# trace operations of the tiled GEMM kernel: the Pallas kernel's name
KERNEL_PATTERNS = ("tiled_matmul",)
# asynchronous copies the compiler puts between operations: markers that
# take no time of their own
ASYNC_MARKS = ("copy-start", "copy-done")
RESULT_DIMS = re.compile(r"^\S+ [a-z0-9]+\[([0-9,]*)\]")


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile; None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def finished(record) -> list[tuple]:
    """(arrival, result) of every request due in the window that came
    back."""
    res = record["run"]["results"]
    return [(a, res[a.uid]) for a in record["run"]["arrivals"]
            if a.uid in res]


def window(record) -> tuple[dict, dict, float]:
    """(counters at the start, counters at the close, window seconds)."""
    run = record["run"]
    return run["report0"], run["close"], run["close"]["t"] - run["t0"]


def is_kernel(op) -> bool:
    return any(p in op.text for p in KERNEL_PATTERNS)


def in_scope(run, part: str) -> list:
    """The operations of `run` whose scope (their `op_name` path) holds
    `part` as one of its `/`-separated components, such as
    `jit(engine_decode)` or a `jax.named_scope` name."""
    return [op for op in run if part in op.scope.split("/")]


def busy_s(ops) -> float:
    """Seconds in which any of `ops` ran (the union of their intervals)."""
    total, end = 0.0, -math.inf
    for op in sorted(ops, key=lambda o: o.start):
        a, b = max(op.start, end), op.end
        if b > a:
            total += b - a
        end = max(end, b)
    return total


def program_runs(record):
    """Yield (step, decode run or None, chunk runs) for every traced step:
    within a step the decode program runs last (nothing is dispatched
    after it), and the chunk programs are the other executions that hold
    GEMM kernel operations (splices and zero fills hold none)."""
    tr = record["trace"]
    if tr is None:
        return
    for st, runs in zip(tr["steps"], tr["step_runs"]):
        if not runs:
            continue
        dec = runs[-1] if st.decode_rows > 0 else None
        rest = runs[:-1] if dec is not None else runs
        yield st, dec, [r for r in rest if any(is_kernel(o) for o in r)]


def result_dims(op) -> tuple[int, ...] | None:
    """Dimensions of an operation's array result, leading 1s dropped;
    None for a tuple."""
    got = RESULT_DIMS.match(op.text)
    if got is None:
        return None
    dims = tuple(int(x) for x in got.group(1).split(",") if x)
    while len(dims) > 1 and dims[0] == 1:
        dims = dims[1:]
    return dims


def kernel_calls(run, k_min: int) -> list[tuple]:
    """(call, the operations that stage its weight operand) for each of
    the tiled kernel's calls in one execution.

    The compiler reads a layer's (K, N) weight out of the stacked
    parameters with a dynamic-slice fusion into on-chip memory, and pads
    it where the kernel's block does not divide it, right before the
    call; the call then reads the staged copy. On a v5e that slice moved
    the weight at about 750 GB/s and the call took a third as long again
    (chip run): without the slice, the call's time would not hold the
    weight's HBM read. The staging operations are the ones right before
    the call (asynchronous copy markers passed over) whose result is a
    matrix of more rows than the call's result, at least `k_min` rows
    (the smallest K of the kernel's GEMMs) and no more columns."""
    out = []
    for i, op in enumerate(run):
        if not is_kernel(op):
            continue
        rows, cols = result_dims(op)[-2:]
        staged, j = [], i - 1
        while j >= 0:
            prev = run[j]
            j -= 1
            if prev.name.lstrip("%").startswith(ASYNC_MARKS):
                continue
            d = result_dims(prev)
            if (is_kernel(prev) or d is None or len(d) != 2
                    or d[0] <= rows or d[0] < k_min or d[1] > cols):
                break
            staged.append(prev)
        out.append((op, staged))
    return out


def kernel_time(run, k_min: int) -> float:
    """Device seconds of the tiled kernel's calls in one execution, each
    with the operations that stage its weight (`kernel_calls`)."""
    return sum(op.dur + sum(o.dur for o in staged)
               for op, staged in kernel_calls(run, k_min))


def kernel_least_s(record, rows: int, head_rows: int,
                   executions: int) -> float:
    """Least time of the GEMMs the tiled kernel runs in one step over
    `rows` token rows and `head_rows` LM-head rows, spread over
    `executions` program executions (each reads every weight once): for
    each GEMM, the larger of its operations at the bf16 peak and its
    bytes at the HBM bandwidth (bench/work/). With all rows counted in
    each GEMM's one term this is at most the sum over executions."""
    from work import common as wc

    m, pk = record["m"], record["peaks"]
    flops_s, bytes_s = pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]

    def least(M, N, K, out=wc.BF16):
        return max(wc.gemm_flops(M, N, K) / flops_s,
                   wc.gemm_bytes(M, N, K, executions, out) / bytes_s)

    t = m["n_layers"] * sum(c * least(rows, n, k)
                            for n, k, c in record["work"].kernel_gemms(m))
    if head_rows > 0:
        # the head returns float32 logits
        t += least(head_rows, m["vocab"], m["d_model"], out=4)
    return t


def kernel_k_min(record) -> int:
    return min(k for _, k, _ in record["work"].kernel_gemms(record["m"]))
