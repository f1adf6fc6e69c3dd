"""Share of the decode program's device time in the traced span that
its least HBM traffic accounts for at the chip's peak bandwidth: per
decode step, the weights a step over its resident rows must read
(bench/work/) plus K and V of every cached position those rows attend
over, over 819 GB/s; summed and divided by the decode executions' busy
device time."""


def read(record):
    from metrics.common import busy_s, program_runs
    from work import common as wc

    m, work = record["m"], record["work"]
    kvb = wc.kv_bytes_per_token(m)
    need = spent = 0.0
    for st, dec, _ in program_runs(record):
        if dec is None:
            continue
        need += work.weight_bytes(m, st.decode_rows) + kvb * st.kv_positions
        spent += busy_s(dec)
    if spent <= 0:
        return None
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / spent
