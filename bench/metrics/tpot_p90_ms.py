"""90th percentile, over every request due in the window, of its time
per output token after the first: decode seconds / (tokens - 1), in ms."""

from metrics.common import finished, percentile


def read(record):
    return percentile([1e3 * r.decode_s / (r.n_tokens - 1)
                       for _, r in finished(record) if r.n_tokens > 1], 90)
