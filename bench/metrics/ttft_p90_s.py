"""90th percentile of time to first token over every request due in the
window: from its due time (so a late submission counts) to its first
token, in seconds."""

from metrics.common import finished, percentile


def read(record):
    return percentile([r.ttft_s for _, r in finished(record)], 90)
