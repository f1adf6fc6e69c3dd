"""`tiled_matmul_roofline.decode`, read the same way, in a cell that
holds no TPOT end to end: there it moves `ttft_p90_s`, through the steps
in which a request's decode shares the device with another's prefill
chunks."""

from harness import metric_reader

read = metric_reader("tiled_matmul_roofline.decode")
