"""The 99th percentile of client latency in the closed-loop cells, read as
``p99_ms.tail`` reads it: two host stalls in a window lift it from about
62 to 176 ms, so it stands beside the judged ``p90_ms``."""
from chipbench.bench import load_file_module

read = load_file_module("metrics", "p99_ms.tail.py").read
