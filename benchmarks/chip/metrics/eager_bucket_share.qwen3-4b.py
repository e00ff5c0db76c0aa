"""``eager_bucket_share.offline`` read in the qwen3-4b cell, whose throughput is a metric of
its own (``tokens_per_s.qwen3-4b``) with a bound of its own."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("eager_bucket_share.offline.py")))["read"]
