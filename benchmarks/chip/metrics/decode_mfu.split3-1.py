"""``decode_mfu.offline`` read in the 3+1 split cell, whose throughput is a metric
of its own (``tokens_per_s.split3-1``) with a bound of its own."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("decode_mfu.offline.py")))["read"]
