"""``paged_attn_roofline.offline`` read in the 3+1 split cell, whose
throughput is a metric of its own (``tokens_per_s.split3-1``) with a bound
of its own. The kernel runs on the stage-2 chip alone, so its trace time is
that one chip's and the roofline is one chip's bandwidth."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("paged_attn_roofline.offline.py")))["read"]
