"""``tick_ms.offline`` read in the 3+1 split cell, whose throughput is a metric
of its own (``tokens_per_s.split3-1``) with a bound of its own. There the
tick is the composed one (program ``_pool_tick`` on the three stage-1
chips), which the reader's ``pool_tick`` matches; each chip's run of it
counts once, so the value is a chip's time per tick."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("tick_ms.offline.py")))["read"]
