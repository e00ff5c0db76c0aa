"""``tick_ms.offline`` read in the DeepSeek-V2-Lite EP4 cell, whose per-layer
metrics are its own."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("tick_ms.offline.py")))["read"]
