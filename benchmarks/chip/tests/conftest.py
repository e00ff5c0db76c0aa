"""Four host devices for the CPU tests, so that the 3+1 split placement
(stage 1 on a 3-device submesh, stage 2 on the fourth) runs here as it runs
on one v5e-4 host. One-chip cells take the first device alone."""
import os

_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}"
