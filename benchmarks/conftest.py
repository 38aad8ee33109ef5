"""The benchmark's CPU tests run cells that ask for four chips: give the
CPU backend as many host devices, before anything imports JAX."""

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()
