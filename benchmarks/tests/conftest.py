"""The rehearsals start ``run.py`` in a child process with this process's
environment: a ``chips: 4`` cell needs four CPU devices there, so the flag
is set here, before any test (and before JAX, which reads it at start-up)."""

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=4").strip()
