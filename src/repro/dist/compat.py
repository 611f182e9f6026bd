"""The CPU pin for forced host devices.

Forcing host-platform devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) is a CPU-only
mode: the multi-device tests and the 512-device dry-run use it to
rehearse meshes without accelerators.  ``install()`` pins the jax
platform to the CPU whenever that flag is set and the caller has not
chosen a platform; otherwise a machine with libtpu installed but no TPU
attached burns minutes probing the TPU backend before falling back to
the CPU.  It is idempotent.
"""

from __future__ import annotations

import os

import jax

__all__ = ["install"]


def install() -> None:
    # jax snapshots JAX_PLATFORMS at import, so update the live config
    # too (no-op if the backend is already initialized — then the choice
    # was made before us anyway).
    if ("--xla_force_host_platform_device_count"
            in os.environ.get("XLA_FLAGS", "")
            and not os.environ.get("JAX_PLATFORMS")):
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            if not jax.config.jax_platforms:
                jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
