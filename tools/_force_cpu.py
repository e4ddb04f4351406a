"""Importing this module pins jax to the CPU platform — the shared
header for host-side tools that must never touch an accelerator. The
env var covers a jax not yet imported; the config API call is made
LOUDLY (a failure here means a backend already initialized and the tool
would otherwise grab it).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
