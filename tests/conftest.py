"""Test harness: simulated 8-device CPU mesh.

The TPU-native analog of the reference's staging story (SURVEY.md §4): where
the reference rehearses SMDDP runs with SageMaker local mode + the gloo
backend, these tests run every distributed path on a virtual 8-device CPU
mesh via ``--xla_force_host_platform_device_count`` — no TPU required, same
compiled collectives.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Also pin through the config, in case jax was imported before the
# environment variable was set: backends initialize lazily on first use.
jax.config.update("jax_platforms", "cpu")

assert jax.default_backend() == "cpu", "tests must run on the simulated CPU mesh"
assert jax.device_count() == 8, "simulated 8-device mesh not active"
