"""One place for the fake-device XLA environment every test process
hand-rolled before (conftest, the multiproc workers): set
`--xla_force_host_platform_device_count=N` BEFORE jax is first imported,
then pin the platform via jax.config.

Import-order contract: call `ensure_fake_devices` before the first
`import jax` of the process — it imports jax itself only for the config
update, which is safe exactly because the XLA_FLAGS write happened
first.
"""
import os
from typing import Optional


def ensure_fake_devices(count: Optional[int], *, force: bool = False,
                        platform: str = "cpu") -> None:
    """Arrange for `count` fake host devices (`count=None` leaves
    XLA_FLAGS alone — real-hardware runs emulate nothing).

    `force=False` (the conftest pattern) appends the flag only if no
    device-count flag is present, preserving an operator's explicit
    XLA_FLAGS; `force=True` (the multiproc-worker pattern) REPLACES
    XLA_FLAGS wholesale — a spawned worker must not inherit the parent
    pytest process's 8-device setup.
    """
    if count is not None:
        if force:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={count}")
        else:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count={count}"
                ).strip()
    import jax

    jax.config.update("jax_platforms", platform)
