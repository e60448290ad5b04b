"""The ``--mesh N`` bootstrap of ``chaoscheck``: forcing N host devices
must happen BEFORE jax initializes its backend —
``--xla_force_host_platform_device_count`` in XLA_FLAGS cannot take
effect after import — so the tool re-execs itself once with the flag
set, first thing."""
import os
import sys

_FLAG = "xla_force_host_platform_device_count"


def force_host_devices(n: int) -> None:
    """Re-exec with ``--xla_force_host_platform_device_count=n`` unless
    the environment's XLA_FLAGS already forces at least that many host
    devices (an existing LOWER count gets bumped, not trusted). On a
    real multi-chip host the forced CPU count is inert — jax serves the
    accelerator backend."""
    if n <= 1:
        return
    parts = os.environ.get("XLA_FLAGS", "").split()
    have = 0
    for p in parts:
        if p.startswith(f"--{_FLAG}="):
            try:
                have = int(p.split("=", 1)[1])
            except ValueError:
                have = 0
    if have >= n:
        return  # environment already provides enough host devices
    parts = [p for p in parts if not p.startswith(f"--{_FLAG}=")]
    parts.append(f"--{_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
    # replaces this process before JAX has initialized a backend, so no
    # chip is held across the exec
    os.execv(sys.executable, [sys.executable] + sys.argv)


def force_host_devices_for_mesh() -> None:
    """:func:`force_host_devices` driven by an ``--mesh N`` argv."""
    if "--mesh" not in sys.argv:
        return
    try:
        n = int(sys.argv[sys.argv.index("--mesh") + 1])
    except (IndexError, ValueError):
        return  # argparse rejects it properly later
    force_host_devices(n)
