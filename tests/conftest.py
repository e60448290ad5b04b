"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware isn't available in CI; sharding correctness is
validated on XLA's host platform with 8 virtual devices (the reference
likewise fakes multi-node with multi-process on one box,
tests/multinode_helpers/mpi_wrapper1.sh — here XLA gives us real SPMD
partitioning without processes).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


def assert_blocks_conserved(engine):
    """Post-drain allocator invariant under prefix caching: every block
    still out of the free list is owned by the radix prefix index (warm
    reusable KV), never leaked by a sequence."""
    used = engine.allocator.num_total - engine.allocator.num_free
    assert used == engine.prefix_cache.resident_blocks, (
        used, engine.prefix_cache.snapshot(),
    )


class FakeClock:
    """Virtual time for injectable-clock tests (deadlines, breaker
    recovery windows, SLO burn windows, time-at-pressure). One shared
    definition — the per-file copies diverged silently before."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt
