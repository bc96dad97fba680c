"""Environment-capability gates for tier-1 tests.

A test that needs a capability the installed toolchain lacks should SKIP
with a reason naming the missing capability, not fail — tier-1 must be
green-by-default on every supported image, and a standing red "known
failure" trains everyone to ignore the suite (the round-7 state: three
multiprocess tests red on every CPU-only image).
"""

from __future__ import annotations

import pytest


def jax_version() -> tuple:
    import jax

    parts = []
    for piece in jax.__version__.split(".")[:3]:
        digits = "".join(ch for ch in piece if ch.isdigit())
        parts.append(int(digits) if digits else 0)
    return tuple(parts)


def multiprocess_cpu_mesh_supported() -> bool:
    """True when jax can run MULTI-PROCESS computations on the CPU
    backend (each worker its own OS process, collectives over gloo).

    jax 0.4.x rejects this outright at dispatch ("Multiprocess
    computations aren't implemented on the CPU backend"), so the
    full-launcher tests that spawn one process per worker on a virtual
    CPU mesh cannot pass there; the 0.5+ images (the TPU image's jax)
    run them.  Single-process virtual CPU meshes
    (--xla_force_host_platform_device_count) work everywhere and are NOT
    gated by this."""
    return jax_version() >= (0, 5)


#: decorate tests that launch a multi-host-shaped experiment as one OS
#: process per worker over a CPU mesh
requires_multiprocess_cpu_mesh = pytest.mark.skipif(
    not multiprocess_cpu_mesh_supported(),
    reason="jax < 0.5 cannot run multiprocess computations on the CPU "
    "backend (gloo collectives); the multi-process launch path is "
    "exercised on images with newer jax",
)


def free_port_outside_the_scan() -> int:
    """A port the kernel hands out, for a coordinator that a child process
    binds only seconds later (after it has imported jax).
    ``network.find_free_port`` scans upward from 20000 and returns the
    first port nobody has BOUND, so two tests that ask within those
    seconds of each other are both given 20000, and the second one's
    processes wait out jax's 300 s for a coordinator that is not theirs
    (a whole run of PR 43 lost 5 minutes to it)."""
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]
