"""Small helpers over the installed jax (0.9) that several engines share."""

from __future__ import annotations

import jax


def start_host_copies(arrs) -> bool:
    """Start async device->host copies for every ``jax.Array`` in
    ``arrs`` (``copy_to_host_async``), so a later blocking conversion
    finds the data already host-resident instead of paying one serial
    PCIe round-trip per array.  Returns True iff copies were started;
    arrays that reject the call are a silent no-op — the eventual
    ``device_get`` still fetches, just unhidden."""
    try:
        started = False
        for x in arrs:
            if isinstance(x, jax.Array):
                x.copy_to_host_async()
                started = True
        return started
    except Exception:  # noqa: BLE001 - best-effort prefetch only
        return False
